"""The benchmark's harness: it finds a cell's files by name, builds the
program's train step as ``repro.launch.train.main`` builds it, drives
it through set-up and the measured window, reads the trace, and
compares what the timed step produced with the plain reference.

Files, each found by the name that ``BENCHMARK.json`` gives:

* ``configs/<config>.json``: the model's sizes under the published
  config's keys, every key changed from it (``reduced``: the cuts of
  scale and the program's departures), the sizes assumed, and the
  program architecture it runs as;
* ``traffic/<traffic>.json``: batch, sequence, mesh, sync mode, the
  generator's parameters, the optimizer and the reference's blocking;
* ``limits/<workload>.json``: the limit of each compared number;
* ``metrics/<metric>.py``: one reader per per-layer metric;
* ``flops/<family>.py``: the FLOPs a token needs;
* ``kernels/<kernel>.py``: which trace events are a kernel's calls, and
  the bytes each reads and writes;
* ``reference/<family>.py``: the plain reference;
* ``peaks.json``: the chip's peaks by ``device_kind``.

A configuration of a new family enters by these files alone.  Its file
maps each published key beyond the dense ones of :data:`FIELDS` to the
program's ``ArchConfig`` field under ``program_fields`` (for example
``{"num_experts_per_tok": "top_k"}``); :func:`program_config` applies
every key in ``reduced`` through that map, refuses any mapped key whose
value is not the program's, and refuses a cut of scale
(``why_reduced``) that maps to no field.  The reference and the FLOP
count get :func:`reference_sizes`: the dense sizes as the program runs
them, ``arch`` (every architecture key the file states, under its
published name) and ``published`` (the published value of each key in
``reduced``).  A reference module may name, in ``EXPERT_LEAVES``, the
stacked leaves that carry an expert axis after the layer axis; the
comparison then takes one norm per (layer, expert) of each, since each
expert is a tensor of its own in the published model.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import inspect
import json
import math
import sys
import tempfile
import time
from functools import partial
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parents[1]
GIB = 2.0 ** 30

# published config key -> ArchConfig field of the program
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv", "head_dim": "head_dim",
          "vocab_size": "vocab", "rope_theta": "rope_theta"}
# a configuration file's keys that describe the file, not the model
META = {"name", "source", "program_arch", "family", "architectures",
        "reduced", "published", "why_reduced", "program_departures",
        "assumed", "deployment", "program_fields"}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# files
# ---------------------------------------------------------------------------

def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a benchmark file by path, under a name of its own."""
    name = "chipbench_" + "_".join(path.relative_to(BENCH).with_suffix("")
                                   .parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    metrics: dict          # per-layer metric name -> (entry, module)
    end_to_end: dict       # end-to-end metric name -> entry
    peaks: dict            # the table, keyed by device_kind
    flops: object          # flops/<family>.py
    reference: object      # reference/<family>.py
    kernels: dict          # kernel name -> kernels/<kernel>.py


def load_cell(name: str, bench_json=None) -> Cell:
    """Everything a cell needs, found by name."""
    bench = load_json(bench_json or REPO / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(REPO / conf["file"])
    traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{name}.json")
    metrics = {m["name"]: (m, load_module(BENCH / "metrics" / f"{m['name']}.py"))
               for m in bench["per_layer"]
               if name in m.get("workloads", [name])}
    e2e = {m["name"]: m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])}
    family = config["family"]
    kernels = {p.stem: load_module(p)
               for p in sorted((BENCH / "kernels").glob("*.py"))}
    return Cell(name, w["chips"], config, traffic, limits, metrics, e2e,
                load_json(BENCH / "peaks.json"),
                load_module(BENCH / "flops" / f"{family}.py"),
                load_module(BENCH / "reference" / f"{family}.py"), kernels)


def peaks_for(table: dict, kind: str) -> dict:
    if kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table['devices'])})")
    return table["devices"][kind]


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def field_value(cfg, field: str):
    """An ArchConfig field as the program runs it (``head_dim`` 0 means
    ``d_model / n_heads``)."""
    return cfg.head_dim_ if field == "head_dim" else getattr(cfg, field)


def param_shapes(api):
    """The program's parameter tree as shapes, and its logical axes."""
    import jax
    box = {}

    def init(key):
        params, box["axes"] = api.init(key)
        return params
    key = jax.ShapeDtypeStruct((2,), np.uint32)   # a raw PRNG key's shape
    return jax.eval_shape(init, key), box["axes"]


def head_is_tied(cfg) -> bool:
    """Whether the program's output head is its input embedding: no leaf
    beside ``embed`` and outside the stacked layers is as wide as the
    vocabulary."""
    import jax
    from repro.models.api import build
    shapes, _ = param_shapes(build(cfg))
    return not any(cfg.vocab_padded in leaf.shape
                   for k, sub in shapes.items() if k not in ("embed", "layers")
                   for leaf in jax.tree.leaves(sub))


def program_config(config: dict):
    """The program's ArchConfig for a configuration file.  Keys the file
    lists as reduced replace the program's values, through
    :data:`FIELDS` and the file's ``program_fields``; every other mapped
    size has to equal the program's own, or the file no longer describes
    what runs.  A cut of scale has to reach a field of the program."""
    from repro import configs
    from repro.models import layers
    cfg = configs.get(config["program_arch"])
    fields = {**FIELDS, **config.get("program_fields", {})}
    unknown = set(fields.values()) - {f.name for f in dataclasses.fields(cfg)}
    if unknown:
        raise ValueError(f"{config['name']}: program_fields names no "
                         f"ArchConfig field {sorted(unknown)}")
    lost = set(config["why_reduced"]) - set(fields)
    if lost:
        raise ValueError(f"{config['name']}: the cuts of scale {sorted(lost)} "
                         "reach no field of the program (program_fields)")
    cuts = {fields[k]: config[k] for k in config["reduced"] if k in fields}
    cfg = dataclasses.replace(cfg, **cuts)
    got = {k: field_value(cfg, f) for k, f in fields.items() if k in config}
    want = {k: config[k] for k in got}
    if got != want:
        raise ValueError(f"{config['name']}: program sizes {got} != "
                         f"configuration {want}")
    eps = inspect.signature(layers.rmsnorm).parameters["eps"].default
    checks = {"rms_norm_eps": eps,
              "tie_word_embeddings": head_is_tied(cfg),
              "hidden_act": "silu" if cfg.mlp_kind == "swiglu" else "gelu",
              "attention_bias": cfg.qkv_bias,
              "activation_dtype": cfg.act_dtype_name}
    for k, v in checks.items():
        if k in config and config[k] != v:
            raise ValueError(f"{config['name']}: the program runs {k}={v!r}, "
                             f"the configuration states {config[k]!r}")
    if cfg.vocab_padded != cfg.vocab:
        raise ValueError("the reference has no padded vocabulary rows")
    return cfg


def reference_sizes(cfg, config: dict, traffic: dict) -> dict:
    """What the reference and the FLOP count are given: the dense sizes
    as the program runs them, the reference's blocking, ``arch`` (every
    architecture key the file states) and ``published`` (the published
    value of each key in ``reduced``)."""
    ref = traffic["reference"]
    return {"d_model": cfg.d_model, "n_heads": cfg.n_heads, "n_kv": cfg.n_kv,
            "head_dim": cfg.head_dim_, "d_ff": cfg.d_ff, "vocab": cfg.vocab,
            "n_layers": cfg.n_layers, "rope_theta": float(cfg.rope_theta),
            "rms_norm_eps": float(config["rms_norm_eps"]),
            "z_loss": float(traffic["optimizer"]["z_loss"]),
            "query_block": ref["query_block"], "head_chunk": ref["head_chunk"],
            "arch": {k: v for k, v in config.items() if k not in META},
            "published": config["published"]}


def jit_step(step_fn, pshard, oshard):
    """The step jitted as ``repro.launch.train.main`` jits it."""
    import jax
    return jax.jit(step_fn, donate_argnums=(0, 1),
                   out_shardings=(pshard, oshard, None))


@dataclasses.dataclass
class Program:
    cfg: object
    mesh: object
    jstep: object
    weights: object        # seed key -> params, on the device
    opt_init: object       # params -> optimizer state, on the device
    sizes: dict            # the reference's sizes


def build_program(cell: Cell) -> Program:
    """The train step as ``repro.launch.train.main`` builds it."""
    import jax
    from repro.dist import sharding as shd
    from repro.dist.steps import make_train_step
    from repro.launch.mesh import make_mesh
    from repro.models import layers
    from repro.models.api import build
    from repro.optim import AdamW, cosine_schedule

    t, o = cell.traffic, cell.traffic["optimizer"]
    cfg = program_config(cell.config)
    z = inspect.signature(layers.chunked_unembed_xent).parameters["z_loss"]
    if z.default != o["z_loss"]:
        raise ValueError(f"the program's z-loss is {z.default}, the traffic "
                         f"file states {o['z_loss']}")
    api = build(cfg)
    mesh = make_mesh(tuple(t["mesh"]), tuple(t["axes"]))
    opt = AdamW(cosine_schedule(o["lr"], o["warmup"], o["total_steps"]),
                b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])
    if o["decay_min_rank"] != 2:
        raise ValueError("the program decays every stored array of rank 2 "
                         "or more")
    sizes = reference_sizes(cfg, cell.config, t)
    key = jax.ShapeDtypeStruct((2,), np.uint32)   # a raw PRNG key's shape
    with jax.set_mesh(mesh):
        shapes, axes = param_shapes(api)
        pshard, oshard = shd.train_state_shardings(axes, shapes, mesh)
        mine = jax.eval_shape(partial(cell.reference.make_weights, sizes),
                              key)
        if jax.tree.structure(mine) != jax.tree.structure(shapes) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(mine), jax.tree.leaves(shapes))):
            raise ValueError("the reference's parameter layout is not the "
                             "program's")
        step_fn = make_train_step(api, opt, mesh, mode=t["sync"],
                                  quantize=t["quantize_grads"],
                                  engine=t["engine"])
        jstep = jit_step(step_fn, pshard, oshard)
        weights = jax.jit(partial(cell.reference.make_weights, sizes),
                          out_shardings=pshard)
        opt_init = jax.jit(opt.init, out_shardings=oshard)
    return Program(cfg, mesh, jstep, weights, opt_init, sizes)


def weight_key(seed: int):
    """The weights' key: a child of the run's seed (any whole number)."""
    import jax
    import jax.numpy as jnp
    data = np.random.SeedSequence([seed, 13]).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(data))


# ---------------------------------------------------------------------------
# driving the step
# ---------------------------------------------------------------------------

def drive(jstep, state, pool, first, *, count=None, deadline=None):
    """The loop of ``repro.launch.train.main`` without recovery: the next
    batch onto the device with ``jnp.asarray``, the jitted step, the
    loss read back.  Runs ``count`` steps, or steps until ``deadline``
    (``time.perf_counter``); every step of set-up and of the window goes
    through here.  Returns ``(state, losses, last metrics, next index)``.
    """
    import jax
    import jax.numpy as jnp
    ann = jax.profiler.TraceAnnotation
    params, opt_state = state
    i, losses, metrics = first, [], None
    while (count is None or len(losses) < count) and \
            (deadline is None or time.perf_counter() < deadline):
        with ann("bench/step"):
            with ann("bench/input"):
                batch = {"tokens": jnp.asarray(pool[i % len(pool)])}
            with ann("bench/dispatch"):
                params, opt_state, metrics = jstep(params, opt_state, batch)
            with ann("bench/readback"):
                losses.append(float(metrics["loss"]))
        i += 1
    return (params, opt_state), losses, metrics, i


def leaf_norms(cell: Cell):
    """``correctness.slice_norms`` with the reference's expert leaves."""
    from correctness import slice_norms
    return partial(slice_norms,
                   experts=getattr(cell.reference, "EXPERT_LEAVES", ()))


def first_steps(prog: Program, cell: Cell, pool, key):
    """Set-up: weights from the seed, then the first steps through
    :func:`drive`, recording what the comparison needs.  Returns
    ``(state, record, next index)``; the state goes on to the window."""
    import jax
    import jax.numpy as jnp
    from correctness import flatten
    o = cell.traffic["optimizer"]
    n = cell.traffic["reference"]["steps"]
    norms = leaf_norms(cell)
    with jax.set_mesh(prog.mesh):
        params = prog.weights(key)
        state = (params, prog.opt_init(params))
        state, losses, m1, i = drive(prog.jstep, state, pool, 0, count=1)
        # Adam's first moment after one step is (1 - b1) times the
        # clipped gradient; undo both to get the gradient as it came in
        gn = float(m1["grad_norm"])
        clip = min(1.0, o["clip_norm"] / (gn + 1e-9))
        mu = jax.device_get(jax.jit(norms)(state[1].mu))
        grad = {k: v / ((1.0 - o["b1"]) * clip)
                for k, v in flatten(mu).items()}
        state, more, _, i = drive(prog.jstep, state, pool, i, count=n - 1)
        losses += more
        change = jax.jit(lambda p, k: norms(
            jax.tree.map(jnp.subtract, p, prog.weights(k))))
        update = flatten(jax.device_get(change(state[0], key)))
    return state, {"losses": losses, "grad": grad, "update": update}, i


def reference_record(cell: Cell, prog: Program, pool, key, *,
                     precision="f32", rows=None):
    """The reference (or its control, or a fault put in its place) over
    the batches of the first steps."""
    from correctness import flatten
    r = cell.traffic["reference"]
    losses, grad, update = cell.reference.train(
        prog.sizes, cell.traffic["optimizer"], key, list(pool[:r["steps"]]),
        precision=precision, rows=rows, rows_per_block=r["rows_per_block"],
        norms=leaf_norms(cell))
    return {"losses": losses, "grad": flatten(grad),
            "update": flatten(update)}


@contextlib.contextmanager
def count_compiles():
    """Counts tracing and compiling (persistent-cache reads included)
    while the block runs."""
    from jax import monitoring
    box = {"n": 0, "on": True}

    def listen(name, *args, **kwargs):
        if box["on"] and name.startswith(("/jax/core/compile/",
                                          "/jax/compilation_cache/compile_")):
            box["n"] += 1
    monitoring.register_event_listener(listen)
    monitoring.register_event_duration_secs_listener(listen)
    try:
        yield box
    finally:
        box["on"] = False


def device_memory(devices) -> int:
    """The fullest chip's peak: buffers in use plus the reservation for
    compiled programs' temporaries, which ``peak_bytes_in_use`` alone
    leaves out on a TPU."""
    peak = 0
    for d in devices:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    return peak


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def use_compile_cache():
    """JAX's persistent cache where the program keeps it (a fixed
    directory in the checkout, or ``$JAX_COMPILATION_CACHE_DIR``), for
    every program of a run however quick to compile, so that only a
    checkout's first run compiles."""
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def chips_or_fail(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX sees {len(devs)} {devs[0].platform} "
                     "device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True):
    """One run of one cell: ``(result line, compared numbers)``."""
    import jax
    from correctness import compare, judge
    import token_generator

    devs = chips_or_fail(cell.chips, require_chip)
    use_compile_cache()
    prog = build_program(cell)
    used = list(prog.mesh.devices.flat)
    if len(used) != cell.chips:
        raise ValueError(f"traffic mesh has {len(used)} devices, the cell "
                         f"asks for {cell.chips}")
    t = cell.traffic
    pool = token_generator.pool_for(t, prog.cfg.vocab, seed)
    key = weight_key(seed)
    state, prog_rec, i = first_steps(prog, cell, pool, key)

    tdir = tempfile.TemporaryDirectory() if trace else None
    with jax.set_mesh(prog.mesh), count_compiles() as compiles:
        if trace:
            jax.profiler.start_trace(tdir.name)
        t0 = time.perf_counter()
        state, losses, _, _ = drive(prog.jstep, state, pool, i,
                                    deadline=t0 + seconds)
        jax.block_until_ready(state)
        t1 = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
    if compiles["n"]:
        raise RuntimeError(f"{compiles['n']} trace/compile events inside the "
                           "measured window")
    steps = len(losses)
    tokens_per_s = steps * t["batch"] * t["seq"] / (t1 - t0)
    mem = device_memory(used)
    kind = devs[0].device_kind
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(used), "memory_peak_bytes": mem}

    metrics, breakdown = {}, None
    if trace:
        import trace_reduce
        with jax.set_mesh(prog.mesh):   # the same program: a cache hit
            hlo = prog.jstep.lower(*state, {"tokens": pool[0]}) \
                .compile().as_text()
        path = next(Path(tdir.name).rglob("*.xplane.pb"))
        rec = trace_reduce.reduce_trace(str(path), [hlo])
        tdir.cleanup()
        ctx = MetricContext(rec, cell, prog, tokens_per_s,
                            peaks_for(cell.peaks, kind) if require_chip
                            else None)
        for name, (entry, mod) in cell.metrics.items():
            v = mod.read(ctx)
            if v is not None:
                metrics[name] = {"value": float(v), "unit": entry["unit"]}
        busy = [rec.busy_ns(d) / 1e9 for d in rec.devices]
        device["busy_s"] = float(np.mean(busy))
        device["window_s"] = rec.window_ns / 1e9
        dev = rec.busiest()
        breakdown = {"device_ops": trace_reduce.top_ops(rec, dev),
                     "idle_gaps": trace_reduce.idle_gaps(rec, dev)}
    else:
        values = {"tokens_per_s": tokens_per_s, "peak_hbm_gib": mem / GIB,
                  "setup_s": t0 - t_start}
        for name, entry in cell.end_to_end.items():
            metrics[name] = {"value": float(values[name]),
                             "unit": entry["unit"]}

    # the program's state goes before the reference runs
    del state
    ref = reference_record(cell, prog, pool, key)
    numbers = compare(prog_rec, ref)
    failed = sum(1 for x in losses if not math.isfinite(x))
    ok, checks = judge(numbers, cell.limits)
    result = {"correct": bool(ok and failed == 0), "attempted": steps,
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result, numbers


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric file reads."""
    trace: object           # trace_reduce.TraceRecord of the window
    cell: Cell
    program: Program
    tokens_per_s: float     # over the traced window, on the host clock
    peaks: dict | None      # this chip's row of peaks.json

    @property
    def flops_per_token(self) -> float:
        return self.cell.flops.flops_per_token(self.program.sizes,
                                               self.cell.traffic["seq"])
