"""In-graph fault *detection* for the EDST collective engines.

:mod:`repro.dist.fault` can recover from failures it is told about -- a
``FailureEvent`` flips a traced schedule id -- but nothing in the runtime
*produced* those events: the drills injected them by hand.  This module
closes the sensing half of the loop (detect -> classify -> escalate ->
recover -> verify; the escalation ladder lives in
:mod:`repro.dist.recovery`):

  * **link heartbeat probes** -- every directed link any compiled wave
    program uses (extracted from the spec's own routing tables, so the
    probe covers exactly the fabric the collective depends on) is echoed
    with a tiny one-element ``ppermute``.  The sender ships ``rank + 1``;
    the receiver compares against the statically-known expected sender
    (``ppermute`` zero-fills devices nobody sent to, so a dead wire reads
    0 and can never alias a healthy token).  Results scatter into a
    global ``(L,)`` link-OK bitmap shared via ``psum`` -- a handful of
    scalar collectives, cheap enough to run between steps.
  * **payload checksums** -- after a gradient allreduce every replica
    must hold bit-identical sums; :func:`replication_divergence` measures
    the cross-replica spread of a (sum, sum-of-squares) checksum in-graph,
    catching corrupt-wire faults that no schedule switch can see.  The
    striped/ZeRO-1 engines scatter instead of replicate, so their
    integrity check is conservation, not replication -- see
    :func:`repro.dist.striped.rs_conservation_gap`.
  * **straggler detection** -- wall-clock per-step times against a rolling
    median (:class:`StragglerDetector`): a step slower than
    ``ratio x median`` flags a straggling fabric without any schedule
    knowledge.

:class:`HealthMonitor` bundles the three detectors behind one
``check(step, ...)`` call returning a :class:`HealthReport`; the report's
``failed_edges()`` / ``node_suspects()`` are what
:class:`repro.dist.recovery.RecoveryController` classifies into
``FailureEvent``s.  The probe takes a traced ``(L,)`` ``fault_mask`` so
the chaos harness (:mod:`repro.dist.chaos`) can inject wire faults at
the telemetry boundary without retracing -- on a real fabric the mask
stays all-ones and dead wires zero the bitmap by themselves.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..analysis.verify import engine_of
from ..core.graph import canon
from ..telemetry import metrics as _metrics


# ---------------------------------------------------------------------------
# link extraction: the probe plan is compiled from the routing tables
# ---------------------------------------------------------------------------

def program_links(spec) -> tuple:
    """Sorted directed ``(src, dst)`` links the compiled wave program
    moves payload over, for any engine's spec form.  Read from the same
    routing tables the executors run, so the probe set is exactly the
    fabric surface the collective depends on."""
    eng = engine_of(spec)
    links = set()
    if eng in ("pipelined", "striped"):
        for wv in spec.waves:
            links.update((int(s), int(d)) for s, d in wv.perm)
    elif eng == "fused":
        for rnd in tuple(spec.reduce_rounds) + tuple(spec.bcast_rounds):
            links.update((int(s), int(d)) for s, d in rnd.perm)
    else:  # per_tree
        for tp in spec.trees:
            for perm in tuple(tp.reduce_rounds) + tuple(tp.bcast_rounds):
                links.update((int(s), int(d)) for s, d in perm)
    return tuple(sorted(links))


def runtime_links(runtime) -> tuple:
    """Union of :func:`program_links` over every precompiled failure
    class of a :class:`repro.dist.fault.FaultAwareAllreduce` -- one probe
    plan covers every schedule the runtime can flip to, so probing never
    retraces on failover."""
    links = set()
    for e in runtime.entries:
        if e.k > 0:
            links.update(program_links(e.spec))
    return tuple(sorted(links))


def _pack_probe_waves(links) -> tuple:
    """Greedy split of the directed links into ppermute-legal waves
    (unique sources AND unique destinations per wave)."""
    remaining = list(links)
    waves = []
    while remaining:
        srcs, dsts, take, rest = set(), set(), [], []
        for s, d in remaining:
            if s not in srcs and d not in dsts:
                take.append((s, d))
                srcs.add(s)
                dsts.add(d)
            else:
                rest.append((s, d))
        waves.append(tuple(take))
        remaining = rest
    return tuple(waves)


@dataclass(frozen=True, eq=False)
class LinkProbeSpec:
    """Compiled heartbeat plan: ``links[i]`` is the directed link that
    owns bitmap slot ``i``; each wave carries per-vertex expected-sender
    and slot tables (-1 = this vertex receives nothing that wave)."""
    n: int
    axes: tuple
    links: tuple               # ((src, dst), ...) sorted
    waves: tuple               # tuple[tuple[(src, dst)]], ppermute-legal
    recv_src: tuple            # tuple[np.ndarray (n,)], expected sender
    recv_slot: tuple           # tuple[np.ndarray (n,)], bitmap slot

    @property
    def num_links(self) -> int:
        return len(self.links)


def compile_link_probe(spec_or_runtime) -> LinkProbeSpec:
    """Build the heartbeat plan for a compiled spec or a fault runtime
    (the union of its failure classes -- see :func:`runtime_links`)."""
    if hasattr(spec_or_runtime, "entries"):   # FaultAwareAllreduce
        links = runtime_links(spec_or_runtime)
        n = spec_or_runtime.graph.n
        axes = tuple(spec_or_runtime.axes)
    else:
        links = program_links(spec_or_runtime)
        n = spec_or_runtime.n
        axes = tuple(spec_or_runtime.axes)
    slot = {l: i for i, l in enumerate(links)}
    waves = _pack_probe_waves(links)
    recv_src, recv_slot = [], []
    for wave in waves:
        src = np.full(n, -1, np.int32)
        slt = np.full(n, -1, np.int32)
        for s, d in wave:
            src[d] = s
            slt[d] = slot[(s, d)]
        recv_src.append(src)
        recv_slot.append(slt)
    return LinkProbeSpec(n=n, axes=axes, links=links, waves=waves,
                         recv_src=tuple(recv_src),
                         recv_slot=tuple(recv_slot))


def make_link_probe(spec_or_runtime):
    """``(probe, plan)``: ``probe(fault_mask)`` runs under ``shard_map``
    over the plan's axes and returns the global ``(L,)`` link-OK bitmap
    (1.0 = echo arrived intact).  ``fault_mask`` is a traced ``(L,)``
    vector ANDed onto the receive path -- the chaos injection point; pass
    ones on a real fabric."""
    plan = compile_link_probe(spec_or_runtime)
    axis = plan.axes[0] if len(plan.axes) == 1 else tuple(plan.axes)
    L = plan.num_links

    def probe(fault_mask):
        idx = jax.lax.axis_index(axis)
        token = (idx + 1).astype(jnp.float32)[None]
        # slot L is the spill row for non-receivers (-1 -> L), cut at the end
        bitmap = jnp.zeros(L + 1, jnp.float32)
        for w, wave in enumerate(plan.waves):
            recv = jax.lax.ppermute(token, axis, wave)[0]
            expect = jnp.asarray(plan.recv_src[w])[idx].astype(jnp.float32)
            slot = jnp.asarray(plan.recv_slot[w])[idx]
            ok = jnp.where(slot >= 0, (recv == expect + 1.0), 0.0)
            ok = ok * jnp.where(slot >= 0, fault_mask[jnp.clip(slot, 0)], 0.0)
            bitmap = bitmap.at[jnp.where(slot >= 0, slot, L)].add(
                ok.astype(jnp.float32))
        return jax.lax.psum(bitmap[:L], axis)

    return probe, plan


def mesh_link_probe(mesh, spec_or_runtime):
    """Jitted driver-side heartbeat: returns ``(run, plan)`` where
    ``run(fault_mask=None) -> np.ndarray (L,) of {0., 1.}`` executes the
    probe on ``mesh`` (mask defaults to all-ones)."""
    probe, plan = make_link_probe(spec_or_runtime)
    fn = jax.jit(jax.shard_map(probe, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    ones = np.ones(plan.num_links, np.float32)

    def run(fault_mask=None):
        mask = ones if fault_mask is None else fault_mask
        return jax.device_get(fn(jnp.asarray(mask, jnp.float32)))

    return run, plan


# ---------------------------------------------------------------------------
# payload checksums (corrupt-wire detection)
# ---------------------------------------------------------------------------

def payload_checksum(x) -> jnp.ndarray:
    """(2,) traced checksum of a payload: (sum, sum of squares) in f32.
    Cheap, order-independent, and any single-element corruption moves at
    least one component."""
    flat = x.astype(jnp.float32).reshape(-1)
    return jnp.stack([jnp.sum(flat), jnp.sum(flat * flat)])


def replication_divergence(chk, axis) -> jnp.ndarray:
    """Cross-replica spread of a per-device checksum under ``shard_map``:
    0.0 when every replica holds identical payload (the allreduce
    postcondition), > 0 when a corrupt wire broke replication."""
    return jnp.max(jax.lax.pmax(chk, axis) - jax.lax.pmin(chk, axis))


# ---------------------------------------------------------------------------
# straggler detection (wall-clock quantiles)
# ---------------------------------------------------------------------------

class StragglerDetector:
    """Rolling-median step-time monitor: ``observe(dt)`` returns True when
    ``dt`` exceeds ``ratio`` times the median of the last ``window``
    healthy samples (flagged samples stay out of the baseline so a
    sustained straggler cannot normalize itself)."""

    def __init__(self, window: int = 32, ratio: float = 2.5,
                 min_samples: int = 5):
        self.window = int(window)
        self.ratio = float(ratio)
        self.min_samples = int(min_samples)
        self._times = collections.deque(maxlen=self.window)

    def baseline(self) -> float:
        if not self._times:
            return 0.0
        return float(np.median(self._times))

    def observe(self, dt: float) -> bool:
        if len(self._times) >= self.min_samples \
                and dt > self.ratio * self.baseline():
            return True
        self._times.append(float(dt))
        return False


# ---------------------------------------------------------------------------
# the bundled monitor
# ---------------------------------------------------------------------------

@dataclass
class HealthReport:
    """One detection tick: raw bitmap plus the derived classifications
    the recovery controller consumes."""
    step: int
    links: tuple                      # directed (src, dst) per bitmap slot
    link_ok: np.ndarray               # (L,) bool
    checksum_dev: float = 0.0
    checksum_tol: float = 1e-3
    step_time: float | None = None
    straggler: bool = False
    extras: dict = field(default_factory=dict)

    @property
    def all_links_ok(self) -> bool:
        return bool(self.link_ok.all())

    @property
    def checksum_ok(self) -> bool:
        return self.checksum_dev <= self.checksum_tol

    def failed_directed(self) -> tuple:
        return tuple(l for l, ok in zip(self.links, self.link_ok) if not ok)

    def failed_edges(self) -> frozenset:
        """Canonical undirected edges with at least one dead direction."""
        return frozenset(canon(s, d) for s, d in self.failed_directed())

    def node_suspects(self) -> frozenset:
        """Vertices whose EVERY probed link (both directions) is dead --
        the link-level signature of a lost node."""
        incident: dict = {}
        for (s, d), ok in zip(self.links, self.link_ok):
            for v in (s, d):
                alive, total = incident.get(v, (0, 0))
                incident[v] = (alive + bool(ok), total + 1)
        return frozenset(v for v, (alive, total) in incident.items()
                         if total > 0 and alive == 0)


class HealthMonitor:
    """Driver-side bundle of the three detectors for one mesh + runtime.

    ``check(step, fault_mask=, step_time=, checksum_dev=)`` runs the
    heartbeat probe and folds in the caller-measured step time and
    checksum divergence (the in-graph divergence is computed by the train
    step's telemetry -- see ``make_train_step(telemetry=True)``)."""

    def __init__(self, mesh, spec_or_runtime,
                 straggler: StragglerDetector | None = None,
                 checksum_tol: float = 1e-3):
        self.probe, self.plan = mesh_link_probe(mesh, spec_or_runtime)
        self.straggler = straggler or StragglerDetector()
        self.checksum_tol = float(checksum_tol)

    @property
    def links(self) -> tuple:
        return self.plan.links

    def check(self, step: int, fault_mask=None, step_time: float | None = None,
              checksum_dev: float = 0.0) -> HealthReport:
        bitmap = self.probe(fault_mask)
        slow = (step_time is not None
                and self.straggler.observe(float(step_time)))
        report = HealthReport(step=step, links=self.plan.links,
                              link_ok=np.asarray(bitmap) > 0.5,
                              checksum_dev=float(checksum_dev),
                              checksum_tol=self.checksum_tol,
                              step_time=step_time, straggler=slow)
        n_failed = int((~report.link_ok).sum())
        _metrics.counter("edst_health_checks_total",
                         "heartbeat/checksum/straggler detection ticks"
                         ).inc()
        if n_failed:
            _metrics.counter("edst_probe_failures_total",
                             "directed links that failed a heartbeat probe"
                             ).inc(n_failed)
        _metrics.gauge("edst_failed_links",
                       "directed links failing the latest probe"
                       ).set(n_failed)
        if not report.checksum_ok:
            _metrics.counter("edst_checksum_failures_total",
                             "payload checksum divergences past tolerance"
                             ).inc()
        if slow:
            _metrics.counter("edst_straggler_flags_total",
                             "steps flagged as stragglers").inc()
        return report
