"""End-to-end behaviour: training loop convergence, checkpoint/restart
determinism, data pipeline determinism + host sharding, optimizer,
sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.ckpt import latest_step, restore, save_checkpoint
from repro.data import SyntheticLMStream
from repro.dist.sharding import spec_for
from repro.launch.mesh import make_mesh
from repro.launch.train import main as train_main
from repro.optim import AdamW, cosine_schedule, global_norm_clip


def test_training_loss_decreases(tmp_path):
    losses = train_main(["--arch", "smollm-135m", "--reduced", "--steps", "40",
                         "--batch", "8", "--seq", "96", "--mesh", "1,1",
                         "--log-every", "100"])
    assert losses[-1] < losses[0]


def test_checkpoint_restart_bit_identical(tmp_path):
    d = str(tmp_path / "ck")
    args = ["--arch", "smollm-135m", "--reduced", "--batch", "4",
            "--seq", "64", "--mesh", "1,1", "--ckpt-dir", d,
            "--log-every", "100"]
    # run 20 steps straight through
    full = train_main(args + ["--steps", "20", "--ckpt-every", "10000"])
    # run 10, checkpoint, resume to 20
    import shutil
    shutil.rmtree(d, ignore_errors=True)
    train_main(args + ["--steps", "10", "--ckpt-every", "10000"])
    assert latest_step(d) == 10
    resumed = train_main(args + ["--steps", "20", "--ckpt-every", "10000"])
    np.testing.assert_allclose(resumed[-1], full[-1], atol=1e-4)


def test_data_determinism_and_host_sharding():
    s1 = SyntheticLMStream(100, 32, 8, seed=3)
    s2 = SyntheticLMStream(100, 32, 8, seed=3)
    np.testing.assert_array_equal(s1.batch(7), s2.batch(7))
    assert not np.array_equal(s1.batch(7), s1.batch(8))
    # 2-host sharding tiles the global batch disjointly & deterministically
    h0 = SyntheticLMStream(100, 32, 8, seed=3, n_hosts=2, host_id=0)
    h1 = SyntheticLMStream(100, 32, 8, seed=3, n_hosts=2, host_id=1)
    b0, b1 = h0.batch(5), h1.batch(5)
    assert b0.shape == (4, 33) and b1.shape == (4, 33)
    assert not np.array_equal(b0, b1)


def test_adamw_and_clip():
    opt = AdamW(cosine_schedule(1e-2, 2, 50))
    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    state = opt.init(params)
    grads = {"w": jnp.full((4, 4), 100.0), "b": jnp.ones((4,))}
    clipped, gn = global_norm_clip(grads, 1.0)
    assert float(gn) > 1.0
    norm_after = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(clipped)))
    assert float(norm_after) == pytest.approx(1.0, rel=1e-5)
    p2, s2, m = opt.apply(params, grads, state)
    assert not jnp.allclose(p2["w"], params["w"])
    assert int(s2.step) == 1


def test_sharding_rules_divisibility():
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("data", "model")

        class devices:
            shape = (16, 16)

    m = FakeMesh()
    # heads=28 not divisible by 16 -> falls through to head_dim
    spec = spec_for(("embed", "heads", "head_dim"), (3584, 28, 128), m)
    assert spec == jax.sharding.PartitionSpec("data", None, "model")
    # vocab padded divisible
    spec = spec_for(("vocab", "embed"), (152064, 3584), m)
    assert spec == jax.sharding.PartitionSpec("model", "data")
    # experts win priority over mlp
    spec = spec_for(("experts", "embed", "mlp"), (64, 2048, 1024), m)
    assert spec[0] == "model"
    # batch=1 (long_500k) stays replicated
    spec = spec_for(("batch", None), (1, 7), m, fsdp=False)
    assert spec == jax.sharding.PartitionSpec(None, None)


def test_checkpoint_atomic_layout(tmp_path):
    d = str(tmp_path)
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "n": {"b": jnp.ones(4)}}
    save_checkpoint(d, 3, tree)
    save_checkpoint(d, 7, tree)
    save_checkpoint(d, 9, tree)   # keeps two most recent
    assert latest_step(d) == 9
    steps = sorted(int(x[5:]) for x in os.listdir(d) if x.startswith("step_"))
    assert steps == [7, 9]
    restored, step, _ = restore(d, tree)
    assert step == 9
    np.testing.assert_allclose(np.asarray(restored["a"]), np.asarray(tree["a"]))


def test_arch_registry_complete():
    assert len(configs.ARCHS) == 10
    for name, cfg in configs.ARCHS.items():
        assert cfg.name == name
        assert cfg.param_count() > 0
        r = cfg.reduced()
        assert r.n_layers <= 4 and r.d_model <= 256
        # skip bookkeeping: long_500k only runs for sub-quadratic archs
        if cfg.family in ("rglru", "rwkv6"):
            assert "long_500k" not in cfg.skip_shapes
        else:
            assert "long_500k" in cfg.skip_shapes and cfg.skip_reason


def test_serve_driver_smoke():
    from repro.launch.serve import main as serve_main
    gen = serve_main(["--arch", "smollm-135m", "--reduced", "--batch", "2",
                      "--prompt-len", "8", "--gen", "4"])
    assert gen.shape == (2, 4)


def test_grad_accumulation_matches_full_batch():
    """grad_accum=N == single-step on the same global batch."""
    from repro.models.api import build
    from repro.dist.steps import make_train_step
    cfg = configs.get("smollm-135m").reduced()
    api = build(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    opt = AdamW(cosine_schedule(1e-3, 5, 50))
    params, _ = api.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 65), 0,
                                          cfg.vocab)}
    with jax.set_mesh(mesh):
        p1, _, m1 = jax.jit(make_train_step(api, opt, mesh))(
            params, opt_state, batch)
        p4, _, m4 = jax.jit(make_train_step(api, opt, mesh, grad_accum=4))(
            params, opt_state, batch)
    assert abs(float(m1["loss"]) - float(m4["loss"])) < 1e-3
    diff = max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4)))
    assert diff < 5e-3, diff


def test_elastic_reshard_across_meshes(tmp_path):
    """Checkpoint on one mesh, resume on a different mesh: params identical,
    EDST schedule rebuilt for the new fabric."""
    from repro.launch.elastic import rebuild_schedule, reshard_checkpoint
    from repro.models.api import build
    d = str(tmp_path / "ck")
    cfg = configs.get("smollm-135m").reduced()
    api = build(cfg)
    opt = AdamW(cosine_schedule(3e-4, 10, 100))
    train_main(["--arch", "smollm-135m", "--reduced", "--steps", "4",
                "--batch", "4", "--seq", "48", "--mesh", "1,1",
                "--ckpt-dir", d, "--ckpt-every", "4", "--log-every", "100"])
    mesh2 = make_mesh((1, 1), ("data", "model"))
    params, opt_state, step = reshard_checkpoint(api, opt, d, mesh2)
    assert step == 4
    assert int(opt_state.step) == 4
    # single-data-shard mesh: no DP fabric, nothing to sync
    assert rebuild_schedule(make_mesh((1, 1), ("data", "model"))) is None


# ---------------------------------------------------------------------------
# ZeRO-1 sharded checkpoints (owner-stripe save / re-shard restore)
# ---------------------------------------------------------------------------

def test_flatten_prefix_keys_never_collide():
    """Regression: "/"-joined flat keys used to collide for trees like
    {"a": {"b/c": x}} vs {"a/b": {"c": x}} -- one silently clobbered the
    other in the npz.  Keys are now percent-escaped per level."""
    from repro.ckpt.checkpoint import _flatten, _unflatten_into
    tree = {"a": {"b/c": np.ones(2)}, "a/b": {"c": np.zeros(2)},
            "pct%": {"x": np.full(2, 3.0)}}
    flat = _flatten(tree)
    assert len(flat) == 3
    back = _unflatten_into(tree, flat)
    assert back["a"]["b/c"][0] == 1.0
    assert back["a/b"]["c"][0] == 0.0
    assert back["pct%"]["x"][0] == 3.0


def _zero1_fixture(m=53):
    from repro.core.collectives import owner_element_map
    from repro.dist.steps import edst_spec_for_mesh
    from repro.optim import ShardedOptState
    spec = edst_spec_for_mesh((16, 1), ("data", "model"), (4, 4),
                              engine="striped")
    emap = owner_element_map(spec, m)
    rng = np.random.RandomState(5)
    params = {"w": jnp.asarray(rng.randn(6, 8), jnp.float32),
              "b": jnp.asarray(rng.randn(5), jnp.float32)}
    mu = jnp.asarray(np.where(emap >= 0, rng.randn(*emap.shape), 0.0),
                     jnp.float32)
    nu = jnp.asarray(np.where(emap >= 0, rng.rand(*emap.shape), 0.0),
                     jnp.float32)
    state = ShardedOptState(jnp.asarray(9, jnp.int32), mu, nu)
    return spec, emap, params, state


def _reassemble(stacks, emap, m):
    flat = np.zeros(m, np.float32)
    live = np.asarray(emap) >= 0
    flat[np.asarray(emap)[live]] = np.asarray(stacks)[live]
    return flat


def test_sharded_checkpoint_roundtrip_bitwise(tmp_path):
    """Same fabric: per-host stripe shards re-assemble bit-identical,
    params/step/extra survive, and the step dir holds one shard file per
    owner host next to the replicated arrays."""
    from repro.ckpt import restore_sharded, save_sharded_checkpoint
    m = 53
    spec, emap, params, state = _zero1_fixture(m)
    d = str(tmp_path / "zck")
    final = save_sharded_checkpoint(d, 7, params, state, emap, m,
                                    extra={"tokens": 123})
    names = sorted(os.listdir(final))
    assert "arrays.npz" in names and "manifest.json" in names
    assert sum(nm.startswith("shard_") for nm in names) == spec.n
    p2, st2, step, extra = restore_sharded(d, params, emap)
    assert step == 7 and extra == {"tokens": 123}
    assert int(st2.step) == 9
    assert np.array_equal(np.asarray(st2.mu), np.asarray(state.mu))
    assert np.array_equal(np.asarray(st2.nu), np.asarray(state.nu))
    for k in params:
        assert np.array_equal(np.asarray(p2[k]), np.asarray(params[k]))


def test_sharded_checkpoint_reshards_to_degraded_fabric(tmp_path):
    """A checkpoint taken on the healthy k-tree fabric restores onto the
    re-striped k-1 (retired-tree) ownership map: different (kmax, smax)
    geometry, same flat moments."""
    from repro.ckpt import restore_sharded, save_sharded_checkpoint
    from repro.core.collectives import owner_element_map
    m = 53
    spec, emap, params, state = _zero1_fixture(m)
    d = str(tmp_path / "zck")
    save_sharded_checkpoint(d, 4, params, state, emap, m)
    fr = tuple(1.0 if j == 0 else 0.0 for j in range(spec.k))
    emap2 = owner_element_map(spec, m, fr)
    assert np.asarray(emap2).shape != np.asarray(emap).shape
    p3, st3, step, _ = restore_sharded(d, params, emap2)
    assert step == 4
    np.testing.assert_allclose(_reassemble(st3.mu, emap2, m),
                               _reassemble(state.mu, emap, m), rtol=0)
    np.testing.assert_allclose(_reassemble(st3.nu, emap2, m),
                               _reassemble(state.nu, emap, m), rtol=0)


def test_sharded_checkpoint_detects_torn_shard(tmp_path):
    """S3: every stripe shard's CRC32 is recorded in the manifest and
    verified on restore -- a single flipped byte in one host's shard file
    fails the restore loudly, naming the torn file, instead of silently
    loading corrupt optimizer moments; restoring the original bytes
    succeeds again."""
    from repro.ckpt import restore_sharded, save_sharded_checkpoint
    m = 53
    spec, emap, params, state = _zero1_fixture(m)
    d = str(tmp_path / "zck")
    final = save_sharded_checkpoint(d, 7, params, state, emap, m)
    shard = os.path.join(final, "shard_00007.npz")
    with open(shard, "rb") as f:
        blob = bytearray(f.read())
    blob[len(blob) // 2] ^= 0xFF
    with open(shard, "wb") as f:
        f.write(blob)
    with pytest.raises(ValueError, match="shard_00007"):
        restore_sharded(d, params, emap)
    # untearing the file restores a loadable checkpoint
    blob[len(blob) // 2] ^= 0xFF
    with open(shard, "wb") as f:
        f.write(blob)
    _, st2, step, _ = restore_sharded(d, params, emap)
    assert step == 7
    assert np.array_equal(np.asarray(st2.mu), np.asarray(state.mu))
