"""Per-kernel interpret-mode validation: shape/dtype sweeps vs jnp oracles."""
import jax
import jax.numpy as jnp
import pytest

KEY = jax.random.PRNGKey(0)


def rand(shape, dtype=jnp.float32, k=0):
    return jax.random.normal(jax.random.PRNGKey(k), shape, jnp.float32).astype(dtype)


# -- flash attention ----------------------------------------------------------

FLASH_CASES = [
    # b, s, h, kv, d, qb, kb, causal, window
    (2, 128, 8, 2, 64, 32, 64, True, None),
    (1, 100, 4, 4, 32, 32, 32, True, None),
    (2, 256, 8, 1, 128, 64, 128, True, 48),
    (1, 128, 2, 2, 64, 128, 128, False, None),
    (1, 64, 4, 2, 128, 16, 16, True, None),
]


@pytest.mark.parametrize("b,s,h,kv,d,qb,kb,causal,window", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, s, h, kv, d, qb, kb, causal, window, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    q, k, v = rand((b, s, h, d), dtype, 1), rand((b, s, kv, d), dtype, 2), \
        rand((b, s, kv, d), dtype, 3)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=qb, kv_block=kb, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


# -- wkv6 ---------------------------------------------------------------------

@pytest.mark.parametrize("b,t,h,n,c", [(2, 100, 3, 16, 32), (1, 64, 2, 64, 64),
                                       (2, 33, 4, 8, 16)])
def test_wkv6_kernel_vs_naive(b, t, h, n, c):
    from repro.kernels.wkv6.kernel import wkv6
    from repro.models.rwkv6 import wkv6_step
    r, k, v = rand((b, t, h, n), k=1), rand((b, t, h, n), k=2), \
        rand((b, t, h, n), k=3)
    logw = -jnp.exp(rand((b, t, h, n), k=4) * 0.5 - 4.0)
    u = rand((h, n), k=5) * 0.5
    out, sfin = wkv6(r, k, v, logw, u, chunk=c, interpret=True)
    s = jnp.zeros((b, h, n, n))
    outs = []
    for i in range(t):
        o, s = wkv6_step(r[:, i], k[:, i], v[:, i], logw[:, i], u, s)
        outs.append(o)
    ref = jnp.stack(outs, 1)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-4
    assert float(jnp.max(jnp.abs(sfin - s))) < 2e-4


def test_wkv6_jnp_chunked_vs_naive():
    from repro.models.rwkv6 import wkv6_chunked, wkv6_step
    b, t, h, n = 2, 53, 2, 8
    r, k, v = rand((b, t, h, n), k=1), rand((b, t, h, n), k=2), \
        rand((b, t, h, n), k=3)
    logw = -jnp.exp(rand((b, t, h, n), k=4) * 0.5 - 4.0)
    u = rand((h, n), k=5) * 0.5
    out, _ = wkv6_chunked(r, k, v, logw, u, chunk=16)
    s = jnp.zeros((b, h, n, n))
    ref = []
    for i in range(t):
        o, s = wkv6_step(r[:, i], k[:, i], v[:, i], logw[:, i], u, s)
        ref.append(o)
    assert float(jnp.max(jnp.abs(out - jnp.stack(ref, 1)))) < 2e-4


# -- rglru --------------------------------------------------------------------

@pytest.mark.parametrize("b,t,w,c,wt", [(2, 100, 48, 32, 16),
                                        (1, 64, 128, 64, 128),
                                        (3, 17, 8, 8, 8)])
def test_rglru_kernel(b, t, w, c, wt):
    from repro.kernels.rglru.kernel import rglru_scan
    from repro.kernels.rglru.ref import rglru_ref
    a = jax.nn.sigmoid(rand((b, t, w), k=1))
    bx = rand((b, t, w), k=2)
    h0 = rand((b, w), k=3)
    o1, hl1 = rglru_scan(a, bx, h0, chunk=c, width_tile=wt, interpret=True)
    o2, hl2 = rglru_ref(a, bx, h0)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 1e-4
    assert float(jnp.max(jnp.abs(hl1 - hl2))) < 1e-4


def test_rglru_scan_matches_sequential():
    """The associative-scan reference equals the sequential recurrence."""
    from repro.models.rglru import rg_lru_scan
    b, t, w = 2, 29, 5
    a = jax.nn.sigmoid(rand((b, t, w), k=1))
    bx = rand((b, t, w), k=2)
    h0 = rand((b, w), k=3)
    hs = rg_lru_scan(a, bx, h0)
    h = h0
    for i in range(t):
        h = a[:, i] * h + bx[:, i]
        assert jnp.allclose(hs[:, i], h, atol=1e-5), i


# -- tree_combine -------------------------------------------------------------

@pytest.mark.parametrize("nch,l,tile", [(3, 1000, 256), (1, 64, 64), (5, 17, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tree_combine_kernel(nch, l, tile, dtype):
    from repro.kernels.tree_combine.kernel import tree_combine
    from repro.kernels.tree_combine.ref import tree_combine_ref
    recv = rand((nch, l), dtype, 1)
    part = rand((l,), dtype, 2)
    out = tree_combine(recv, part, tile=tile, interpret=True)
    ref = tree_combine_ref(recv, part)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - ref.astype(jnp.float32)))) < tol


# -- int8 wire codec ----------------------------------------------------------

@pytest.mark.parametrize("l,tile", [(64, 1 << 18), (1000, 1 << 18),
                                    (4096, 1 << 18), (1000, 256),
                                    (4097, 1024), (17, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_q8_wire_kernels_match_refs(l, tile, dtype):
    """Gridded codec kernels equal the jitted references bit for bit,
    with one block, many blocks, and a last block that runs past L."""
    from repro.kernels.tree_combine.kernel import (q8_combine_wire,
                                                   q8_pack_wire,
                                                   q8_unpack_wire)
    from repro.kernels.tree_combine.ref import (q8_combine_ref, q8_pack_ref,
                                                q8_scale, q8_unpack_ref)
    x = rand((l,), dtype, 1) * 3.3
    s = q8_scale(x)
    wire_k = q8_pack_wire(x, s, tile=tile, interpret=True)
    wire_r = jax.jit(q8_pack_ref)(x, s)
    assert wire_k.dtype == jnp.int8 and wire_k.shape == (l + 4,)
    assert (jnp.asarray(wire_k) == jnp.asarray(wire_r)).all()

    part = rand((l,), jnp.float32, 2)
    out_k = q8_combine_wire(wire_k, part, tile=tile, interpret=True)
    assert (out_k == jax.jit(q8_combine_ref)(wire_r, part)).all()

    dec_k = q8_unpack_wire(wire_k, jnp.float32, tile=tile, interpret=True)
    dec_r = jax.jit(q8_unpack_ref)(wire_r)
    assert (dec_k == dec_r).all()
    # quantization round-trip error bounded by half a step
    assert float(jnp.max(jnp.abs(dec_r - x.astype(jnp.float32)))) \
        <= float(s) * 0.51


def test_q8_row_batched_codec_roundtrip():
    from repro.kernels.tree_combine.ref import (q8_pack_ref, q8_pack_rows_ref,
                                                q8_scale, q8_unpack_rows_ref)
    x = rand((3, 257), jnp.float32, 5) * 2.1
    wires = q8_pack_rows_ref(x)
    assert wires.shape == (3, 261) and wires.dtype == jnp.int8
    # row-batched pack equals the per-row pack
    for j in range(3):
        assert (jnp.asarray(wires[j])
                == jnp.asarray(q8_pack_ref(x[j], q8_scale(x[j])))).all()
    dec = q8_unpack_rows_ref(wires, jnp.float32)
    scales = jnp.max(jnp.abs(x), axis=1) / 127.0
    assert float(jnp.max(jnp.abs(dec - x) / scales[:, None])) <= 0.51


def test_q8_ops_dispatch_and_zero_wire():
    from repro.kernels.tree_combine import ops
    x = rand((100,), jnp.float32, 3)
    w = ops.q8_pack(x)
    assert float(jnp.max(jnp.abs(ops.q8_unpack(w) - x))) < 0.05
    # an all-zero wire (what ppermute hands non-destinations) decodes to
    # exact zeros: the zero-bit scale annihilates the payload
    z = jnp.zeros_like(w)
    assert (jnp.asarray(ops.q8_unpack(z)) == 0).all()
    assert jnp.allclose(ops.q8_combine(z, x), x)


# -- blockwise jnp sdpa (the model's CPU path) ---------------------------------

@pytest.mark.parametrize("mode", ["causal", "full"])
@pytest.mark.parametrize("qb,kb", [(32, 16), (16, 32), (7, 13)])
def test_model_sdpa_blockwise(mode, qb, kb):
    from repro.models.layers import AttnCfg, sdpa, sdpa_reference
    cfg = AttnCfg(d_model=64, n_heads=8, n_kv=2, head_dim=16)
    pos = jnp.arange(100, dtype=jnp.int32)
    q, k, v = rand((2, 100, 8, 16), k=1), rand((2, 100, 2, 16), k=2), \
        rand((2, 100, 2, 16), k=3)
    o1 = sdpa(q, k, v, pos, pos, cfg, mode, q_block=qb, kv_block=kb)
    o2 = sdpa_reference(q, k, v, pos, pos, cfg, mode)
    assert float(jnp.max(jnp.abs(o1 - o2))) < 2e-5
