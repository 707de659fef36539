"""Shared layers: norms, rotary embeddings, GQA attention (optionally
qk-norm / qkv-bias / sliding-window / KV cache), gated MLPs, embeddings.

Parameters are plain dicts; every ``init_*`` returns ``(params, axes)`` where
``axes`` mirrors the param tree with tuples of logical axis names consumed by
``repro.dist.sharding``.  Logical axes used here:
  "embed" (d_model), "heads", "kv_heads", "head_dim", "mlp" (d_ff),
  "vocab", "layers" (scan-stacked leading dim, added by the stacker).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# sublayer scopes
# ---------------------------------------------------------------------------

def sublayer(name: str):
    """Trace the decorated layer under ``jax.named_scope(name)``, so every
    op it adds (its backward and recomputation included) names the
    sublayer in the compiled program's metadata.  Metadata only: the
    compiled program is the same without it."""
    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return wrap


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def batch_hint(x, batch_dim: int = 0):
    """Constrain an activation's batch dim to the DP mesh axes.

    GSPMD sharding propagation loses the batch sharding on values that enter
    scan carries from fresh broadcasts (zeros inits) -- without this hint the
    flash-attention online-softmax carries (and similar) come out replicated,
    inflating per-device temps by the DP factor.  No-op when: no Auto mesh is
    active, the DP axes are Manual (inside shard_map the arrays are already
    local), or the dim is not divisible.
    """
    try:
        m = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return x
    if m is None or m.empty:
        return x
    names = []
    for a, t in zip(m.axis_names, m.axis_types):
        if a in ("pod", "data"):
            if "Auto" not in str(t):
                return x
            names.append(a)
    if not names:
        return x
    total = 1
    for a in names:
        total *= m.shape[a]
    if x.ndim <= batch_dim or x.shape[batch_dim] % total or \
            x.shape[batch_dim] < total:
        return x
    spec = [None] * x.ndim
    spec[batch_dim] = tuple(names) if len(names) > 1 else names[0]
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def seq_hint(x, seq_dim: int = 1):
    """Megatron-SP-style hint: shard an activation's sequence dim over the
    "model" axis.  Applied to the residual stream at layer boundaries so the
    scan-AD saved carries (L, B, S, d) are sequence-sharded; XLA inserts the
    all-gather before attention and the reduce-scatter after.  No-op when no
    Auto "model" axis is active or S is not divisible."""
    try:
        m = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return x
    if m is None or m.empty or "model" not in m.axis_names:
        return x
    t = dict(zip(m.axis_names, m.axis_types))["model"]
    if "Auto" not in str(t):
        return x
    n = m.shape["model"]
    if x.ndim <= seq_dim or x.shape[seq_dim] % n or x.shape[seq_dim] < n:
        return x
    spec = [None] * x.ndim
    spec[seq_dim] = "model"
    # keep any batch sharding on dim 0
    names = [a for a in ("pod", "data") if a in m.axis_names]
    if names and x.shape[0] % _prod_sizes(m, names) == 0 and seq_dim != 0:
        spec[0] = tuple(names) if len(names) > 1 else names[0]
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def _prod_sizes(m, names):
    out = 1
    for a in names:
        out *= m.shape[a]
    return out


def head_hint(x, head_dim: int):
    """Shard dim ``head_dim`` of an activation over the "model" axis (plus
    batch over DP axes on dim 0 when divisible).  No-op outside an Auto mesh
    or when not divisible."""
    try:
        m = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001
        return x
    if m is None or m.empty or "model" not in m.axis_names:
        return x
    if "Auto" not in str(dict(zip(m.axis_names, m.axis_types))["model"]):
        return x
    n = m.shape["model"]
    if x.ndim <= head_dim or x.shape[head_dim] % n or x.shape[head_dim] < n:
        return batch_hint(x)
    spec = [None] * x.ndim
    spec[head_dim] = "model"
    names = [a for a in ("pod", "data") if a in m.axis_names]
    if names and head_dim != 0 and x.shape[0] % _prod_sizes(m, names) == 0:
        spec[0] = tuple(names) if len(names) > 1 else names[0]
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


def ninit(key, shape, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / np.sqrt(shape[0])
    return jax.random.normal(key, shape, dtype) * jnp.asarray(scale, dtype)


def zinit(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}, {"scale": ("embed",)}


def rmsnorm(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"]).astype(dt)


def init_layernorm(d):
    return ({"scale": jnp.ones((d,), jnp.float32), "bias": zinit((d,))},
            {"scale": ("embed",), "bias": ("embed",)})


def layernorm(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(dt)


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x, positions, theta=10000.0):
    """x: (..., S, H, D); positions: (..., S) int32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, d, 2) / d))
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., S, D/2)
    ang = ang[..., None, :]                                  # (..., S, 1, D/2)
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA), cache-aware
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    qk_norm: bool = False
    window: int | None = None       # sliding-window size (None = full)
    causal: bool = True
    rope_theta: float = 10000.0
    use_rope: bool = True


def init_attention(key, cfg: AttnCfg):
    ks = jax.random.split(key, 4)
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p = {
        "wq": ninit(ks[0], (d, h, hd)),
        "wk": ninit(ks[1], (d, kv, hd)),
        "wv": ninit(ks[2], (d, kv, hd)),
        "wo": ninit(ks[3], (h, hd, d), scale=1.0 / np.sqrt(h * hd)),
    }
    a = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"], a["bq"] = zinit((h, hd)), ("heads", "head_dim")
        p["bk"], a["bk"] = zinit((kv, hd)), ("kv_heads", "head_dim")
        p["bv"], a["bv"] = zinit((kv, hd)), ("kv_heads", "head_dim")
    if cfg.qk_norm:
        p["q_norm"], a["q_norm"] = jnp.ones((hd,)), ("head_dim",)
        p["k_norm"], a["k_norm"] = jnp.ones((hd,)), ("head_dim",)
    return p, a


def _headwise_rms(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale).astype(dt)


@sublayer("model/attention")
def attention(p, cfg: AttnCfg, x, positions, *, kv_cache=None, cache_len=None,
              cache_write_idx=None, cache_positions=None,
              kv_x=None, kv_positions=None, mask_mode="causal",
              q_block=1024, kv_block=1024):
    """Returns (out, new_cache).

    x: (B, S, d).  positions: (S,) int32 (shared across batch).  kv_cache:
    optional (k_cache, v_cache) of shape (B, S_max, n_kv, hd) with valid
    length ``cache_len`` (decode: new kv written at cache_len).
    Ring-buffer caches (sliding window): pass ``cache_write_idx`` (slot) and
    ``cache_positions`` ((S_max,) absolute positions per slot, sentinel 1e9
    for unwritten).  kv_x: cross-attention source.  mask_mode: "causal" |
    "full" (encoder / cross).
    """
    b, s, _ = x.shape
    xkv = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = _headwise_rms(q, p["q_norm"])
        k = _headwise_rms(k, p["k_norm"])
    if cfg.use_rope:
        kpos = kv_positions if kv_positions is not None else positions
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kpos, cfg.rope_theta)

    if kv_cache is not None:
        kc, vc = kv_cache
        wi = cache_len if cache_write_idx is None else cache_write_idx
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype),
                                                 wi, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype),
                                                 wi, axis=1)
        k_all, v_all = kc.astype(q.dtype), vc.astype(q.dtype)
        if cache_positions is not None:
            kv_pos = cache_positions
            valid_len = None   # sentinel + causal/window terms do the masking
        else:
            kv_pos = jnp.arange(kc.shape[1], dtype=jnp.int32)
            valid_len = cache_len + s
        new_cache = (kc, vc)
    else:
        k_all, v_all = k, v
        kv_pos = kv_positions if kv_positions is not None else positions
        new_cache = (k, v)
        valid_len = None

    out = sdpa(q, k_all, v_all, positions.astype(jnp.int32),
               kv_pos.astype(jnp.int32), cfg, mask_mode,
               valid_len=valid_len, q_block=q_block, kv_block=kv_block)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


def _block_mask(qp, kp, cfg: AttnCfg, mask_mode, valid_len):
    """(qb, kb) bool mask from 1-D position blocks -- never materializes
    anything batch- or head-shaped."""
    m = kp[None, :] < 10 ** 9   # padded kv sentinel is +1e9: always masked
    m = jnp.broadcast_to(m, (qp.shape[0], kp.shape[0]))
    if mask_mode == "causal":
        m = m & (kp[None, :] <= qp[:, None])
        if cfg.window is not None:
            m = m & (kp[None, :] > qp[:, None] - cfg.window)
    if valid_len is not None:
        m = m & (kp[None, :] < valid_len)
    return m


def _attn_block(q, k, mask, scale):
    """Masked logits for one (q-block x kv-block) pair.
    q: (b,qb,kv,g,d), k: (b,kb,kv,d), mask: (qb,kb) -> (b,kv,g,qb,kb) f32."""
    logits = jnp.einsum("bqkgd,btkd->bkgqt", q, k) * scale
    return jnp.where(mask[None, None, None], logits.astype(jnp.float32), -1e30)


def sdpa(q, k, v, q_pos, kv_pos, cfg: AttnCfg, mask_mode="causal",
         valid_len=None, q_block=1024, kv_block=1024):
    """Blockwise (flash-style) attention in pure JAX: online softmax over KV
    blocks, O(block^2) live memory.  For causal masks the kv loop for query
    block i covers blocks [0, i] only -- no wasted block compute, matching
    what the Pallas kernel does on TPU with pl.when.

    q: (B,S,H,D); k,v: (B,T,KV,D); q_pos: (S,), kv_pos: (T,) int32.
    """
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    scale = 1.0 / np.sqrt(d)
    qb = min(q_block, s)
    kb = min(kv_block, t)
    # pad to block multiples (static)
    s_pad, t_pad = -(-s // qb) * qb, -(-t // kb) * kb
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, s_pad - s), constant_values=-(10 ** 9))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, t_pad - t), constant_values=10 ** 9)
    nq, nk = s_pad // qb, t_pad // kb
    qr = batch_hint(q.reshape(b, nq, qb, kv, g, d))
    kr = batch_hint(k.reshape(b, nk, kb, kv, d))
    vr = batch_hint(v.reshape(b, nk, kb, kv, d))
    qpr = q_pos.reshape(nq, qb)
    kpr = kv_pos.reshape(nk, kb)

    def process_qblock(qi, n_kv_blocks):
        """Scan kv blocks [0, n_kv_blocks) for query block qi."""
        qcur, qp = qr[:, qi], qpr[qi]

        # the scope again inside the checkpointed body: its reductions
        # lose the scopes it was called under
        @sublayer("model/attention")
        def step(carry, inputs):
            m, l, acc = carry
            kblk, vblk, kp = inputs
            logits = _attn_block(qcur, kblk,
                                 _block_mask(qp, kp, cfg, mask_mode, valid_len),
                                 scale)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p_ = jnp.exp(logits - m_new[..., None])
            l_new = l * corr + p_.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p_.astype(qcur.dtype),
                vblk).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = batch_hint(jnp.full((b, kv, g, qb), -1e30, jnp.float32))
        l0 = batch_hint(jnp.zeros((b, kv, g, qb), jnp.float32))
        a0 = batch_hint(jnp.zeros((b, kv, g, qb, d), jnp.float32))
        # flash-style backward: recompute the (qb x kb) score block in the
        # bwd pass instead of saving it (only the online-softmax carries are
        # stored per step) -- keeps attention AD memory at O(S) not O(S^2)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(step), (m0, l0, a0),
            (kr[:, :n_kv_blocks].swapaxes(0, 1),
             vr[:, :n_kv_blocks].swapaxes(0, 1), kpr[:n_kv_blocks]))
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qcur.dtype)
        return out.transpose(0, 3, 1, 2, 4)  # (b, qb, kv, g, d)

    if mask_mode == "causal" and nq > 1 and s == t:
        # triangle-exact: query block i only visits kv blocks [0, ceil((i+1)qb/kb))
        outs = [process_qblock(i, min(nk, -(-((i + 1) * qb) // kb)))
                for i in range(nq)]
    else:
        outs = [process_qblock(i, nk) for i in range(nq)]
    out = jnp.stack(outs, axis=1).reshape(b, s_pad, kv, g, d)[:, :s]
    return out.reshape(b, s, h, d)


def sdpa_reference(q, k, v, q_pos, kv_pos, cfg: AttnCfg, mask_mode="causal",
                   valid_len=None):
    """Quadratic-memory oracle (small shapes only; used by tests)."""
    b, s, h, d = q.shape
    t, kv = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, s, kv, g, d)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    logits = logits / np.sqrt(d)
    mask = _block_mask(q_pos, kv_pos, cfg, mask_mode, valid_len)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(b, s, h, d)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_glu_mlp(key, d, f, kind="swiglu"):
    ks = jax.random.split(key, 3)
    p = {"wi_gate": ninit(ks[0], (d, f)), "wi_up": ninit(ks[1], (d, f)),
         "wo": ninit(ks[2], (f, d))}
    a = {"wi_gate": ("embed", "mlp"), "wi_up": ("embed", "mlp"),
         "wo": ("mlp", "embed")}
    return p, a


@sublayer("model/mlp")
def glu_mlp(p, x, kind="swiglu"):
    act = jax.nn.silu if kind == "swiglu" else jax.nn.gelu
    g = jnp.einsum("bsd,df->bsf", x, p["wi_gate"].astype(x.dtype))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", act(g) * u, p["wo"].astype(x.dtype))


def init_dense_mlp(key, d, f):
    ks = jax.random.split(key, 2)
    return ({"wi": ninit(ks[0], (d, f)), "wo": ninit(ks[1], (f, d))},
            {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")})


def dense_mlp(p, x):
    h = jax.nn.gelu(jnp.einsum("bsd,df->bsf", x, p["wi"].astype(x.dtype)))
    return jnp.einsum("bsf,fd->bsd", h, p["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# embedding / unembedding (padded vocab for TP divisibility)
# ---------------------------------------------------------------------------

def pad_vocab(vocab: int, multiple: int = 256) -> int:
    return -(-vocab // multiple) * multiple


def init_embedding(key, vocab_padded, d):
    return ({"table": ninit(key, (vocab_padded, d), scale=0.02)},
            {"table": ("vocab", "embed")})


@sublayer("model/embed")
def embed(p, tokens, dtype=jnp.bfloat16):
    return batch_hint(p["table"].astype(dtype)[tokens])


@sublayer("model/head")
def unembed(p, x, vocab: int):
    """Logits against the (tied) embedding table; padded slots masked."""
    logits = jnp.einsum("bsd,vd->bsv", x, p["table"].astype(x.dtype))
    vp = p["table"].shape[0]
    if vp != vocab:
        neg = jnp.asarray(-1e30, logits.dtype)
        logits = jnp.where(jnp.arange(vp)[None, None, :] < vocab, logits, neg)
    return logits


@sublayer("model/head")
def chunked_unembed_xent(embed_p, x, labels, vocab: int, chunk: int = 512,
                         z_loss=1e-4):
    """Cross-entropy over tied-embedding logits, computed (and re-computed in
    the backward pass) in sequence chunks so the (tokens x vocab) logits
    tensor never materializes beyond one chunk.  x: (B, S, d), labels (B, S).
    """
    b, s, d = x.shape
    c = min(chunk, s)
    s_pad = -(-s // c) * c
    if s_pad != s:
        x = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, s_pad - s)),
                         constant_values=-1)
    nch = s_pad // c
    xr = x.reshape(b, nch, c, d).swapaxes(0, 1)
    lr = labels.reshape(b, nch, c).swapaxes(0, 1)

    @jax.checkpoint
    @sublayer("model/head")     # inside: the reductions lose outer scopes
    def step(acc, inp):
        xc, lc = inp
        logits = unembed(embed_p, xc, vocab).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, jnp.maximum(lc, 0)[..., None],
                                 axis=-1)[..., 0]
        loss = lse - ll
        if z_loss:
            loss = loss + z_loss * lse ** 2
        valid = (lc >= 0).astype(jnp.float32)
        return (acc[0] + (loss * valid).sum(), acc[1] + valid.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.float32)), (xr, lr))
    return tot / jnp.maximum(cnt, 1.0)


def softmax_xent(logits, labels, valid_mask=None, z_loss=1e-4):
    """Mean token cross-entropy in f32 with optional z-loss."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    loss = lse - ll
    if z_loss:
        loss = loss + z_loss * lse ** 2
    if valid_mask is None:
        return loss.mean()
    w = valid_mask.astype(jnp.float32)
    return (loss * w).sum() / jnp.maximum(w.sum(), 1.0)
