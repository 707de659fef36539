"""Plain float32 reference of the decoder-only LM that the benchmark's
``lm`` configurations train, and of its training step.

The block is the llama / mistral block as published: pre-norm RMSNorm,
grouped-query attention with rotary position embeddings (the
rotate-half form, ``inv_freq = theta ** -(2i / head_dim)``), a causal
softmax scaled by ``head_dim ** -0.5``, and a SwiGLU MLP
(``silu(x W_gate) * (x W_up) W_down``); a final RMSNorm and logits
against the input embedding (tied).  The loss is the mean over tokens
of ``logsumexp - logit[label] + z_loss * logsumexp**2``.  The training
step is AdamW on the global-norm-clipped gradient, as the traffic
file's ``optimizer`` block states it.

Everything is float32 with every matrix product at
``Precision.HIGHEST``.  Memory is held down by remat of each layer, by
attention over blocks of query rows, and by the head over chunks of
positions, so the reference fits on one chip beside nothing else.

``precision="fp8"`` is the control, the step below the bfloat16 that the
configurations state, as float8 training computes it: every matrix
product's operands rounded to float8 e4m3 in the forward pass, and the
gradient arriving at its result rounded to float8 e5m2 in the backward
pass, each with one scale per tensor.

This module imports nothing of the program under test and takes nothing
it made: it makes the weights itself from the seed, with the program's
layout of the parameter tree (names and shapes), which the harness
checks against the program's own.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
E4M3, E4M3_MAX = jnp.float8_e4m3fn, 448.0
E5M2, E5M2_MAX = jnp.float8_e5m2, 57344.0


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def weight_shapes(s: dict) -> dict:
    """Parameter tree of shapes, in the program's layout: per-layer
    arrays stacked on a leading layer axis."""
    d, h, kv, hd = s["d_model"], s["n_heads"], s["n_kv"], s["head_dim"]
    f, v, n = s["d_ff"], s["vocab"], s["n_layers"]
    return {
        "embed": {"table": (v, d)},
        "final_norm": {"scale": (d,)},
        "layers": {
            "ln1": {"scale": (n, d)},
            "ln2": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, hd), "wk": (n, d, kv, hd),
                     "wv": (n, d, kv, hd), "wo": (n, h, hd, d)},
            "mlp": {"wi_gate": (n, d, f), "wi_up": (n, d, f),
                    "wo": (n, f, d)},
        },
    }


def _init_scale(path: str, s: dict):
    """Standard deviation of each leaf's normal init (None: ones)."""
    if path.endswith("scale"):
        return None
    if path == "embed.table":
        return 0.02
    if path == "layers.attn.wo":
        return 1.0 / math.sqrt(s["n_heads"] * s["head_dim"])
    if path == "layers.mlp.wo":
        return 1.0 / math.sqrt(s["d_ff"])
    return 1.0 / math.sqrt(s["d_model"])


def _paths(tree, prefix=""):
    for k in sorted(tree):
        p = f"{prefix}.{k}" if prefix else k
        if isinstance(tree[k], dict):
            yield from _paths(tree[k], p)
        else:
            yield p, tree[k]


def _set(tree, path, value):
    keys = path.split(".")
    for k in keys[:-1]:
        tree = tree.setdefault(k, {})
    tree[keys[-1]] = value


def make_weights(s: dict, key):
    """Float32 weights from ``key``: call under ``jax.jit`` so that they
    are made on the device in one program."""
    out = {}
    for i, (path, shape) in enumerate(_paths(weight_shapes(s))):
        std = _init_scale(path, s)
        if std is None:
            leaf = jnp.ones(shape, jnp.float32)
        else:
            leaf = jax.random.normal(jax.random.fold_in(key, i), shape,
                                     jnp.float32) * std
        _set(out, path, leaf)
    return out


# ---------------------------------------------------------------------------
# forward and loss
# ---------------------------------------------------------------------------

def _round(x, dtype, top):
    """``x`` rounded to a float8 ``dtype`` with one scale per tensor."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _operand_fp8(x):
    """Forward: x in e4m3.  Backward: the gradient passes through."""
    return x + lax.stop_gradient(_round(x, E4M3, E4M3_MAX) - x)


@jax.custom_vjp
def _result_fp8(y):
    """Forward: y as it is.  Backward: the gradient in e5m2."""
    return y


_result_fp8.defvjp(lambda y: (y, None),
                   lambda _, g: (_round(g, E5M2, E5M2_MAX),))


def _matmul(precision: str):
    def mm(eq, a, b):
        if precision == "fp8":
            a, b = _operand_fp8(a), _operand_fp8(b)
        out = jnp.einsum(eq, a, b, precision=HIGHEST,
                         preferred_element_type=jnp.float32)
        return _result_fp8(out) if precision == "fp8" else out
    return mm


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: (r, S, heads, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, q_block, mm):
    """Causal grouped-query attention, one block of query rows at a
    time.  q: (r, S, h, hd); k, v: (r, S, kv, hd)."""
    r, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    qb = min(q_block, s)
    nb = s // qb
    qg = q.reshape(r, nb, qb, kv, g, hd)
    keys = jnp.arange(s)

    @jax.checkpoint
    def one(i):
        qi = qg[:, i]
        logits = mm("rqkgd,rtkd->rkgqt", qi, k) / math.sqrt(hd)
        rows = i * qb + jnp.arange(qb)
        causal = keys[None, :] <= rows[:, None]
        logits = jnp.where(causal, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return mm("rkgqt,rtkd->rqkgd", p, v)

    out = lax.map(one, jnp.arange(nb))          # (nb, r, qb, kv, g, hd)
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(r, s, h, hd)


def _layer(x, lp, s, mm):
    h = _rms(x, lp["ln1"]["scale"], s["rms_norm_eps"])
    a = lp["attn"]
    q = _rope(mm("rsd,dhk->rshk", h, a["wq"]), s["rope_theta"])
    k = _rope(mm("rsd,dhk->rshk", h, a["wk"]), s["rope_theta"])
    v = mm("rsd,dhk->rshk", h, a["wv"])
    o = _attention(q, k, v, s["query_block"], mm)
    x = x + mm("rshk,hkd->rsd", o, a["wo"])
    h = _rms(x, lp["ln2"]["scale"], s["rms_norm_eps"])
    m = lp["mlp"]
    up = jax.nn.silu(mm("rsd,df->rsf", h, m["wi_gate"])) \
        * mm("rsd,df->rsf", h, m["wi_up"])
    return x + mm("rsf,fd->rsd", up, m["wo"])


def loss_sum(params, tokens, s: dict, precision: str = "f32"):
    """Sum over the tokens of ``tokens[:, 1:]`` of the per-token loss,
    given ``tokens[:, :-1]``."""
    mm = _matmul(precision)
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"]["table"][inputs]

    @jax.checkpoint
    def body(x, lp):
        return _layer(x, lp, s, mm), None

    x, _ = lax.scan(body, x, params["layers"])
    x = _rms(x, params["final_norm"]["scale"], s["rms_norm_eps"])
    table = params["embed"]["table"]
    r, n, d = x.shape
    c = min(s["head_chunk"], n)
    xc = x.reshape(r, n // c, c, d).swapaxes(0, 1)
    lc = labels.reshape(r, n // c, c).swapaxes(0, 1)

    @jax.checkpoint
    def head(total, inp):
        xi, li = inp
        logits = mm("rcd,vd->rcv", xi, table)
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, li[..., None], axis=-1)[..., 0]
        per = lse - ll + s["z_loss"] * lse * lse
        return total + jnp.sum(per), None

    total, _ = lax.scan(head, jnp.zeros((), jnp.float32), (xc, lc))
    return total


# ---------------------------------------------------------------------------
# the training step
# ---------------------------------------------------------------------------

def learning_rate(t, opt: dict):
    """The cosine schedule with linear warm-up, at step ``t`` (1-based)."""
    t = jnp.asarray(t, jnp.float32)
    base, warm, total = opt["lr"], opt["warmup"], opt["total_steps"]
    up = base * t / max(warm, 1)
    frac = jnp.clip((t - warm) / max(total - warm, 1), 0.0, 1.0)
    down = 0.5 * base * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(t < warm, up, down)


def _grads(params, tokens, s, precision, rows_per_block):
    """Mean loss and its gradient over ``tokens``, accumulated over
    blocks of rows."""
    b = tokens.shape[0]
    rb = min(rows_per_block, b)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    vg = jax.value_and_grad(loss_sum)
    if rb == b:
        tot, g = vg(params, tokens, s, precision)
        return tot / n, jax.tree.map(lambda x: x / n, g)
    blocks = tokens.reshape(b // rb, rb, tokens.shape[1])

    def body(carry, blk):
        tot, acc = carry
        val, g = vg(params, blk, s, precision)
        return (tot + val, jax.tree.map(jnp.add, acc, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (tot, acc), _ = lax.scan(body, (jnp.zeros((), jnp.float32), zeros),
                             blocks)
    return tot / n, jax.tree.map(lambda g: g / n, acc)


def _step(params, mu, nu, tokens, t, *, s, opt, precision, rows_per_block,
          norms):
    loss, g = _grads(params, tokens, s, precision, rows_per_block)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    raw_norms = norms(g)
    scale = jnp.minimum(1.0, opt["clip_norm"] / (gnorm + 1e-9))
    lr = learning_rate(t, opt)
    b1, b2 = opt["b1"], opt["b2"]
    tf = jnp.asarray(t, jnp.float32)

    def upd(p, gi, m, v):
        gi = gi * scale
        m = b1 * m + (1 - b1) * gi
        v = b2 * v + (1 - b2) * gi * gi
        mhat = m / (1 - b1 ** tf)
        vhat = v / (1 - b2 ** tf)
        decay = opt["weight_decay"] if p.ndim >= opt["decay_min_rank"] \
            else 0.0
        return p - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"]) + decay * p), \
            m, v

    out = jax.tree.map(upd, params, g, mu, nu)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2), loss, raw_norms


def train(s: dict, opt: dict, key, batches, *, precision="f32",
          rows=None, rows_per_block=None, norms):
    """Train from the weights of ``key`` over ``batches`` (host arrays of
    shape (B, S + 1)), one step each.  ``rows`` keeps only the first
    ``rows`` rows of every batch (the faults that leave rows out).
    ``norms(tree) -> {leaf: array}`` gives per-leaf norms.

    Returns ``(losses, first-gradient norms, change norms)``: the loss of
    each step, the per-leaf norms of step 1's gradient before clipping,
    and the per-leaf norms of the parameters' change over all steps.
    """
    weights = jax.jit(partial(make_weights, s))
    params = weights(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    rpb = rows_per_block or batches[0].shape[0]
    step = jax.jit(partial(_step, s=s, opt=opt, precision=precision,
                           rows_per_block=rpb, norms=norms),
                   donate_argnums=(0, 1, 2))
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for t, tokens in enumerate(batches, start=1):
            tok = jnp.asarray(tokens if rows is None else tokens[:rows])
            params, mu, nu, loss, gn = step(params, mu, nu, tok, t)
            losses.append(float(loss))
            if first is None:
                first = jax.device_get(gn)
        del mu, nu
        # the first weights are made again, not kept: the chip holds the
        # parameters and Adam's two moments, and little more
        change = jax.device_get(jax.jit(
            lambda p, k: norms(jax.tree.map(jnp.subtract, p, weights(k))))(
                params, key))
    return losses, first, change
