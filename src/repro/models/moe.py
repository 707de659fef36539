"""Mixture-of-Experts layer (OLMoE / Qwen2-MoE families).

Grouped GShard-style dispatch: tokens are processed in groups of
``group_size``; each group dispatches to per-expert capacity slots via one-hot
einsums (TPU-friendly dense dataflow, EP = experts sharded over the "model"
mesh axis by GSPMD).  Router uses top-k with optional softmax renorm, plus
load-balance and router-z auxiliary losses.  Expert count is padded to the
mesh divisor; padded experts are masked to -inf in the router.

Shared experts (Qwen2-MoE) run as an always-on GLU MLP with a sigmoid gate.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .layers import ninit, sublayer


@dataclasses.dataclass(frozen=True)
class MoECfg:
    d_model: int
    n_experts: int            # real expert count (router range)
    n_experts_padded: int     # padded for EP divisibility
    top_k: int
    d_expert: int             # per-expert ffn width
    n_shared: int = 0         # always-on shared experts (width n_shared*d_expert)
    group_size: int = 512
    capacity_factor: float = 1.0
    renorm: bool = True       # renormalize top-k gates (Qwen2-MoE: True)


def init_moe(key, cfg: MoECfg):
    ks = jax.random.split(key, 6)
    e, d, f = cfg.n_experts_padded, cfg.d_model, cfg.d_expert
    p = {
        "router": ninit(ks[0], (d, e), scale=0.02),
        "wi_gate": ninit(ks[1], (e, d, f)),
        "wi_up": ninit(ks[2], (e, d, f)),
        "wo": ninit(ks[3], (e, f, d)),
    }
    a = {
        "router": ("embed", "experts"),
        "wi_gate": ("experts", "embed", "mlp"),
        "wi_up": ("experts", "embed", "mlp"),
        "wo": ("experts", "mlp", "embed"),
    }
    if cfg.n_shared:
        fs = cfg.n_shared * f
        p["shared"] = {
            "wi_gate": ninit(ks[4], (d, fs)), "wi_up": ninit(ks[4], (d, fs)),
            "wo": ninit(ks[5], (fs, d)), "gate": ninit(ks[5], (d, 1), scale=0.02),
        }
        a["shared"] = {"wi_gate": ("embed", "mlp"), "wi_up": ("embed", "mlp"),
                       "wo": ("mlp", "embed"), "gate": ("embed", None)}
    return p, a


@sublayer("model/mlp")
def moe_layer(p, cfg: MoECfg, x):
    """x: (B, S, d) -> (out (B, S, d), aux_losses dict)."""
    b, s, d = x.shape
    e, k = cfg.n_experts_padded, cfg.top_k
    g = min(cfg.group_size, s)
    s_pad = -(-s // g) * g
    if s_pad != s:
        x_r = jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0)))
    else:
        x_r = x
    ng = s_pad // g
    xg = x_r.reshape(b, ng, g, d)

    logits = jnp.einsum("bgtd,de->bgte", xg, p["router"].astype(x.dtype))
    logits = logits.astype(jnp.float32)
    if cfg.n_experts != e:   # mask padded experts
        logits = jnp.where(jnp.arange(e) < cfg.n_experts, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)

    gate_vals, gate_idx = jax.lax.top_k(probs, k)        # (b,ng,g,k)
    if cfg.renorm:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-9)

    cap = int(np.ceil(g * k / cfg.n_experts * cfg.capacity_factor))
    cap = max(4, -(-cap // 4) * 4)

    # position of each (token, choice) in its expert's capacity buffer:
    # cumsum over the flattened (token, choice) order per expert
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)     # (b,ng,g,k,e)
    flat = onehot.reshape(b, ng, g * k, e)
    pos = (jnp.cumsum(flat, axis=2) * flat).reshape(b, ng, g, k, e)
    pos_tk = pos.sum(-1)                                      # (b,ng,g,k) 1-idx
    keep = (pos_tk > 0) & (pos_tk <= cap)
    slot_tk = jnp.clip(pos_tk - 1, 0, cap - 1)

    # dispatch/combine (b,ng,g,e,cap) via two one-hots contracted over k --
    # never materializes a (k, e, cap) product
    from .layers import batch_hint
    oh_e = onehot.astype(x.dtype)                             # (b,ng,g,k,e)
    oh_c = (jax.nn.one_hot(slot_tk, cap, dtype=x.dtype) *
            keep[..., None].astype(x.dtype))                  # (b,ng,g,k,cap)
    dispatch = batch_hint(jnp.einsum("bgtke,bgtkc->bgtec", oh_e, oh_c))
    combine = batch_hint(jnp.einsum(
        "bgtke,bgtkc->bgtec",
        oh_e * gate_vals[..., None].astype(x.dtype), oh_c))

    xin = jnp.einsum("bgtec,bgtd->bgecd", dispatch, xg)
    h_g = jnp.einsum("bgecd,edf->bgecf", xin, p["wi_gate"].astype(x.dtype))
    h_u = jnp.einsum("bgecd,edf->bgecf", xin, p["wi_up"].astype(x.dtype))
    h = jax.nn.silu(h_g) * h_u
    xout = jnp.einsum("bgecf,efd->bgecd", h, p["wo"].astype(x.dtype))
    out = jnp.einsum("bgtec,bgecd->bgtd", combine, xout)

    out = out.reshape(b, s_pad, d)[:, :s]

    # aux losses (computed on real experts only)
    me = probs[..., : cfg.n_experts].mean(axis=(0, 1, 2))
    ce = (onehot.sum(3)[..., : cfg.n_experts] > 0).astype(jnp.float32).mean(
        axis=(0, 1, 2)) * cfg.n_experts / k
    lb_loss = cfg.n_experts * jnp.mean(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    aux = {"moe_load_balance": lb_loss, "moe_router_z": z_loss}

    if cfg.n_shared:
        sp = p["shared"]
        sg = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, sp["wi_gate"].astype(x.dtype)))
        su = jnp.einsum("bsd,df->bsf", x, sp["wi_up"].astype(x.dtype))
        sh = jnp.einsum("bsf,fd->bsd", sg * su, sp["wo"].astype(x.dtype))
        gate = jax.nn.sigmoid(jnp.einsum("bsd,dz->bsz", x, sp["gate"].astype(x.dtype)))
        out = out + gate * sh
    return out, aux
