"""Mixture-of-experts block.

Two implementations sharing one router:

- ``dispatch`` (default): MaxText-style group-capacity one-hot dispatch.
  Tokens are processed in groups; per (group, expert) capacity buffers are
  built with cumsum position indices (no sort), all compute is einsums, so
  GSPMD can shard it: groups follow the batch (data) sharding, the expert
  axis is sharded over data axes when divisible (true expert parallelism —
  GSPMD materializes the G->E resharding as all-to-alls) and the per-expert
  hidden dim is sharded over "model".
- ``dense``: every expert computes every token, combined with router weights.
  Simple, exact (no capacity drops), top_k/n_experts-fraction wasteful; used
  as the correctness oracle and as a fallback.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..parallel.sharding import hint
from .layers import _act
from .schema import P, Schema


def moe_schema(cfg: ModelConfig) -> Schema:
    assert cfg.moe is not None
    d, e, fe = cfg.d_model, cfg.moe.n_experts, cfg.moe.d_expert
    s: Schema = {
        "router": P((d, e), ("embed", None), scale=1.0 / math.sqrt(d)),
        "wi": P((e, d, fe), ("experts", "embed", "expert_ff")),
        "wo": P((e, fe, d), ("experts", "expert_ff", "embed")),
    }
    if cfg.mlp_gated:
        s["wg"] = P((e, d, fe), ("experts", "embed", "expert_ff"))
    return s


def router_topk(cfg: ModelConfig, params, x: jax.Array):
    """x: (..., d) -> gates (..., k) normalized, idx (..., k), aux load-balance loss."""
    moe = cfg.moe
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    gates_all = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(gates_all, moe.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss: E * sum_e f_e * p_e
    tokens = gates_all.reshape(-1, moe.n_experts)
    me = tokens.mean(0)
    onehot = jax.nn.one_hot(idx.reshape(-1, moe.top_k), moe.n_experts, dtype=jnp.float32)
    ce = onehot.sum(1).mean(0) / moe.top_k
    aux = moe.n_experts * jnp.sum(me * ce)
    return gates, idx, aux


def _expert_ffn(cfg: ModelConfig, params, xb: jax.Array) -> jax.Array:
    """xb: (..., E, C, d) batched per-expert FFN -> same shape."""
    h = jnp.einsum("...ecd,edf->...ecf", xb, params["wi"])
    h = _act(cfg.mlp_act, h)
    if cfg.mlp_gated:
        h = h * jnp.einsum("...ecd,edf->...ecf", xb, params["wg"])
    return jnp.einsum("...ecf,efd->...ecd", h, params["wo"])


def moe_dispatch(cfg: ModelConfig, params, x: jax.Array, *, group_size: int = 512):
    """Group-capacity dispatch. x: (B, S, d) -> (y, aux_loss)."""
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    tg = min(group_size, t)
    if t % tg != 0:  # group size must divide tokens; shrink to a divisor
        tg = math.gcd(t, tg)
    g = t // tg
    cap = max(1, math.ceil(tg * moe.top_k * moe.capacity_factor / moe.n_experts))
    # round capacity up to a multiple of 4 for friendlier tiling
    cap = (cap + 3) // 4 * 4

    xg = x.reshape(g, tg, d)
    gates, idx, aux = router_topk(cfg, params, xg)  # (g,tg,k)

    # position of each (token, slot) within its expert, cumsum over the group
    onehot_e = jax.nn.one_hot(idx, moe.n_experts, dtype=jnp.float32)  # (g,tg,k,e)
    flat = onehot_e.reshape(g, tg * moe.top_k, moe.n_experts)
    pos = jnp.cumsum(flat, axis=1) - flat  # (g, tg*k, e)
    pos_tok = jnp.sum(flat * pos, axis=-1).reshape(g, tg, moe.top_k)  # (g,tg,k)
    keep = pos_tok < cap

    dispatch = jnp.zeros((g, tg, moe.n_experts, cap), jnp.float32)
    combine = jnp.zeros((g, tg, moe.n_experts, cap), jnp.float32)
    for kk in range(moe.top_k):  # k is small (<=8); unrolled outer products
        oc = jax.nn.one_hot(
            pos_tok[:, :, kk].astype(jnp.int32), cap, dtype=jnp.float32
        )
        oc = oc * keep[:, :, kk, None]
        ec = onehot_e[:, :, kk, :, None] * oc[:, :, None, :]  # (g,tg,e,cap)
        dispatch = dispatch + ec
        combine = combine + ec * gates[:, :, kk, None, None]

    xb = jnp.einsum("gtd,gtec->gecd", xg, dispatch.astype(x.dtype))
    # Optional EP constraints (active only when the run's sharding rules
    # define "moe_group"): pin the capacity buffers to expert-sharded layout,
    # forcing GSPMD to all-to-all activations instead of gathering expert
    # weights across the data axes. See EXPERIMENTS.md §Perf (kimi-k2).
    xb = hint(xb, ("moe_group", "experts", None, "embed"))
    yb = _expert_ffn(cfg, params, xb)
    yb = hint(yb, ("moe_group", "experts", None, "embed"))
    y = jnp.einsum("gecd,gtec->gtd", yb, combine.astype(x.dtype))
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return y.reshape(b, s, d), aux, dropped


def moe_dense(cfg: ModelConfig, params, x: jax.Array):
    """Oracle: compute all experts for all tokens, weighted-combine."""
    moe = cfg.moe
    b, s, d = x.shape
    gates, idx, aux = router_topk(cfg, params, x)  # (b,s,k)
    weights = jnp.zeros((b, s, moe.n_experts), jnp.float32)
    for kk in range(moe.top_k):
        weights = weights + jax.nn.one_hot(idx[:, :, kk], moe.n_experts) * gates[:, :, kk, None]
    xb = x[:, :, None, None, :]  # (b,s,1,1,d) broadcast as capacity buffer of 1
    xe = jnp.broadcast_to(xb, (b, s, moe.n_experts, 1, d))
    ye = _expert_ffn(cfg, params, xe.reshape(b * s, moe.n_experts, 1, d))
    ye = ye.reshape(b, s, moe.n_experts, d)
    y = jnp.einsum("bsed,bse->bsd", ye, weights.astype(x.dtype))
    return y, aux, jnp.float32(0.0)


def apply_moe(
    cfg: ModelConfig,
    params,
    x: jax.Array,
    *,
    impl: str = "dispatch",
    group_size: int = 512,
):
    if impl == "dense":
        return moe_dense(cfg, params, x)
    return moe_dispatch(cfg, params, x, group_size=group_size)
