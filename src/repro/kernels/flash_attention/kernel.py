"""Pallas TPU flash attention (GQA, causal, sliding-window) — forward.

Grid: (B*Hq, n_q_blocks, n_kv_blocks); the kv dimension is innermost and
sequential ("arbitrary") so VMEM scratch accumulators (m, l, acc) carry the
online softmax across kv blocks. GQA is handled in the K/V index_map: query
head h reads kv head h // group_size — no tensor replication.

TPU adaptation notes (vs. the CUDA flash-attention formulation):
  - blocks are (block_q x Dh) / (block_k x Dh) VMEM tiles sized for the MXU
    (multiples of 128 on the matmul dims; Dh < 128 is lane-padded),
  - out-of-window / fully-future blocks are skipped via ``pl.when``
    predication — this realizes the SWA block-skip that the pure-jnp path
    only masks,
  - accumulation in fp32 scratch; inputs may be bf16.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window, block_q: int, block_k: int, n_k: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr[...], NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr[...])
        acc_scr[...] = jnp.zeros_like(acc_scr[...])

    q_start = qi * block_q
    k_start = ki * block_k
    # Block-level skip: entirely in the future (causal) or behind the window.
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window is not None:
        live = jnp.logical_and(live, k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, Dh)
        k = k_ref[0].astype(jnp.float32)  # (block_k, Dh)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = (acc_scr[...] / denom).astype(o_ref.dtype)[None]


def flash_attention_bhsd(
    q: jax.Array,  # (BHq, Sq, Dh)
    k: jax.Array,  # (BHkv, Sk, Dh)
    v: jax.Array,
    *,
    causal: bool = True,
    window=None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = True,
) -> jax.Array:
    bhq, sq, dh = q.shape
    bhkv, sk, _ = k.shape
    assert bhq % bhkv == 0
    group = bhq // bhkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, "pad seq to block multiple"
    n_q, n_k = sq // block_q, sk // block_k
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k,
    )
    scratch = [
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, 1), jnp.float32),
        pltpu.VMEM((block_q, dh), jnp.float32),
    ]
    grid = (bhq, n_q, n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, qi, ki, g=group: (h // g, ki, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, qi, ki, g=group: (h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, dh), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v)
