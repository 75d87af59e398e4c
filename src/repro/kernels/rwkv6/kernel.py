"""Pallas TPU kernel for the chunked WKV6 recurrence (RWKV6 "Finch").

Grid: (B*H, n_chunks); the chunk dimension is sequential and the (N x N)
key->value state lives in fp32 VMEM scratch across chunks. Within a chunk
everything is matmul form (MXU):

    o_intra = tril_strict( (r * e^{cum_ex}) @ (k * e^{-cum})^T ) @ v
              + diag(r . u . k) v
    o_inter = (r * e^{cum_ex}) @ S
    S'      = diag(e^{cum_end}) S + (k * e^{cum_end - cum})^T @ v

Numerics (TPU adaptation vs. the paper-exact pairwise form used by the
oracle in ``repro.models.rwkv6.wkv_chunked``): ``k * e^{-cum}`` can overflow
when the cumulative decay within a chunk is extreme, so ``cum`` is clamped
to >= -CAP (CAP=30). Terms affected by the clamp carry a factor < e^-30 —
below bf16/f32 relevance. Chunk length is kept at 32 (also bounds the clamp
error); the N x N state tile (64 x 64 fp32 = 16 KiB) sits in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

CAP = 30.0


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, s_scr, *, chunk: int, n: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr[...])

    r = r_ref[0].astype(jnp.float32)  # (Lc, N)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    logw = w_ref[0].astype(jnp.float32)  # <= 0
    u = u_ref[0].astype(jnp.float32)  # (1, N)

    cum = jnp.cumsum(logw, axis=0)  # (Lc, N), decreasing
    cum_ex = cum - logw
    cum_cl = jnp.maximum(cum, -CAP)
    q_in = r * jnp.exp(cum_ex)  # <= |r|
    k_in = k * jnp.exp(-cum_cl)  # bounded by e^CAP
    scores = jax.lax.dot_general(
        q_in, k_in, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(ti > si, scores, 0.0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (Lc, 1)
    scores = scores + jnp.where(ti == si, diag, 0.0)
    o_intra = jax.lax.dot_general(
        scores, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s = s_scr[...]
    o_inter = jax.lax.dot_general(
        q_in, s, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    cum_end = cum[-1:, :]  # (1, N)
    k_dec = k * jnp.exp(cum_end - cum)  # <= |k|
    s_scr[...] = jnp.exp(cum_end).T * s + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[...] = (o_intra + o_inter).astype(o_ref.dtype)[None]


def wkv6_bhsn(
    r: jax.Array,  # (BH, S, N)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,  # (BH, S, N), <= 0
    u: jax.Array,  # (BH, N) bonus, expanded per head
    *,
    chunk: int = 32,
    interpret: bool = True,
) -> jax.Array:
    bh, s, n = r.shape
    assert s % chunk == 0, "pad sequence to a chunk multiple"
    n_chunks = s // chunk
    u3 = u[:, None, :]
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n=n)
    scratch = [pltpu.VMEM((n, n), jnp.float32)]
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
            pl.BlockSpec((1, 1, n), lambda h, c: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, n), lambda h, c: (h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, n), r.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(r, k, v, logw, u3)
