"""Pallas TPU kernel for the RWKV6 (Finch) chunked recurrence.

TPU mapping (chunked linear attention, matching nn/ssm.rwkv6_mix_chunked):
  * grid = (B, H, num_chunks); the chunk dimension is sequential on TPU, so
    the (dk, dv) state matrix lives in VMEM scratch and carries across
    chunks — the HBM<->VMEM traffic per chunk is just the (C, dh) tiles of
    r/k/v/logw plus the (C, dh) output tile.
  * Inside a chunk everything is dense (C x dh) x (dh x dh) matmuls on the
    MXU (intra-chunk attention, state application, state update) instead of
    a length-S sequential scan — the TPU-native adaptation of RWKV's
    CUDA per-timestep kernel.
  * VMEM working set at C=64, dh=64, fp32: 5*(64*64) + (64*64) state +
    (64,64) attention ~= 115 KB — tiny; production would raise C to 256.
  * Numerical form: per-channel log-decay cumsum with midpoint
    renormalization for the intra-chunk product form (see nn/ssm.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sfin_ref,
                  s_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)      # (C, dk)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)      # (C, dv)
    lw = lw_ref[0, 0].astype(jnp.float32)    # (C, dk), < 0
    u = u_ref[0].astype(jnp.float32)         # (1, dk) bonus

    cum = jnp.cumsum(lw, axis=0)
    cum_prev = cum - lw
    total = cum[-1:]                          # (1, dk)
    mid = cum[chunk // 2][None]               # midpoint renormalizer

    q_in = r * jnp.exp(cum_prev)              # decay from chunk start (<=1)
    q_mid = r * jnp.exp(cum_prev - mid)
    k_mid = k * jnp.exp(mid - cum)
    k_out = k * jnp.exp(total - cum)          # decay to chunk end (<=1)

    s_prev = s_scr[...]
    o_inter = jax.lax.dot_general(q_in, s_prev, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    att = jax.lax.dot_general(q_mid, k_mid, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    att = jnp.where(si < ti, att, 0.0)        # strictly lower triangular
    o_intra = jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    o_diag = jnp.sum(r * u * k, axis=1, keepdims=True) * v

    s_scr[...] = jnp.exp(total).T * s_prev + jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    o_ref[0, 0] = (o_inter + o_intra + o_diag).astype(o_ref.dtype)

    @pl.when(ci == nc - 1)
    def _final():
        sfin_ref[0, 0] = s_scr[...].astype(sfin_ref.dtype)


def rwkv6_chunked_bhsd(r: jax.Array, k: jax.Array, v: jax.Array,
                       logw: jax.Array, u: jax.Array, *, chunk: int = 64,
                       interpret: bool | None = None):
    """r/k/v/logw: (B, H, S, dh); u: (H, dh).  Returns (out (B,H,S,dh),
    final state (B,H,dk,dv)).  S must be a multiple of `chunk` (the ops.py
    wrapper pads)."""
    b, h, s, dh = r.shape
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk)
    out, sfin = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, chunk, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, dh), lambda bi, hi, ci: (hi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, dh), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, dh, dh), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dh), r.dtype),
            jax.ShapeDtypeStruct((b, h, dh, dh), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dh, dh), jnp.float32)],
        interpret=interpret,
    )(r, k, v, logw, u)
    return out, sfin
