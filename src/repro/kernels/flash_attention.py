"""Pallas TPU flash-attention kernel (online softmax, causal / sliding
window, GQA-aware kv-head indexing).

TPU mapping:
  * grid = (B, H, num_q_blocks, num_k_blocks); the last grid dimension is
    sequential on TPU, so VMEM scratch (m, l, acc) carries the online-softmax
    state across k-blocks of one q-block.
  * BlockSpecs tile Q to (block_q, head_dim) and K/V to (block_k, head_dim)
    in VMEM; head_dim and block sizes are multiples of 128 for MXU alignment
    (tests sweep smaller shapes in interpret mode; production blocks are
    q=512, k=512, dh in {64,128,256} -> working set
    2*(bq*dh + 2*bk*dh + bq*bk) * 4B  ~=  3.3 MB at bq=bk=512, dh=128,
    comfortably inside the ~16 MB VMEM budget with double buffering).
  * GQA: the kv BlockSpec index map selects kv head = h // (H // H_kv), so
    kv tiles are fetched once per kv head group, not H/H_kv times.
  * causal/window: tiles entirely above the diagonal (or entirely outside
    the sliding-window band) are skipped with pl.when — no MXU work and no
    accumulator traffic for masked-out tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  window: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    q_start = qi * block_q
    k_start = ki * block_k

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile-level skip: fully-masked tiles do no MXU work
    relevant = k_start < seq_k
    if causal:
        relevant = jnp.logical_and(relevant,
                                   k_start <= q_start + block_q - 1)
    if window > 0:
        relevant = jnp.logical_and(
            relevant, k_start + block_k - 1 > q_start - window)

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)                 # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        ok = k_pos < seq_k
        if causal:
            ok = jnp.logical_and(ok, k_pos <= q_pos)
        if window > 0:
            ok = jnp.logical_and(ok, k_pos > q_pos - window)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int = 0,
                         block_q: int = 512, block_k: int = 512,
                         interpret: bool | None = None) -> jax.Array:
    """q: (B, H, Sq, Dh); k, v: (B, Hkv, Sk, Dh) with H % Hkv == 0.
    Returns (B, H, Sq, Dh)."""
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    assert h % hkv == 0, (h, hkv)
    group = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    nq = -(-sq // block_q)
    nk = -(-sk // block_k)
    pad_q = nq * block_q - sq
    pad_k = nk * block_k - sk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / (dh ** 0.5), block_q=block_q,
        block_k=block_k, causal=causal, window=int(window), seq_k=sk)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, nq * block_q, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
