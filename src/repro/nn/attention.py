"""GQA attention: naive, chunked (flash-style online softmax in pure JAX),
and Pallas-kernel paths, plus KV-cache decode.

The chunked path is the TPU adaptation that keeps prefill memory O(S * block)
instead of O(S^2): queries are processed in blocks with a running
(max, sum, acc) online-softmax state — the same algorithm the Pallas kernel
implements with explicit VMEM tiling (kernels/flash_attention.py).

Masks: causal, causal + sliding window (``window > 0``), or bidirectional
(``causal=False``, for encoder stacks).  A per-layer scalar window lets
heterogeneous local/global stacks (gemma3's 5:1) stay inside one homogeneous
`lax.scan`.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.nn.layers import apply_rope

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype=jnp.float32):
    kq, kk, kv, ko = jax.random.split(key, 4)
    s = 1.0 / jnp.sqrt(d_model)
    return {
        "wq": (jax.random.normal(kq, (d_model, n_heads, head_dim)) * s).astype(dtype),
        "wk": (jax.random.normal(kk, (d_model, n_kv_heads, head_dim)) * s).astype(dtype),
        "wv": (jax.random.normal(kv, (d_model, n_kv_heads, head_dim)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (n_heads, head_dim, d_model))
               * (1.0 / jnp.sqrt(n_heads * head_dim))).astype(dtype),
    }


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B, S, Hkv, Dh) -> (B, S, H, Dh) by repeating each kv head."""
    hkv = k.shape[-2]
    if hkv == n_heads:
        return k
    rep = n_heads // hkv
    return jnp.repeat(k, rep, axis=-2)


def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: jax.Array | int) -> jax.Array:
    """Additive bias (Sq, Sk): 0 where attendable, NEG_INF elsewhere.
    window: 0 = unlimited; >0 = sliding window (causal only)."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), jnp.bool_)
    if causal:
        ok = dk <= dq
    w = jnp.asarray(window)
    ok = jnp.where(w > 0, jnp.logical_and(ok, dk > dq - w), ok)
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def attention_naive(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: jax.Array | int = 0,
                    q_offset: int = 0) -> jax.Array:
    """q: (B, Sq, H, Dh); k, v: (B, Sk, Hkv, Dh).  O(Sq*Sk) memory."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    bias = _mask_bias(jnp.arange(sq) + q_offset, jnp.arange(sk), causal, window)
    logits = logits + bias[None, None]
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: jax.Array | int = 0,
                      q_block: int = 512, k_block: int = 512) -> jax.Array:
    """Flash-style online-softmax attention in pure JAX (O(S*block) memory).

    Scans key blocks inside a scan over query blocks, maintaining
    (running max, running sum, accumulator)."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    q_block = min(q_block, sq)
    k_block = min(k_block, sk)
    nq = -(-sq // q_block)
    nk = -(-sk // k_block)
    # pad to multiples
    pad_q = nq * q_block - sq
    pad_k = nk * k_block - sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)

    kp_blocks = kp.reshape(b, nk, k_block, h, dh)
    vp_blocks = vp.reshape(b, nk, k_block, h, dh)

    def q_block_fn(qi, q_blk):
        q_pos = qi * q_block + jnp.arange(q_block)

        def k_body(carry, inp):
            m, l, acc = carry
            kj, k_blk, v_blk = inp
            k_pos = kj * k_block + jnp.arange(k_block)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk.astype(jnp.float32),
                                k_blk.astype(jnp.float32)) * scale
            bias = _mask_bias(q_pos, k_pos, causal, window)
            kvalid = (k_pos < sk)[None, :]
            bias = jnp.where(kvalid, bias, NEG_INF)
            logits = logits + bias[None, None]
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        acc0 = jnp.zeros((b, h, q_block, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_body, (m0, l0, acc0),
            (jnp.arange(nk),
             jnp.moveaxis(kp_blocks, 1, 0), jnp.moveaxis(vp_blocks, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return jnp.moveaxis(out, 1, 2)  # (b, q_block, h, dh)

    qp_blocks = jnp.moveaxis(qp.reshape(b, nq, q_block, h, dh), 1, 0)
    outs = jax.lax.map(lambda args: q_block_fn(*args),
                       (jnp.arange(nq), qp_blocks))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_block, h, dh)
    return out[:, :sq].astype(q.dtype)


def _shard_attention_inputs(q, k, v):
    """Pin the attention working set to the 'model' axis: heads when they
    divide it, else q's sequence dim (context parallelism).  Without this,
    archs whose head count doesn't divide the TP axis (smollm 9H, gemma3 8H
    on model=16) compute attention fully replicated across 'model' — 16x
    redundant FLOPs/bytes (measured on the smollm train_4k dry-run)."""
    from repro.dist.sharding import _current_mesh, batch_axes
    mesh = _current_mesh()
    if mesh is None or "model" not in getattr(mesh, "axis_names", ()):
        return q, k, v
    import jax as _jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    n = mesh.shape["model"]
    ba = batch_axes(mesh)
    nb = 1
    for a in ba:
        nb *= mesh.shape[a]
    bspec = ba if (ba and q.shape[0] % nb == 0 and q.shape[0] >= nb) else None

    def cons(x, spec):
        return _jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    h, hkv = q.shape[2], k.shape[2]
    if h % n == 0 and hkv % n == 0:
        spec = P(bspec, None, "model", None)
        return cons(q, spec), cons(k, spec), cons(v, spec)
    if q.shape[1] % n == 0:
        # context parallelism: queries sharded over seq; k/v left to GSPMD
        # propagation (an explicit replication pin here segfaults the
        # XLA:CPU SPMD partitioner and buys nothing — k/v are gathered
        # against the seq-sharded q either way)
        q = cons(q, P(bspec, "model", None, None))
    return q, k, v




# ---------------------------------------------------------------------------
# flash-attention custom VJP (recompute-in-backward)
#
# Differentiating through the online-softmax scans makes JAX stack every
# k-block's probability matrix as a scan residual — O(S^2) backward traffic
# (measured: the dominant bytes of the smollm train_4k dry-run).  The
# textbook flash backward stores only (out, rowwise logsumexp) and
# recomputes each block's P in the reverse pass:
#     D   = rowsum(dO * O)
#     P   = exp(S - L)            (recomputed per block)
#     dV += P^T dO ;  dP = dO V^T ;  dS = P * (dP - D)
#     dQ += dS K * scale ;  dK += dS^T Q * scale
# ---------------------------------------------------------------------------

def _win_blocks(window_static, k_block: int, nk: int):
    """Static count of k-blocks a q-block can see under a sliding window
    (None = no static skip)."""
    if window_static is None or window_static <= 0:
        return None
    import math
    wb = min(math.ceil(window_static / k_block) + 1, nk)
    return wb


def _flash_core(q, k, v, window, *, causal: bool, q_block: int,
                k_block: int, window_static=None):
    """q/k/v: (B, S, H, Dh) (kv already head-repeated).  Returns
    (out (B,Sq,H,Dh), lse (B,H,Sq))."""
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    nq = -(-sq // q_block)
    nk = -(-sk // k_block)
    pad_q = nq * q_block - sq
    pad_k = nk * k_block - sk
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    scale = 1.0 / jnp.sqrt(dh).astype(jnp.float32)
    kb_ = jnp.moveaxis(kp.reshape(b, nk, k_block, h, dh), 1, 0)
    vb_ = jnp.moveaxis(vp.reshape(b, nk, k_block, h, dh), 1, 0)
    # static sliding-window skip: a q-block only sees the last `wb` k-blocks
    wb = _win_blocks(window_static, k_block, nk) if causal else None

    def q_block_fn(qi, q_blk):
        q_pos = qi * q_block + jnp.arange(q_block)
        if wb is not None and wb < nk:
            start = jnp.clip(qi - (wb - 1), 0, nk - wb)
            kb_loc = jax.lax.dynamic_slice_in_dim(kb_, start, wb, axis=0)
            vb_loc = jax.lax.dynamic_slice_in_dim(vb_, start, wb, axis=0)
            kidx = start + jnp.arange(wb)
        else:
            kb_loc, vb_loc, kidx = kb_, vb_, jnp.arange(nk)

        def k_body(carry, inp):
            m, l, acc = carry
            kj, k_blk, v_blk = inp
            k_pos = kj * k_block + jnp.arange(k_block)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk.astype(jnp.float32),
                                k_blk.astype(jnp.float32)) * scale
            bias = _mask_bias(q_pos, k_pos, causal, window)
            bias = jnp.where((k_pos < sk)[None, :], bias, NEG_INF)
            logits = logits + bias[None, None]
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        acc0 = jnp.zeros((b, h, q_block, dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            k_body, (m0, l0, acc0), (kidx, kb_loc, vb_loc))
        lsafe = jnp.maximum(l, 1e-30)
        out = acc / lsafe[..., None]
        lse = m + jnp.log(lsafe)
        return jnp.moveaxis(out, 1, 2), lse      # (b,qb,h,dh), (b,h,qb)

    qb_ = jnp.moveaxis(qp.reshape(b, nq, q_block, h, dh), 1, 0)
    outs, lses = jax.lax.map(lambda a: q_block_fn(*a), (jnp.arange(nq), qb_))
    out = jnp.moveaxis(outs, 0, 1).reshape(b, nq * q_block, h, dh)
    lse = jnp.concatenate(jnp.unstack(lses, axis=0), axis=-1)
    return out[:, :sq].astype(q.dtype), lse[..., :sq]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_attention(q, k, v, window, causal, q_block, k_block,
                     window_static=None):
    out, _ = _flash_core(q, k, v, window, causal=causal, q_block=q_block,
                         k_block=k_block, window_static=window_static)
    return out


def _flash_fwd(q, k, v, window, causal, q_block, k_block,
               window_static=None):
    out, lse = _flash_core(q, k, v, window, causal=causal, q_block=q_block,
                           k_block=k_block, window_static=window_static)
    return out, (q, k, v, window, out, lse)


def _flash_bwd(causal, q_block, k_block, window_static, res, dout):
    import numpy as _np
    q, k, v, window, out, lse = res
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    nq = -(-sq // q_block)
    nk = -(-sk // k_block)
    pad_q = nq * q_block - sq
    pad_k = nk * k_block - sk
    f32 = jnp.float32
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).astype(f32)
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))).astype(f32)
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))).astype(f32)
    dop = jnp.pad(dout.astype(f32), ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    op = jnp.pad(out.astype(f32), ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q)), constant_values=0.0)
    scale = 1.0 / jnp.sqrt(dh).astype(f32)
    # D_i = rowsum(dO * O): (b, h, sq_padded)
    dvec = jnp.einsum("bqhd,bqhd->bhq", dop, op)

    qb_ = jnp.moveaxis(qp.reshape(b, nq, q_block, h, dh), 1, 0)
    dob_ = jnp.moveaxis(dop.reshape(b, nq, q_block, h, dh), 1, 0)
    kb_ = jnp.moveaxis(kp.reshape(b, nk, k_block, h, dh), 1, 0)
    vb_ = jnp.moveaxis(vp.reshape(b, nk, k_block, h, dh), 1, 0)
    lse_b = jnp.moveaxis(lsep.reshape(b, h, nq, q_block), 2, 0)
    dvec_b = jnp.moveaxis(dvec.reshape(b, h, nq, q_block), 2, 0)
    wbq = _win_blocks(window_static, k_block, nk) if causal else None
    wbk = _win_blocks(window_static, q_block, nq) if causal else None

    def block_p(qi, kj, q_blk, k_blk, lse_blk):
        q_pos = qi * q_block + jnp.arange(q_block)
        k_pos = kj * k_block + jnp.arange(k_block)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk) * scale
        bias = _mask_bias(q_pos, k_pos, causal, window)
        bias = jnp.where((k_pos < sk)[None, :], bias, NEG_INF)
        logits = logits + bias[None, None]
        return jnp.exp(logits - lse_blk[..., None])     # (b,h,qb,kb)

    # pass 1: dq — scan q blocks, inner scan k blocks
    def dq_block(qi, q_blk, do_blk, lse_blk, d_blk):
        if wbq is not None and wbq < nk:
            start = jnp.clip(qi - (wbq - 1), 0, nk - wbq)
            kb_loc = jax.lax.dynamic_slice_in_dim(kb_, start, wbq, axis=0)
            vb_loc = jax.lax.dynamic_slice_in_dim(vb_, start, wbq, axis=0)
            kidx = start + jnp.arange(wbq)
        else:
            kb_loc, vb_loc, kidx = kb_, vb_, jnp.arange(nk)

        def k_body(dq_acc, inp):
            kj, k_blk, v_blk = inp
            p = block_p(qi, kj, q_blk, k_blk, lse_blk)
            dp = jnp.einsum("bqhd,bkhd->bhqk", do_blk, v_blk)
            ds = p * (dp - d_blk[..., None])
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, k_blk) * scale
            return dq_acc, None
        dq0 = jnp.zeros((b, q_block, h, dh), f32)
        dq_blk, _ = jax.lax.scan(k_body, dq0, (kidx, kb_loc, vb_loc))
        return dq_blk

    dqs = jax.lax.map(lambda a: dq_block(*a),
                      (jnp.arange(nq), qb_, dob_, lse_b, dvec_b))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(b, nq * q_block, h, dh)[:, :sq]

    # pass 2: dk/dv — scan k blocks, inner scan q blocks
    def dkv_block(kj, k_blk, v_blk):
        if wbk is not None and wbk < nq:
            start = jnp.clip(kj, 0, nq - wbk)
            qb_loc = jax.lax.dynamic_slice_in_dim(qb_, start, wbk, axis=0)
            dob_loc = jax.lax.dynamic_slice_in_dim(dob_, start, wbk, axis=0)
            lse_loc = jax.lax.dynamic_slice_in_dim(lse_b, start, wbk, axis=0)
            dvec_loc = jax.lax.dynamic_slice_in_dim(dvec_b, start, wbk,
                                                    axis=0)
            qidx = start + jnp.arange(wbk)
        else:
            qb_loc, dob_loc, lse_loc, dvec_loc = qb_, dob_, lse_b, dvec_b
            qidx = jnp.arange(nq)

        def q_body(carry, inp):
            dk_acc, dv_acc = carry
            qi, q_blk, do_blk, lse_blk, d_blk = inp
            p = block_p(qi, kj, q_blk, k_blk, lse_blk)
            dv_acc = dv_acc + jnp.einsum("bhqk,bqhd->bkhd", p, do_blk)
            dp = jnp.einsum("bqhd,bkhd->bhqk", do_blk, v_blk)
            ds = p * (dp - d_blk[..., None])
            dk_acc = dk_acc + jnp.einsum("bhqk,bqhd->bkhd", ds, q_blk) * scale
            return (dk_acc, dv_acc), None
        z = jnp.zeros((b, k_block, h, dh), f32)
        (dk_blk, dv_blk), _ = jax.lax.scan(
            q_body, (z, z), (qidx, qb_loc, dob_loc, lse_loc, dvec_loc))
        return dk_blk, dv_blk

    dks, dvs = jax.lax.map(lambda a: dkv_block(*a),
                           (jnp.arange(nk), kb_, vb_))
    dk = jnp.moveaxis(dks, 0, 1).reshape(b, nk * k_block, h, dh)[:, :sk]
    dv = jnp.moveaxis(dvs, 0, 1).reshape(b, nk * k_block, h, dh)[:, :sk]

    dwindow = _np.zeros((), jax.dtypes.float0) \
        if jnp.issubdtype(jnp.asarray(window).dtype, jnp.integer) \
        else jnp.zeros_like(jnp.asarray(window))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dwindow)


_flash_attention.defvjp(_flash_fwd, _flash_bwd)


def attention(q, k, v, *, causal=True, window: jax.Array | int = 0,
              impl: str = "auto", q_offset: int = 0):
    """Dispatch: 'naive' | 'chunked' | 'pallas' | 'auto'."""
    sq, sk = q.shape[1], k.shape[1]
    q, k, v = _shard_attention_inputs(q, k, v)
    if impl == "auto":
        impl = "chunked" if max(sq, sk) > 2048 else "naive"
    if impl == "naive":
        return attention_naive(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)
    if impl == "chunked":
        # custom-VJP flash path: identical forward to attention_chunked but
        # with a recompute-in-backward gradient (no stacked P residuals).
        # A static python window enables trace-time k-block skipping.
        h = q.shape[2]
        k = _repeat_kv(k, h)
        v = _repeat_kv(v, h)
        qb = min(512, q.shape[1])
        kb = min(512, k.shape[1])
        wstat = int(window) if isinstance(window, int) else None
        return _flash_attention(q, k, v, jnp.asarray(window), causal, qb, kb,
                                wstat)
    if impl == "chunked_ad":
        return attention_chunked(q, k, v, causal=causal, window=window)
    if impl == "pallas":
        from repro.kernels.ops import flash_attention
        return flash_attention(q, k, v, causal=causal, window=int(window))
    raise ValueError(impl)


def attention_block(params, x: jax.Array, *, n_heads: int, rope_theta: float,
                    causal: bool = True, window: jax.Array | int = 0,
                    impl: str = "auto", positions: Optional[jax.Array] = None,
                    kv_x: Optional[jax.Array] = None) -> jax.Array:
    """Full projection + attention + output.  kv_x enables cross-attention."""
    b, s, _ = x.shape
    src = x if kv_x is None else kv_x
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", src, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", src, params["wv"].astype(x.dtype))
    if positions is None:
        positions = jnp.arange(s)[None, :]
    if rope_theta > 0:
        q = apply_rope(q, positions, rope_theta)
        kpos = positions if kv_x is None else jnp.arange(src.shape[1])[None, :]
        k = apply_rope(k, kpos, rope_theta)
    o = attention(q, k, v, causal=causal and kv_x is None, window=window,
                  impl=impl)
    return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------

def decode_attention_block(params, x: jax.Array, cache_k: jax.Array,
                           cache_v: jax.Array, pos: jax.Array, *,
                           n_heads: int, rope_theta: float,
                           window: jax.Array | int = 0):
    """One-token decode.  x: (B, 1, D); cache_k/v: (B, S_max, Hkv, Dh);
    pos: scalar current position.  Returns (out, cache_k, cache_v)."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
    posb = jnp.full((x.shape[0], 1), pos)
    if rope_theta > 0:
        q = apply_rope(q, posb, rope_theta)
        k_new = apply_rope(k_new, posb, rope_theta)
    cache_k = jax.lax.dynamic_update_slice_in_dim(
        cache_k, k_new.astype(cache_k.dtype), pos, axis=1)
    cache_v = jax.lax.dynamic_update_slice_in_dim(
        cache_v, v_new.astype(cache_v.dtype), pos, axis=1)
    s_max = cache_k.shape[1]
    h = q.shape[2]
    kk = _repeat_kv(cache_k.astype(jnp.float32), h)
    vv = _repeat_kv(cache_v.astype(jnp.float32), h)
    dh = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), kk)
    logits = logits / jnp.sqrt(dh)
    k_pos = jnp.arange(s_max)
    ok = k_pos <= pos
    w = jnp.asarray(window)
    ok = jnp.where(w > 0, jnp.logical_and(ok, k_pos > pos - w), ok)
    logits = jnp.where(ok[None, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, vv).astype(x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(x.dtype))
    return out, cache_k, cache_v
