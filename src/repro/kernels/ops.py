"""jit'd public wrappers for the Pallas kernels (model-facing layouts) plus
the fused RK stage-combine kernel used by the adjoint hot path.

Every kernel here (and in flash_attention/rwkv6_scan) runs through the
Pallas interpreter on the CPU backend, which the tests use, and compiles
with Mosaic on every other backend.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rwkv6_scan import rwkv6_chunked_bhsd


# ---------------------------------------------------------------------------
# fused linear combination (the RK stage-update / stage-adjoint primitive)
#
# Every hot operation of the discrete adjoint is the same shape of math:
#
#   forward stage inputs   x_i = u + h * sum_j a_ij k_j
#   forward combine        u'  = u + h * sum_i b_i  k_i
#   adjoint stage weights  v_i = b_i * lam + sum_{j>i} a_ji w_j
#
# i.e. out = (base_coeff * base) + sum_i c_i * term_i with trace-time
# tableau weights.  Unfused, each term lowers to a separate mul+add pair
# with its own output buffer; this kernel emits ONE pallas_call per pytree
# leaf with the whole accumulation inside, in the exact order the unfused
# ``tree_axpy`` chain uses — so results (and therefore the adjoint's
# gradients) are bitwise-identical to the unfused path when both run under
# jit (XLA's FMA contraction is consistent within a compiled program).
# ---------------------------------------------------------------------------


def _lincomb_kernel_static(*refs, coeffs, base_coeff):
    """out = base_coeff*base + sum_i coeffs[i]*terms[i]; coeffs are
    trace-time Python floats (fixed-step path: h folded into coeffs)."""
    base_ref = refs[0]
    out_ref = refs[-1]
    term_refs = refs[1:-1]
    acc = base_ref[...]
    if base_coeff is not None:
        acc = base_coeff * acc
    for c, r in zip(coeffs, term_refs):
        acc = acc + c * r[...]
    out_ref[...] = acc


def _lincomb_kernel_scaled(*refs, weights, base_coeff):
    """Like _lincomb_kernel_static but the per-term coefficient is
    h * weights[i] with h a traced scalar operand (adaptive-step path) —
    computed inside the kernel in the same order the unfused chain uses."""
    base_ref, h_ref = refs[0], refs[1]
    out_ref = refs[-1]
    term_refs = refs[2:-1]
    h = h_ref[0]
    acc = base_ref[...]
    if base_coeff is not None:
        acc = base_coeff * acc
    for w, r in zip(weights, term_refs):
        acc = acc + (h * w) * r[...]
    out_ref[...] = acc


#: leaves above one block run on a lane-dense ``(rows, 128)`` view in
#: blocks of 1024 rows: 512 KiB per f32 operand buffer, so even dopri5's
#: seven terms plus base and output, double-buffered, stay inside the
#: default scoped VMEM.  A leaf that fits one block runs gridless on its
#: flat vector (also the form whose XLA:CPU interpretation is bitwise
#: equal to the unfused chain).
_LANES = 128
_BLOCK_ROWS = 1024


def fused_lincomb(base: jax.Array, terms, weights, scale=None,
                  base_coeff: float | None = None, *,
                  interpret: bool | None = None) -> jax.Array:
    """One-kernel ``base_coeff*base + sum_i (scale*weights[i]) * terms[i]``.

    ``weights`` are trace-time floats (Butcher-tableau entries); ``scale``
    is the step size h — a Python float (fixed-step: folded into the
    coefficients at trace time) or a traced scalar (adaptive: passed as a
    kernel operand in SMEM).  ``base_coeff=None`` means the base enters
    unscaled (the RK state-update form); a float (including 0.0)
    multiplies it first (the adjoint ``v_i = b_i*lam + ...`` form).  Zero
    weights must be dropped by the caller (to mirror the unfused chain's
    trace-time skip).

    A leaf larger than one ``(1024, 128)`` block is viewed as rows of 128
    lanes, its tail zero-padded to whole blocks, and the kernel runs over
    a 1-D grid of row blocks; the padding is sliced off the result.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    shape, n = base.shape, base.size
    rows = -(-n // _LANES)
    if rows <= _BLOCK_ROWS:
        out_shape, grid = (n,), ()
        blk = pl.BlockSpec(memory_space=pltpu.VMEM)
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)

        def view(x):
            return x.reshape(-1)
    else:
        rows_p = -(-rows // _BLOCK_ROWS) * _BLOCK_ROWS
        out_shape, grid = (rows_p, _LANES), (rows_p // _BLOCK_ROWS,)
        # int32 block indices: a Python 0 (or the default index map) is
        # int64 under x64, which Mosaic cannot lower
        blk = pl.BlockSpec((_BLOCK_ROWS, _LANES),
                           lambda i: (i, jnp.int32(0)))
        smem = pl.BlockSpec(index_map=lambda i: (jnp.int32(0),),
                            memory_space=pltpu.SMEM)

        def view(x):
            x = jnp.pad(x.reshape(-1), (0, rows_p * _LANES - n))
            return x.reshape(rows_p, _LANES)

    call = functools.partial(
        pl.pallas_call, out_shape=jax.ShapeDtypeStruct(out_shape, base.dtype),
        grid=grid, out_specs=blk, interpret=interpret)
    vterms = [view(t) for t in terms]
    if scale is None or isinstance(scale, (int, float)):
        coeffs = [w if scale is None else float(scale) * w for w in weights]
        kern = functools.partial(_lincomb_kernel_static, coeffs=coeffs,
                                 base_coeff=base_coeff)
        out = call(kern, in_specs=[blk] * (1 + len(vterms)))(view(base),
                                                             *vterms)
    else:
        kern = functools.partial(_lincomb_kernel_scaled, weights=list(weights),
                                 base_coeff=base_coeff)
        h_op = jnp.asarray(scale, base.dtype).reshape(1)
        out = call(kern, in_specs=[blk, smem] + [blk] * len(vterms))(
            view(base), h_op, *vterms)
    out = out.reshape(-1)
    if out.size != n:
        out = out[:n]
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512):
    """Model layout: q (B,S,H,Dh), k/v (B,S,Hkv,Dh) -> (B,S,H,Dh)."""
    qt = jnp.moveaxis(q, 1, 2)
    kt = jnp.moveaxis(k, 1, 2)
    vt = jnp.moveaxis(v, 1, 2)
    o = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                             block_q=block_q, block_k=block_k)
    return jnp.moveaxis(o, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def rwkv6_chunked(r, k, v, logw, u, *, chunk: int = 64):
    """Model layout: r/k/v/logw (B,S,H,Dh), u (H,Dh).
    Returns (out (B,S,H,Dh), final_state (B,H,dk,dv))."""
    s = r.shape[1]
    pad = (-s) % chunk
    def mov(t):
        tt = jnp.moveaxis(t, 1, 2)
        if pad:
            tt = jnp.pad(tt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        return tt
    out, sfin = rwkv6_chunked_bhsd(mov(r), mov(k), mov(v), mov(logw), u,
                                   chunk=chunk)
    return jnp.moveaxis(out, 1, 2)[:, :s], sfin
