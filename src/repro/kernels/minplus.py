"""(min, +) matrix-product Pallas kernel — the blocked Floyd-Warshall hot spot.

C[i, j] = min_k A[i, k] + B[k, j].  Tropical semiring ⇒ no MXU; this is a VPU
kernel, so the tiling objective is purely memory-hierarchy: stage (bk, bm) A^T
and (bk, bn) B tiles in VMEM, keep a running-min accumulator in VMEM, and walk k
innermost.  The inner product is unrolled over the bk dimension in steps of
``uk`` rank-1 (min, +) updates to bound VREG pressure (a full (bm, bk, bn)
broadcast would not fit in VMEM for useful block sizes).  A is staged
*transposed*: step s reads rows ``s*uk..`` of the (bk, bm) A^T tile — a
sublane slice, which the TPU lowers at any multiple-of-8 offset — and
transposes that (uk, bm) strip in VMEM; a lane slice of A itself at a
dynamic offset that is not a multiple of 128 does not lower.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _minplus_kernel(at_ref, b_ref, o_ref, acc_ref, *, k_steps: int, uk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, jnp.inf)

    def body(s, acc):
        off = pl.multiple_of(s * uk, uk)
        a_sl = at_ref[pl.ds(off, uk), :].astype(jnp.float32).T   # (bm, uk)
        b_sl = b_ref[pl.ds(off, uk), :].astype(jnp.float32)      # (uk, bn)
        for j in range(uk):                 # uk rank-1 (min, +) updates
            acc = jnp.minimum(acc, a_sl[:, j:j + 1] + b_sl[j:j + 1, :])
        return acc

    acc_ref[...] = lax.fori_loop(0, at_ref.shape[0] // uk, body, acc_ref[...])

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def minplus_pallas(a: jax.Array, b: jax.Array, *, bm: int = 256, bn: int = 256,
                   bk: int = 256, uk: int = 8,
                   interpret: bool = False) -> jax.Array:
    """C = A ⊗ B over the (min, +) semiring, VMEM-tiled."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    uk = min(uk, bk)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0 and bk % uk == 0
    k_steps = k // bk

    kernel = functools.partial(_minplus_kernel, k_steps=k_steps, uk=uk)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a.T, b)
