"""Hierarchy-blind Pallas GEMM — the TPU analogue of the paper's
Listing 3 (the 'nieoptymalna' version).

The CUDA original gives every thread one output element and streams the
full row of A / column of B from *global* memory with zero cross-thread
reuse. A literal port is impossible (Pallas kernels compute on VMEM
refs), so the honest analogue keeps the structural sin — *no k-blocking
and minimal staging reuse* — within TPU constraints:

  * grid is (M/bm, N/bn) only; each cell stages the FULL (bm, K) strip
    of A and (K, bn) strip of B;
  * tiles are the minimum hardware shape (sublane x lane), so the reuse
    factor per loaded byte is bm (=8 for f32) vs the tiled kernel's
    256+ — matching the paper's 'one row / one column per thread'
    traffic ratio as closely as the ISA allows;
  * it simply cannot run for large K (the strips overflow VMEM), which
    is the paper's scalability argument against Listing 3 made physical.

Used only by benchmarks (Fig. 8 before/after) and tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import mxu_precision


def _naive_kernel(a_ref, b_ref, o_ref, *, out_dtype):
    acc_dtype = jnp.float64 if a_ref.dtype == jnp.float64 else jnp.float32
    o_ref[...] = jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=acc_dtype,
        precision=mxu_precision(a_ref.dtype),
    ).astype(out_dtype)


def matmul_naive(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 8,
    bn: int = 128,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    m, ka = a.shape
    kb, n = b.shape
    assert ka == kb
    if out_dtype is None:
        out_dtype = a.dtype
    bm, bn = min(bm, m), min(bn, n)
    assert m % bm == 0 and n % bn == 0, (m, n, bm, bn)

    kernel = functools.partial(_naive_kernel, out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, ka), lambda i, j: (i, 0)),
            pl.BlockSpec((ka, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        name="matmul_naive",
    )(a, b)
