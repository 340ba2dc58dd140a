"""Multi-accelerator GEMM — the paper's Tesla S2050 section, TPU-native.

The paper notes the block decomposition that feeds shared memory also
splits a GEMM across 4 GPUs, *if* the matrices are large enough to
amortise transfer. On TPU the analogue is mesh-sharded GEMM under
`shard_map`, and 'large enough' becomes a roofline statement
(core.intensity) about ICI bytes vs MXU flops.

Three schedules, increasing in sophistication:

  column_parallel    W sharded on N; no comm in fwd (comm in bwd).
  row_parallel       W sharded on K; one reduce-scatter (or all-reduce).
  ring_matmul        W sharded on K and *cycled* around the ring with
                     collective_permute while each device multiplies the
                     K-block it currently holds — the compute hides the
                     permute (async start/done in HLO). This is the
                     beyond-paper overlap schedule measured in §Perf.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import gemm as _gemm


def column_parallel(x, w, *, axis: str, backend: str | None = None):
    """Y[..., N/p] = X @ W[:, N/p]; inputs replicated, output sharded."""
    return _gemm.matmul(x, w, backend=backend)


def row_parallel(x, w, *, axis: str, backend: str | None = None,
                 scatter: bool = True):
    """X sharded on K (last dim), W sharded on K (first dim).

    scatter=True emits reduce-scatter (output row-sharded), else
    all-reduce (output replicated).
    """
    part = _gemm.matmul(x, w, backend=backend)
    if scatter:
        return jax.lax.psum_scatter(part, axis, scatter_dimension=part.ndim - 1,
                                    tiled=True)
    return jax.lax.psum(part, axis)


def ring_matmul(x, w, *, axis: str, backend: str | None = None):
    """Ring-overlapped Y = X @ W.

    Per-device state: x_local (M_local, K) — full K; w_local (K/p, N) —
    this device's K-block of W. Step t: multiply the K-block we hold,
    pass it to the next ring neighbour. P-1 permutes hide behind P local
    GEMMs of shape (M_local, K/p, N).
    """
    p = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    kb = w.shape[0]          # local K block
    n = w.shape[1]
    perm = [(i, (i + 1) % p) for i in range(p)]

    def body(t, carry):
        acc, w_t = carry
        # K-block currently held = the one originally owned by (idx - t).
        owner = (idx - t) % p
        x_blk = jax.lax.dynamic_slice_in_dim(x, owner * kb, kb, axis=x.ndim - 1)
        acc = acc + _gemm.matmul(x_blk, w_t, backend=backend)
        w_t = jax.lax.ppermute(w_t, axis, perm)
        return acc, w_t

    acc0 = jnp.zeros(x.shape[:-1] + (n,), dtype=x.dtype)
    acc, _ = jax.lax.fori_loop(0, p, body, (acc0, w))
    return acc


def sharded_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    mesh: Mesh,
    *,
    axis: str = "model",
    schedule: str = "ring",
    backend: str | None = None,
) -> jnp.ndarray:
    """Top-level multi-device GEMM (the S2050 reproduction entry point).

    A (M, K) is sharded on M over `axis` for ring/column, on K for row;
    B (K, N) is sharded to match the schedule. Returns the full product.
    The per-device GEMM may be a Pallas kernel, whose output type
    carries no varying-mesh-axes information, so the shard_maps run
    with check_vma=False.
    """
    if schedule == "ring":
        fn = jax.shard_map(
            functools.partial(ring_matmul, axis=axis, backend=backend),
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
        return fn(a, b)
    if schedule == "column":
        fn = jax.shard_map(
            functools.partial(column_parallel, axis=axis, backend=backend),
            mesh=mesh,
            in_specs=(P(axis, None), P(None, None)),
            out_specs=P(axis, None),
            check_vma=False,
        )
        return fn(a, b)
    if schedule == "row":
        fn = jax.shard_map(
            functools.partial(row_parallel, axis=axis, backend=backend,
                              scatter=False),
            mesh=mesh,
            in_specs=(P(None, axis), P(axis, None)),
            out_specs=P(None, None),
            check_vma=False,
        )
        return fn(a, b)
    raise ValueError(f"unknown schedule {schedule!r}")


def comm_model_bytes(m: int, n: int, k: int, p: int, itemsize: int,
                     schedule: str) -> int:
    """ICI bytes per device for each schedule — the 'matrices must be
    very large' claim quantified (used by bench_distributed_gemm)."""
    if schedule == "column":
        return 0
    if schedule == "row":
        return 2 * m * n * itemsize * (p - 1) // p      # all-reduce
    if schedule == "ring":
        return k * n * itemsize * (p - 1) // p          # W blocks cycled
    raise ValueError(schedule)
