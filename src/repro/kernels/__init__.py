"""Pallas TPU kernels for the paper's hot spots (tiled GEMM, flash
attention, SSD) plus their policy-dispatched wrappers (kernels.ops)."""

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core import blocking


def compiler_params(*dimension_semantics: str) -> pltpu.CompilerParams:
    """Mosaic parameters shared by every kernel: the grid's dimension
    semantics, and a scoped VMEM limit equal to the budget the tile
    choosers in core.blocking size working sets against (the compiler's
    own default scope is smaller than that budget)."""
    return pltpu.CompilerParams(
        dimension_semantics=dimension_semantics,
        vmem_limit_bytes=blocking.vmem_budget())


def mxu_precision(dtype):
    """Contraction precision for a kernel's MXU dot on `dtype` operands.
    Mosaic's default feeds f32 operands through one bf16 pass (about
    three significant digits); HIGHEST keeps the f32 product. Other
    dtypes keep the default."""
    return (jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else None)


def row_to_column(row: jnp.ndarray) -> jnp.ndarray:
    """A lane-dense (1, n) row -> its (n, 1) column inside a kernel, by a
    lane-aligned (128, n) -> (n, 128) transpose (no arithmetic)."""
    return jnp.broadcast_to(row, (128, row.shape[1])).T[:, :1]
