"""Tiled Pallas GEMM — the TPU adaptation of the paper's Listing 4 —
plus fused epilogues and the dual-GEMM gated (SwiGLU) variant.

The CUDA original stages BLOCK x BLOCK sub-matrices of A and B into
shared memory, __syncthreads(), FMAs over the block's k range, and
accumulates in a register. The TPU version:

  * the grid is (M/bm, N/bn, K/bk) with k innermost ("arbitrary"
    semantics) — the k loop of Listing 4 becomes the minor grid dim;
  * BlockSpec index maps stage (bm, bk) and (bk, bn) tiles into VMEM —
    Mosaic double-buffers the HBM->VMEM DMA, which replaces the paper's
    explicit __syncthreads() staging discipline;
  * accumulation happens in an f32 VMEM scratch tile (the register
    C_temporary of the paper, grown to a full output tile) and is cast
    to the output dtype on the last k step;
  * jnp.dot inside the kernel body maps onto the 128x128 MXU with
    preferred_element_type=f32.

Fused epilogues extend the paper's staying-in-fast-memory argument to
the operator *chain*: the last-k flush — the only moment the f32
accumulator is in registers anyway — applies bias / activation /
residual before the single HBM write, so the (M, N) intermediate of the
unfused composition never round-trips through HBM. The epilogue operand
(a (1, N) bias row or (M, N) residual) is streamed through its own
BlockSpec. Supported epilogues:

    none       C = A @ B
    bias       C = A @ B + bias
    bias_gelu  C = gelu(A @ B + bias)
    bias_silu  C = silu(A @ B + bias)
    residual   C = A @ B + R

`gated_matmul_tiled` goes one step further for the SwiGLU hot path: one
A tile is staged against TWO weight operands (W_gate, W_up), two f32
accumulators run in parallel, and the flush emits
``silu(A @ Wg) * (A @ Wu)`` in a single pass — both (M, N)
intermediates of the unfused composition are eliminated.

`matmul_q_tiled` extends the same staying-in-fast-memory argument to
the *operand encoding*: the weight operand streams through HBM as int8
(1 byte/element, a 2-4x reduction on the dominant weight-side traffic),
is widened to the activation dtype in-register for the MXU dot (int8
magnitudes <= 127 are exact in bf16), and the per-channel f32 scales —
constant along k, so they commute with the contraction — are applied
once on the f32 accumulator in the last-k flush, BEFORE the epilogue
lattice, so every fused epilogue composes with quantized weights
unchanged. Dequantized weights never materialise anywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, mxu_precision

EPILOGUES = ("none", "bias", "bias_gelu", "bias_silu", "residual")


def _apply_epilogue(acc, e, epilogue: str):
    """Flush-phase epilogue on the f32 (or f64) accumulator tile. `e` is
    the staged epilogue operand: (1, bn) bias row or (bm, bn) residual."""
    if epilogue == "none":
        return acc
    acc = acc + e.astype(acc.dtype)       # bias broadcasts over rows
    if epilogue == "bias_gelu":
        acc = jax.nn.gelu(acc)
    elif epilogue == "bias_silu":
        acc = jax.nn.silu(acc)
    return acc


def _matmul_kernel(*refs, n_k: int, out_dtype, epilogue: str = "none"):
    if epilogue == "none":
        a_ref, b_ref, o_ref, acc_ref = refs
        e_ref = None
    else:
        a_ref, b_ref, e_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=acc_ref.dtype,
        precision=mxu_precision(a_ref.dtype),
    )

    @pl.when(k == n_k - 1)
    def _flush():
        acc = acc_ref[...]
        if epilogue != "none":
            acc = _apply_epilogue(acc, e_ref[...], epilogue)
        o_ref[...] = acc.astype(out_dtype)


def _matmul_q_kernel(*refs, n_k: int, out_dtype, epilogue: str = "none"):
    """Int8-weight GEMM: accumulate A @ widen(Wq) per k step; the flush
    dequantizes the f32 accumulator with the (1, bn) scale row and then
    runs the ordinary epilogue lattice."""
    if epilogue == "none":
        a_ref, b_ref, s_ref, o_ref, acc_ref = refs
        e_ref = None
    else:
        a_ref, b_ref, s_ref, e_ref, o_ref, acc_ref = refs
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...].astype(a_ref.dtype),
        preferred_element_type=acc_ref.dtype,
        precision=mxu_precision(a_ref.dtype),
    )

    @pl.when(k == n_k - 1)
    def _flush():
        acc = acc_ref[...] * s_ref[...].astype(acc_ref.dtype)
        if epilogue != "none":
            acc = _apply_epilogue(acc, e_ref[...], epilogue)
        o_ref[...] = acc.astype(out_dtype)


def _gated_matmul_kernel(a_ref, g_ref, u_ref, o_ref, accg_ref, accu_ref,
                         *, n_k: int, out_dtype):
    """Dual-GEMM SwiGLU: the A tile in VMEM feeds both weight operands;
    the flush applies the gate product without leaving fast memory."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        accg_ref[...] = jnp.zeros_like(accg_ref)
        accu_ref[...] = jnp.zeros_like(accu_ref)

    a = a_ref[...]
    prec = mxu_precision(a.dtype)
    accg_ref[...] += jnp.dot(a, g_ref[...], precision=prec,
                             preferred_element_type=accg_ref.dtype)
    accu_ref[...] += jnp.dot(a, u_ref[...], precision=prec,
                             preferred_element_type=accu_ref.dtype)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = (jax.nn.silu(accg_ref[...])
                      * accu_ref[...]).astype(out_dtype)


def _clamp_block(bm: int, bn: int, bk: int, m: int, n: int, ka: int):
    """Clamp tile dims to the problem and re-validate divisibility.

    A tile larger than the (padded) problem is legitimately clamped —
    that collapses a grid dim to 1 — but a clamp must never silently
    rewrite an autotuner-served config into one that does not tile the
    problem (the old bare `assert` made that failure mode opaque).
    """
    bm_c, bn_c, bk_c = min(bm, m), min(bn, n), min(bk, ka)
    if m % bm_c or n % bn_c or ka % bk_c:
        raise ValueError(
            f"block ({bm},{bn},{bk}) clamped to ({bm_c},{bn_c},{bk_c}) "
            f"does not divide the ({m},{n},{ka}) problem; route through "
            "kernels.ops (pads operands to tile multiples) or pick tiles "
            "via core.blocking.choose_block_config")
    return bm_c, bn_c, bk_c


def _tile_params(bm: int, bn: int, acc_dtype, n_acc: int = 1):
    scratch = [pltpu.VMEM((bm, bn), acc_dtype) for _ in range(n_acc)]
    return scratch, {"compiler_params": compiler_params(
        "parallel", "parallel", "arbitrary")}


def matmul_tiled(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    block=None,
    out_dtype=None,
    interpret: bool = False,
    epilogue: str = "none",
    epilogue_operand: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """C[M,N] = epilogue(A[M,K] @ B[K,N]), real dtypes only (complex is
    decomposed in core.gemm). Shapes must be multiples of the block dims
    — ops.py pads otherwise. `block` (a core.blocking.BlockConfig, e.g.
    from the autotuner cache) overrides the bm/bn/bk defaults.

    epilogue_operand: (1, N) bias row for the bias* epilogues, (M, N)
    residual for "residual"; staged through its own BlockSpec and
    consumed in the last-k flush.
    """
    assert epilogue in EPILOGUES, epilogue
    if block is not None:
        bm, bn, bk = block.bm, block.bn, block.bk
    m, ka = a.shape
    kb, n = b.shape
    assert ka == kb, (a.shape, b.shape)
    if out_dtype is None:
        out_dtype = a.dtype
    bm, bn, bk = _clamp_block(bm, bn, bk, m, n, ka)
    n_k = ka // bk
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32

    grid = (m // bm, n // bn, n_k)
    kernel = functools.partial(_matmul_kernel, n_k=n_k, out_dtype=out_dtype,
                               epilogue=epilogue)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
    ]
    operands = [a, b]
    if epilogue != "none":
        e = epilogue_operand
        assert e is not None, f"epilogue={epilogue} needs its operand"
        if epilogue == "residual":
            assert e.shape == (m, n), (e.shape, (m, n))
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        else:
            assert e.shape == (1, n), (e.shape, (1, n))
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(e)

    scratch, params = _tile_params(bm, bn, acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="matmul_tiled",
        **params,
    )(*operands)


def matmul_q_tiled(
    a: jnp.ndarray,
    wq: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    block=None,
    out_dtype=None,
    interpret: bool = False,
    epilogue: str = "none",
    epilogue_operand: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """C[M,N] = epilogue((A[M,K] @ Wq[K,N]) * scale[1,N]).

    Wq is int8 (per-channel symmetric, core.precision.quantize_int8),
    scale the matching (1, N) f32 row. Same tiling contract as
    matmul_tiled; the int8 W tile halves-to-quarters the B-side DMA and
    the scale row rides its own (1, bn) BlockSpec into the flush. Note
    the TPU int8 min-tile is (32, 128) — bk from core.blocking is
    always a lane multiple, which satisfies it.
    """
    assert epilogue in EPILOGUES, epilogue
    assert wq.dtype == jnp.int8, wq.dtype
    if block is not None:
        bm, bn, bk = block.bm, block.bn, block.bk
    m, ka = a.shape
    kb, n = wq.shape
    assert ka == kb, (a.shape, wq.shape)
    assert scale.shape == (1, n), (scale.shape, n)
    if out_dtype is None:
        out_dtype = a.dtype
    bm, bn, bk = _clamp_block(bm, bn, bk, m, n, ka)
    n_k = ka // bk
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32

    grid = (m // bm, n // bn, n_k)
    kernel = functools.partial(_matmul_q_kernel, n_k=n_k,
                               out_dtype=out_dtype, epilogue=epilogue)

    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
        pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
    ]
    operands = [a, wq, scale]
    if epilogue != "none":
        e = epilogue_operand
        assert e is not None, f"epilogue={epilogue} needs its operand"
        if epilogue == "residual":
            assert e.shape == (m, n), (e.shape, (m, n))
            in_specs.append(pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)))
        else:
            assert e.shape == (1, n), (e.shape, (1, n))
            in_specs.append(pl.BlockSpec((1, bn), lambda i, j, k: (0, j)))
        operands.append(e)

    scratch, params = _tile_params(bm, bn, acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="matmul_q_tiled",
        **params,
    )(*operands)


def gated_matmul_tiled(
    a: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    *,
    bm: int = 256,
    bn: int = 256,
    bk: int = 512,
    block=None,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """H[M,N] = silu(A @ Wg) * (A @ Wu) in one pass over A.

    The VMEM working set doubles on the B side (two weight tiles, two
    accumulators) — size tiles with choose_block_config(..., n_rhs=2).
    """
    m, ka = a.shape
    kg, n = w_gate.shape
    assert w_up.shape == (kg, n) and ka == kg, \
        (a.shape, w_gate.shape, w_up.shape)
    if block is not None:
        bm, bn, bk = block.bm, block.bn, block.bk
    if out_dtype is None:
        out_dtype = a.dtype
    bm, bn, bk = _clamp_block(bm, bn, bk, m, n, ka)
    n_k = ka // bk
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32

    grid = (m // bm, n // bn, n_k)
    kernel = functools.partial(_gated_matmul_kernel, n_k=n_k,
                               out_dtype=out_dtype)
    scratch, params = _tile_params(bm, bn, acc_dtype, n_acc=2)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="gated_matmul_tiled",
        **params,
    )(a, w_gate, w_up)
