"""Policy-dispatched wrappers around the Pallas kernels.

Handles the plumbing the kernels themselves keep out of scope: shape
padding to block multiples, block-size choice via core.blocking (the
paper's shared-memory sizing argument), epilogue operand validation,
and the interpret-mode fallback used on CPU-only containers.

Execution selection is typed: every public op takes a
`core.policy.Policy` (explicit `policy=`, or the ambient
`policy.scope()` default) and dispatches through the kernel registry
(kernels.registry):

    op name     registered backends
    matmul      xla (jnp reference) | pallas (tiled, Listing 4) |
                naive (hierarchy-blind, Listing 3)
    matmul_q    xla (dequantized reference) | pallas (int8-weight
                tiled kernel, flush-phase dequant) | naive (dequantize
                then hierarchy-blind)
    gated_matmul  xla/naive (unfused compose) | pallas (dual-GEMM)
    flash_attention  xla (reference) | pallas (flash kernel)
    flash_attention_bwd  xla (closed-form ref) | pallas (recompute-
                style two-sweep kernel, S/P never in HBM)
    flash_decode  xla (ref composition) | pallas (q_len=1 kernel,
                prefix-only K/V streaming)
    flash_decode_paged  xla (page-gather + ref composition) | pallas
                (scalar-prefetched page-table gather, optional int8
                in-kernel dequant)
    ssd         xla (chunked jnp composition) | naive (sequential
                per-token scan oracle) | pallas (intra-chunk Pallas
                kernel: decay mask + CB scores VMEM-resident)
    add / sub   xla | pallas/naive (elementwise kernel)

`policy.interpret` (None = auto off-TPU) decides interpreter vs.
compiled for every Pallas op — no per-op suffix sniffing.
`policy.autotune == "cached"` serves tile winners from the autotuner
cache (repro.tuning) with the static core.blocking chooser as fallback;
the legacy "tuned"/"tuned_interpret" backend strings map onto exactly
that policy via the compat shims at the bottom of this module (the only
place backend strings are still interpreted).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import blocking, hw
from repro.core import policy as _policy
from repro.core.policy import Policy
from repro.kernels import elementwise as _ew
from repro.kernels import flash_attention as _fa
from repro.kernels import matmul as _mm
from repro.kernels import matmul_naive as _mmn
from repro.kernels import ref as _ref
from repro.kernels import registry as _registry
from repro.kernels import ssd as _ssd
from repro.kernels.registry import register_op
from repro.tuning import cache as _tcache


# ----------------------------------------------------------------------
# shared helpers
# ----------------------------------------------------------------------

def _pad2(x: jnp.ndarray, m_to: int, n_to: int) -> jnp.ndarray:
    m, n = x.shape
    if m == m_to and n == n_to:
        return x
    return jnp.pad(x, ((0, m_to - m), (0, n_to - n)))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _usable_block(block, served: bool) -> bool:
    """Discard degenerate tile configs (a corrupt cache entry must fall
    back to the static chooser, not crash the padding arithmetic)."""
    if block is None:
        return False
    ok = block.bm > 0 and block.bn > 0 and block.bk > 0
    if not ok and not served:
        raise ValueError(f"invalid block config {block}")
    return ok


def _check_epilogue(epilogue: str) -> None:
    """Validated against the kernel's own lattice (kernels.matmul
    EPILOGUES) — the registry of fused flushes, not a local tuple."""
    if epilogue not in _mm.EPILOGUES:
        raise ValueError(f"unknown epilogue {epilogue!r}; registered "
                         f"epilogues: {_mm.EPILOGUES}")


def _epilogue_operand(epilogue, bias, residual, m, n, mp, np_):
    """Validate + pad the flush-phase operand to the padded tile grid.
    The operand keeps its own dtype — the kernel casts it to the
    accumulator dtype, mirroring the unfused ref.epilogue_ref cast, so
    a residual/bias wider than the inputs loses no precision."""
    if epilogue == "none":
        if bias is not None or residual is not None:
            raise ValueError("bias/residual operands need an epilogue")
        return None
    if epilogue == "residual":
        if residual is None or residual.shape != (m, n):
            raise ValueError(
                f"epilogue='residual' needs residual of shape {(m, n)}, "
                f"got {None if residual is None else residual.shape}")
        return _pad2(residual, mp, np_)
    if bias is None:
        raise ValueError(f"epilogue={epilogue!r} needs bias=")
    e = bias.reshape(1, -1)
    if e.shape != (1, n):
        raise ValueError(f"bias shape {bias.shape} incompatible with n={n}")
    return _pad2(e, 1, np_)


# ----------------------------------------------------------------------
# matmul implementations (self-registered)
# ----------------------------------------------------------------------

@register_op("matmul", backend="xla")
def _matmul_xla(a, b, *, policy, out_dtype, block, epilogue, bias, residual):
    y = _ref.matmul_ref(a, b, out_dtype=out_dtype)
    return _ref.epilogue_ref(y, epilogue, bias, residual)


@register_op("matmul", backend="naive")
def _matmul_naive(a, b, *, policy, out_dtype, block, epilogue, bias,
                  residual):
    m, k = a.shape
    n = b.shape[1]
    chip = policy.chip
    itemsize = jnp.dtype(a.dtype).itemsize
    sub = chip.sublane(itemsize)
    mp, np_ = _round_up(m, sub), _round_up(n, chip.lane)
    out = _mmn.matmul_naive(
        _pad2(a, mp, k), _pad2(b, k, np_),
        out_dtype=out_dtype, interpret=policy.resolved_interpret)[:m, :n]
    return _ref.epilogue_ref(out, epilogue, bias, residual)


@register_op("matmul", backend="pallas")
def _matmul_pallas(a, b, *, policy, out_dtype, block, epilogue, bias,
                   residual):
    m, k = a.shape
    n = b.shape[1]
    served = False
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_matmul(
            m, n, k, a.dtype, policy, epilogue=epilogue)
        served = block is not None
        # miss / fingerprint mismatch -> block stays None and the
        # static chooser below picks the paper's default tiles.
    itemsize = jnp.dtype(a.dtype).itemsize
    if not _usable_block(block, served):
        block = blocking.choose_block_config(m, n, k, itemsize, policy.chip)
    # padding to block multiples guarantees the kernel's clamp
    # re-validation passes: every dim is a multiple of its tile edge.
    mp = _round_up(m, block.bm)
    np_ = _round_up(n, block.bn)
    kp = _round_up(k, block.bk)
    e = _epilogue_operand(epilogue, bias, residual, m, n, mp, np_)
    out = _mm.matmul_tiled(
        _pad2(a, mp, kp), _pad2(b, kp, np_),
        bm=block.bm, bn=block.bn, bk=block.bk,
        out_dtype=out_dtype, interpret=policy.resolved_interpret,
        epilogue=epilogue, epilogue_operand=e)
    return out[:m, :n]


def matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    policy: Policy | None = None,
    backend: str | None = None,        # deprecated string shim
    out_dtype=None,
    block: blocking.BlockConfig | None = None,
    chip: hw.ChipSpec | None = None,
    epilogue: str = "none",
    bias: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """2D real GEMM through the policy-selected backend, padding as
    needed.

    epilogue/bias/residual select a fused flush (kernels.matmul
    EPILOGUES): the pallas backend applies it inside the kernel on the
    f32 accumulator; xla and naive apply the same composition unfused
    (ref.epilogue_ref), so every backend computes the same function.
    """
    assert a.ndim == 2 and b.ndim == 2, (a.shape, b.shape)
    assert a.shape[1] == b.shape[0], (a.shape, b.shape)
    pol = _policy.resolve(policy, backend)
    if chip is not None and chip is not pol.chip:
        pol = pol.replace(chip=chip)
    _check_epilogue(epilogue)
    out_dtype = out_dtype or pol.resolved_out_dtype(a.dtype)
    impl = _registry.get_impl("matmul", pol.backend)
    return impl(a, b, policy=pol, out_dtype=out_dtype, block=block,
                epilogue=epilogue, bias=bias, residual=residual)


# ----------------------------------------------------------------------
# quantized matmul (int8 weights, per-channel scales)
# ----------------------------------------------------------------------

def _check_quant_operands(wq, scale, k, n):
    """Validate the (Wq, scale) pair and normalise scale to (1, n) —
    the kernel's BlockSpec layout."""
    if wq.dtype != jnp.int8:
        raise ValueError(f"matmul_q weights must be int8 "
                         f"(core.precision.quantize_int8), got {wq.dtype}")
    if wq.shape != (k, n):
        raise ValueError(f"quantized weight shape {wq.shape} incompatible "
                         f"with ({k}, {n})")
    s = scale.reshape(1, -1) if scale.ndim == 1 else scale
    if s.shape != (1, n):
        raise ValueError(f"per-channel scale shape {scale.shape} "
                         f"incompatible with n={n}; expected ({n},) or "
                         f"(1, {n})")
    if not jnp.issubdtype(s.dtype, jnp.floating):
        raise ValueError(f"scale must be floating, got {s.dtype}")
    return s


@register_op("matmul_q", backend="xla")
def _matmul_q_xla(a, wq, scale, *, policy, out_dtype, block, epilogue,
                  bias, residual):
    y = _ref.matmul_q_ref(a, wq, scale, out_dtype=out_dtype)
    return _ref.epilogue_ref(y, epilogue, bias, residual)


@register_op("matmul_q", backend="naive")
def _matmul_q_naive(a, wq, scale, *, policy, out_dtype, block, epilogue,
                    bias, residual):
    """Dequantize in HBM, then the hierarchy-blind kernel — the
    fallback composition (no traffic win, same function)."""
    w = _ref.dequantize_ref(wq, scale).astype(a.dtype)
    return _matmul_naive(a, w, policy=policy, out_dtype=out_dtype,
                         block=block, epilogue=epilogue, bias=bias,
                         residual=residual)


@register_op("matmul_q", backend="pallas")
def _matmul_q_pallas(a, wq, scale, *, policy, out_dtype, block, epilogue,
                     bias, residual):
    m, k = a.shape
    n = wq.shape[1]
    served = False
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_matmul_q(
            m, n, k, a.dtype, policy, epilogue=epilogue)
        served = block is not None
    itemsize = jnp.dtype(a.dtype).itemsize
    if not _usable_block(block, served):
        # tiles sized by the activation itemsize: conservative for the
        # 1-byte W stream (a dedicated int8 chooser could go larger).
        block = blocking.choose_block_config(m, n, k, itemsize, policy.chip)
    mp = _round_up(m, block.bm)
    np_ = _round_up(n, block.bn)
    kp = _round_up(k, block.bk)
    e = _epilogue_operand(epilogue, bias, residual, m, n, mp, np_)
    out = _mm.matmul_q_tiled(
        _pad2(a, mp, kp), _pad2(wq, kp, np_), _pad2(scale, 1, np_),
        bm=block.bm, bn=block.bn, bk=block.bk,
        out_dtype=out_dtype, interpret=policy.resolved_interpret,
        epilogue=epilogue, epilogue_operand=e)
    return out[:m, :n]


def matmul_q(
    a: jnp.ndarray,
    wq: jnp.ndarray,
    scale: jnp.ndarray,
    *,
    policy: Policy | None = None,
    backend: str | None = None,        # deprecated string shim
    out_dtype=None,
    block: blocking.BlockConfig | None = None,
    chip: hw.ChipSpec | None = None,
    epilogue: str = "none",
    bias: jnp.ndarray | None = None,
    residual: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """2D GEMM against int8 per-channel-quantized weights:
    ``epilogue((a @ wq) * scale)``.

    The pallas backend streams the weight tiles as int8 and dequantizes
    on the f32 accumulator in the flush (kernels.matmul.matmul_q_tiled);
    xla/naive compute the same function from the dequantized composition
    — every backend is conformance-tested against the ref oracle in
    tests/test_property.py. Quantize weights once with
    core.precision.quantize_int8; training-time cotangents live in
    core.gemm.dense_q.
    """
    assert a.ndim == 2 and wq.ndim == 2, (a.shape, wq.shape)
    assert a.shape[1] == wq.shape[0], (a.shape, wq.shape)
    pol = _policy.resolve(policy, backend)
    if chip is not None and chip is not pol.chip:
        pol = pol.replace(chip=chip)
    _check_epilogue(epilogue)
    scale = _check_quant_operands(wq, scale, a.shape[1], wq.shape[1])
    out_dtype = out_dtype or pol.resolved_out_dtype(a.dtype)
    impl = _registry.get_impl("matmul_q", pol.backend)
    return impl(a, wq, scale, policy=pol, out_dtype=out_dtype, block=block,
                epilogue=epilogue, bias=bias, residual=residual)


# ----------------------------------------------------------------------
# gated matmul (SwiGLU dual-GEMM)
# ----------------------------------------------------------------------

@register_op("gated_matmul", backend="xla")
@register_op("gated_matmul", backend="naive")
def _gated_compose(a, w_gate, w_up, *, policy, out_dtype, block):
    """Unfused composition through the plain matmul dispatcher: the
    xla/naive backends compute the same function with two GEMMs and an
    HBM intermediate."""
    g = matmul(a, w_gate, policy=policy, out_dtype=out_dtype)
    u = matmul(a, w_up, policy=policy, out_dtype=out_dtype)
    return (jax.nn.silu(g) * u).astype(out_dtype)


@register_op("gated_matmul", backend="pallas")
def _gated_pallas(a, w_gate, w_up, *, policy, out_dtype, block):
    m, k = a.shape
    n = w_gate.shape[1]
    served = False
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_gated(m, n, k, a.dtype, policy)
        served = block is not None
    itemsize = jnp.dtype(a.dtype).itemsize
    if not _usable_block(block, served):
        block = blocking.choose_block_config(m, n, k, itemsize, policy.chip,
                                             n_rhs=2)
    mp = _round_up(m, block.bm)
    np_ = _round_up(n, block.bn)
    kp = _round_up(k, block.bk)
    out = _mm.gated_matmul_tiled(
        _pad2(a, mp, kp), _pad2(w_gate, kp, np_), _pad2(w_up, kp, np_),
        bm=block.bm, bn=block.bn, bk=block.bk,
        out_dtype=out_dtype, interpret=policy.resolved_interpret)
    return out[:m, :n]


def gated_matmul(
    a: jnp.ndarray,
    w_gate: jnp.ndarray,
    w_up: jnp.ndarray,
    *,
    policy: Policy | None = None,
    backend: str | None = None,        # deprecated string shim
    out_dtype=None,
    block: blocking.BlockConfig | None = None,
    chip: hw.ChipSpec | None = None,
) -> jnp.ndarray:
    """silu(a @ w_gate) * (a @ w_up) — the SwiGLU hidden phase.

    The pallas backend runs the dual-GEMM kernel (one A stream, two
    weight operands, zero HBM intermediates); xla/naive compose it
    unfused. Tiles come from the gated autotuner cache entries
    (policy.autotune == "cached") or the n_rhs=2 static chooser."""
    assert a.ndim == w_gate.ndim == w_up.ndim == 2
    assert w_gate.shape == w_up.shape == (a.shape[1], w_gate.shape[1])
    pol = _policy.resolve(policy, backend)
    if chip is not None and chip is not pol.chip:
        pol = pol.replace(chip=chip)
    out_dtype = out_dtype or pol.resolved_out_dtype(a.dtype)
    impl = _registry.get_impl("gated_matmul", pol.backend)
    return impl(a, w_gate, w_up, policy=pol, out_dtype=out_dtype,
                block=block)


# ----------------------------------------------------------------------
# elementwise
# ----------------------------------------------------------------------

@register_op("add", backend="xla")
def _add_xla(a, b, *, policy):
    return _ref.add_ref(a, b)


@register_op("add", backend="pallas")
@register_op("add", backend="naive")
def _add_pallas(a, b, *, policy):
    return _ew.binary_op(a, b, "add", interpret=policy.resolved_interpret)


@register_op("sub", backend="xla")
def _sub_xla(a, b, *, policy):
    return _ref.sub_ref(a, b)


@register_op("sub", backend="pallas")
@register_op("sub", backend="naive")
def _sub_pallas(a, b, *, policy):
    return _ew.binary_op(a, b, "sub", interpret=policy.resolved_interpret)


def _elementwise(op, a, b, policy, backend, interpret):
    pol = _policy.resolve(policy, backend)
    if interpret is not None:
        # explicit bool overrides the policy (e.g. force-interpret on
        # CPU regardless of what the ambient policy says).
        pol = pol.replace(interpret=interpret)
    return _registry.get_impl(op, pol.backend)(a, b, policy=pol)


def add(a, b, *, policy: Policy | None = None, backend: str | None = None,
        interpret: bool | None = None):
    return _elementwise("add", a, b, policy, backend, interpret)


def sub(a, b, *, policy: Policy | None = None, backend: str | None = None,
        interpret: bool | None = None):
    return _elementwise("sub", a, b, policy, backend, interpret)


# ----------------------------------------------------------------------
# flash attention
# ----------------------------------------------------------------------

@register_op("flash_attention", backend="xla")
def _flash_xla(q, k, v, *, policy, causal, window, q_offset, bq, bk, block,
               scale):
    return _ref.attention_ref(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset)


@register_op("flash_attention", backend="pallas")
def _flash_pallas(q, k, v, *, policy, causal, window, q_offset, bq, bk,
                  block, scale):
    b_, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    if jnp.asarray(q_offset).ndim == 1:
        # per-batch offsets -> per-(batch*head) rows of the flat layout
        q_offset = jnp.repeat(jnp.asarray(q_offset, jnp.int32), h)
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_flash(tq, tk, d, q.dtype, policy)
    if block is not None:
        bq, bk = block.bq, block.bk
    g = h // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b_ * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b_ * hkv, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b_ * hkv, tk, d)
    o = _fa.flash_attention(
        qf, kf, vf, group=g, causal=causal, window=window, scale=scale,
        q_offset=q_offset, bq=bq, bk=bk,
        interpret=policy.resolved_interpret)
    return o.reshape(b_, h, tq, d).transpose(0, 2, 1, 3)


def flash_attention(
    q: jnp.ndarray,            # [B, Tq, H, D]
    k: jnp.ndarray,            # [B, Tk, Hkv, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset=0,                # scalar, or (B,) per-row vector (decode)
    policy: Policy | None = None,
    backend: str | None = None,        # deprecated string shim
    bq: int = 256,
    bk: int = 512,
    block: blocking.FlashBlockConfig | None = None,
    scale: float | None = None,        # softmax scale; None = D^-0.5
) -> jnp.ndarray:
    """Layout-normalising wrapper: model code uses [B, T, H, D]."""
    pol = _policy.resolve(policy, backend)
    impl = _registry.get_impl("flash_attention", pol.backend)
    return impl(q, k, v, policy=pol, causal=causal, window=window,
                q_offset=q_offset, bq=bq, bk=bk, block=block, scale=scale)


def _flat_heads(x):
    """[B, T, H, D] -> the kernels' flat [B*H, T, D] layout."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _per_head(offset, h):
    """Broadcast a (B,) per-batch offset vector to the flat layout's
    per-(batch*head) rows; scalars pass through."""
    if jnp.asarray(offset).ndim == 1:
        return jnp.repeat(jnp.asarray(offset, jnp.int32), h)
    return offset


def flash_attention_fwd(
    q: jnp.ndarray,            # [B, Tq, H, D]
    k: jnp.ndarray,            # [B, Tk, Hkv, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset=0,
    policy: Policy | None = None,
    backend: str | None = None,
    bq: int = 256,
    bk: int = 512,
    block: blocking.FlashBlockConfig | None = None,
    scale: float | None = None,
):
    """Forward with residuals: (o, lse[B, H, Tq] f32) — what the
    attention custom-VJP saves for flash_attention_bwd. Not a separate
    registry op: it IS flash_attention plus the lse output, so it
    follows the same backend split (pallas = kernel, else = ref)."""
    pol = _policy.resolve(policy, backend)
    if pol.backend != "pallas":
        return _ref.attention_fwd_ref(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset)
    b_, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    if block is None and pol.autotune == "cached":
        block = _tcache.get_cache().get_flash(tq, tk, d, q.dtype, pol)
    if block is not None:
        bq, bk = block.bq, block.bk
    o, lse = _fa.flash_attention(
        _flat_heads(q), _flat_heads(k), _flat_heads(v),
        group=h // hkv, causal=causal, window=window, scale=scale,
        q_offset=_per_head(q_offset, h), bq=bq, bk=bk,
        interpret=pol.resolved_interpret, return_lse=True)
    return (o.reshape(b_, h, tq, d).transpose(0, 2, 1, 3),
            lse.reshape(b_, h, tq))


@register_op("flash_attention_bwd", backend="xla")
def _flash_bwd_xla(q, k, v, o, do, lse, *, policy, causal, window,
                   q_offset, block, scale):
    return _ref.attention_bwd_ref(
        q, k, v, o, do, lse, causal=causal, window=window, scale=scale,
        q_offset=q_offset)


@register_op("flash_attention_bwd", backend="pallas")
def _flash_bwd_pallas(q, k, v, o, do, lse, *, policy, causal, window,
                      q_offset, block, scale):
    b_, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    g = h // hkv
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_flash_bwd(tq, tk, d, q.dtype, policy)
    dq, dk, dv = _fa.flash_attention_bwd(
        _flat_heads(q), _flat_heads(k), _flat_heads(v),
        _flat_heads(o), _flat_heads(do), lse.reshape(b_ * h, tq),
        group=g, causal=causal, window=window, scale=scale,
        q_offset=_per_head(q_offset, h), block=block,
        interpret=policy.resolved_interpret)
    dq = dq.reshape(b_, h, tq, d).transpose(0, 2, 1, 3)
    # the kernel returns per-QUERY-head dK/dV (it cannot revisit output
    # blocks across the GQA fan-in); the group-sum happens here, in f32
    dk = dk.reshape(b_, hkv, g, tk, d).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dv.reshape(b_, hkv, g, tk, d).sum(axis=2).transpose(0, 2, 1, 3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def flash_attention_bwd(
    q: jnp.ndarray,            # [B, Tq, H, D]
    k: jnp.ndarray,            # [B, Tk, Hkv, D]
    v: jnp.ndarray,
    o: jnp.ndarray,            # [B, Tq, H, D]  forward output
    do: jnp.ndarray,           # [B, Tq, H, D]  output cotangent
    lse: jnp.ndarray,          # [B, H, Tq] f32 forward residual
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset=0,
    policy: Policy | None = None,
    backend: str | None = None,
    block: blocking.FlashBlockConfig | None = None,
    scale: float | None = None,
):
    """Recompute-style attention backward: (dq, dk, dv) from the saved
    (o, lse) residuals — S/P never hit HBM on the pallas backend."""
    pol = _policy.resolve(policy, backend)
    impl = _registry.get_impl("flash_attention_bwd", pol.backend)
    return impl(q, k, v, o, do, lse, policy=pol, causal=causal,
                window=window, q_offset=q_offset, block=block, scale=scale)


@register_op("flash_decode", backend="xla")
def _flash_decode_xla(q, k, v, *, policy, pos, window, bk, block, scale):
    # the fwd_ref composition (not attention_ref): its exp(S - lse) form
    # zeroes fully-masked rows, so inactive slots (pos < 0) agree with
    # the kernel's zero output instead of softmaxing over -1e30 logits.
    o, _ = _ref.attention_fwd_ref(
        q, k, v, causal=True, window=window, scale=scale, q_offset=pos)
    return o


@register_op("flash_decode", backend="pallas")
def _flash_decode_pallas(q, k, v, *, policy, pos, window, bk, block,
                         scale):
    _, tk, hkv, d = k.shape
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_flash_decode(tk, hkv, d, q.dtype,
                                                     policy)
    if block is None:
        # the caller's bk is a ceiling; the chooser keeps the (bk, hkv, d)
        # K/V blocks inside the VMEM budget
        bk = min(bk, blocking.choose_decode_config(
            tk, hkv, d, jnp.dtype(k.dtype).itemsize, policy.chip).bk)
    else:
        bk = block.bk
    return _fa.flash_decode(q, k, v, window=window, pos=pos, bk=bk,
                            scale=scale,
                            interpret=policy.resolved_interpret)


def flash_decode(
    q: jnp.ndarray,            # [B, 1, H, D]  one new token per slot
    k: jnp.ndarray,            # [B, Tk, Hkv, D]  the KV cache
    v: jnp.ndarray,
    *,
    pos=0,                     # scalar, or (B,) per-slot depth vector
    window: int | None = None,
    policy: Policy | None = None,
    backend: str | None = None,
    bk: int = 512,
    block: blocking.FlashBlockConfig | None = None,
    scale: float | None = None,        # softmax scale; None = D^-0.5
) -> jnp.ndarray:
    """Decode-specialized attention: each slot's query attends its
    cache prefix [0, pos] (kv_len = pos + 1). The pallas backend streams
    only the K/V blocks covering the prefix; slots with pos < 0 are
    inactive and return finite garbage the engine discards."""
    assert q.shape[1] == 1, f"flash_decode is q_len=1 only: {q.shape}"
    pol = _policy.resolve(policy, backend)
    impl = _registry.get_impl("flash_decode", pol.backend)
    return impl(q, k, v, policy=pol, pos=pos, window=window, bk=bk,
                block=block, scale=scale)


@register_op("flash_decode_paged", backend="xla")
def _flash_decode_paged_xla(q, kp, vp, table, *, policy, pos, window,
                            ks, vs, bk, block):
    return _ref.flash_decode_paged_ref(
        q, kp, vp, table, pos=pos, window=window, ks=ks, vs=vs)


@register_op("flash_decode_paged", backend="pallas")
def _flash_decode_paged_pallas(q, kp, vp, table, *, policy, pos, window,
                               ks, vs, bk, block):
    b_, tq, h, d = q.shape
    hkv, ps = kp.shape[1], kp.shape[2]
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_flash_decode_paged(
            ps, d, q.dtype, policy)
    if block is not None:
        bk = block.bk
    o = _fa.flash_decode_paged(
        q[:, 0], kp, vp, table, group=h // hkv, window=window, pos=pos,
        ks=ks, vs=vs, bk=bk, interpret=policy.resolved_interpret)
    return o[:, None]


def flash_decode_paged(
    q: jnp.ndarray,            # [B, 1, H, D]  one new token per slot
    kp: jnp.ndarray,           # [P, Hkv, page_size, D]  K page pool
    vp: jnp.ndarray,           # [P, Hkv, page_size, D]  V page pool
    table: jnp.ndarray,        # [B, pages_per_slot] int32; -1 unmapped
    *,
    pos=0,                     # scalar, or (B,) per-slot depth vector
    window: int | None = None,
    ks: jnp.ndarray | None = None,    # [P, Hkv, page_size] f32 scales
    vs: jnp.ndarray | None = None,    # (int8 pools only)
    policy: Policy | None = None,
    backend: str | None = None,
    bk: int | None = None,
    block: blocking.FlashBlockConfig | None = None,
) -> jnp.ndarray:
    """flash_decode against a paged KV pool (serving.kv_pool layout):
    slot b's logical page j lives at pool index table[b, j]. The pallas
    backend gathers pages through scalar-prefetched table rows and —
    for int8 pools — dequantizes on the f32 accumulator in-kernel; the
    xla backend is the gather + masked-softmax composition
    (ref.flash_decode_paged_ref), conformance-tested per backend in
    tests/test_property.py. Same pos/window/inactive-slot contract as
    flash_decode."""
    assert q.shape[1] == 1, \
        f"flash_decode_paged is q_len=1 only: {q.shape}"
    assert kp.shape == vp.shape and kp.ndim == 4, (kp.shape, vp.shape)
    assert (ks is None) == (vs is None)
    if ks is not None:
        assert kp.dtype == jnp.int8, \
            f"scale planes supplied for a {kp.dtype} pool"
    pol = _policy.resolve(policy, backend)
    impl = _registry.get_impl("flash_decode_paged", pol.backend)
    return impl(q, kp, vp, table, policy=pol, pos=pos, window=window,
                ks=ks, vs=vs, bk=bk, block=block)


# ----------------------------------------------------------------------
# SSD (Mamba-2 state-space duality)
# ----------------------------------------------------------------------

@register_op("ssd", backend="xla")
def _ssd_xla(x, a, b, c, *, policy, chunk, init_state, block):
    return _ssd.ssd_chunked(x, a, b, c, chunk, init_state=init_state)


@register_op("ssd", backend="naive")
def _ssd_naive(x, a, b, c, *, policy, chunk, init_state, block):
    return _ref.ssd_ref(x, a, b, c, chunk, init_state=init_state)


@register_op("ssd", backend="pallas")
def _ssd_pallas_impl(x, a, b, c, *, policy, chunk, init_state, block):
    p = x.shape[-1]
    n = b.shape[-1]
    served = False
    if block is None and policy.autotune == "cached":
        block = _tcache.get_cache().get_ssd(chunk, p, n, x.dtype, policy)
        served = block is not None
    ok = (block is not None and block.q > 0 and chunk % block.q == 0
          and (block.bp > 0 and p % block.bp == 0 or block.bp == p))
    if not ok:
        if block is not None and not served:
            raise ValueError(f"invalid ssd block config {block} for "
                             f"chunk={chunk}, p={p}")
        block = blocking.choose_ssd_config(
            chunk, p, n, jnp.dtype(x.dtype).itemsize, policy.chip)
    # the execution chunk may subdivide the model chunk: SSD chunking
    # is algebraically exact, so any divisor computes the same function.
    return _ssd.ssd_pallas(
        x, a, b, c, block.q, init_state=init_state, block_p=block.bp,
        interpret=policy.resolved_interpret)


def ssd(
    x: jnp.ndarray,            # (B, L, H, P) — dt-scaled inputs
    a: jnp.ndarray,            # (B, L, H)    — dt*A log decays
    b: jnp.ndarray,            # (B, L, G, N)
    c: jnp.ndarray,            # (B, L, G, N)
    chunk: int,
    init_state: jnp.ndarray | None = None,   # (B, H, P, N)
    *,
    policy: Policy | None = None,
    backend: str | None = None,        # deprecated string shim
    block: blocking.SSDBlockConfig | None = None,
    chip: hw.ChipSpec | None = None,
):
    """Chunked SSD scan (Mamba-2 dual form): returns
    ``(y (B, L, H, P) in x.dtype, final_state (B, H, P, N) f32)``.

    The inter-chunk state is carried in f32 on every backend (cast at
    the boundary), and `init_state` seeds the recurrence — carried-state
    chunked prefill composes exactly. The pallas backend keeps the
    per-chunk decay mask and CB score matrices VMEM-resident
    (kernels.ssd); `chunk` is the model's configured chunk, while the
    kernel's *execution* chunk/tiling comes from the autotuner cache
    (policy.autotune == "cached") or the static chooser — any divisor
    computes the same function. Training flows through the core.ssd
    chokepoint, whose custom VJP differentiates the unfused composition.
    """
    if x.ndim != 4 or a.ndim != 3 or b.ndim != 4 or c.ndim != 4:
        raise ValueError(f"ssd expects x(B,L,H,P) a(B,L,H) b/c(B,L,G,N); "
                         f"got {x.shape}, {a.shape}, {b.shape}, {c.shape}")
    bsz, l, h, p = x.shape
    g, n = b.shape[-2:]
    if a.shape != (bsz, l, h):
        raise ValueError(f"a shape {a.shape} incompatible with x {x.shape}")
    if b.shape != (bsz, l, g, n) or c.shape != b.shape:
        raise ValueError(f"b/c shapes {b.shape}/{c.shape} must match")
    if h % g:
        raise ValueError(f"heads {h} not divisible by groups {g}")
    if chunk <= 0 or l % chunk:
        raise ValueError(f"seq len {l} not divisible by chunk {chunk}")
    if init_state is not None and init_state.shape != (bsz, h, p, n):
        raise ValueError(f"init_state shape {init_state.shape} != "
                         f"{(bsz, h, p, n)}")
    pol = _policy.resolve(policy, backend)
    if chip is not None and chip is not pol.chip:
        pol = pol.replace(chip=chip)
    impl = _registry.get_impl("ssd", pol.backend)
    return impl(x, a, b, c, policy=pol, chunk=chunk, init_state=init_state,
                block=block)


# ----------------------------------------------------------------------
# compat shims — the ONLY layer that still interprets backend strings.
# Everything below exists so pre-Policy call sites keep working; new
# code constructs a Policy (core.policy) instead.
# ----------------------------------------------------------------------

#: Deprecated alias: the legacy string spellings `Policy.from_backend`
#: accepts. Kept so old `choices=kops.MATMUL_BACKENDS` CLIs still run.
MATMUL_BACKENDS = _policy.LEGACY_BACKEND_NAMES


def resolve_tuned(backend: str) -> str:
    """Deprecated: "tuned(_interpret)" executes the tiled kernel; the
    typed equivalent is Policy.from_backend(backend).kernel_fingerprint
    (cache entries stay keyed by execution backend so interpreter
    timings never leak into compiled-TPU decisions)."""
    _policy.warn_deprecated(
        "resolve_tuned",
        "kernels.ops.resolve_tuned is deprecated; use "
        "Policy.from_backend(name).kernel_fingerprint")
    return "pallas_interpret" if backend.endswith("interpret") else "pallas"
