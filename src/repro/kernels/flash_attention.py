"""Flash (online-softmax) attention as a Pallas TPU kernel.

This is the paper's core insight — stage tiles in scratchpad memory and
maximise reuse before touching HBM — applied to the framework's second
GEMM-shaped hot spot. The S = QK^T matrix is never materialised in HBM;
(bq, d) query tiles stay resident in VMEM while (bk, d) key/value tiles
stream through, with the running max/denominator kept in VMEM scratch
(the 'register accumulator' of Listing 4, generalised to softmax).

Supports causal masking, sliding windows (Mixtral), and GQA via an
index-map trick: query head h reads kv head h // group, so kv tensors
are never physically repeated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import compiler_params, row_to_column

_NEG_INF = -1e30
# logsumexp sentinel for a fully-masked row (inactive decode slot): the
# backward recomputes P = exp(S - lse), and S <= ~1e30, so +1e30 forces
# P = 0 — the row contributes nothing to any gradient.
_LSE_EMPTY = 1e30
_LANES = 128


def _row_offsets(offset, rows: int) -> jnp.ndarray:
    """Scalar or per-row offset -> the (rows,) int32 scalar-prefetch
    operand the kernels index by grid row."""
    return jnp.broadcast_to(
        jnp.asarray(offset, jnp.int32).reshape(-1), (rows,))


def _flash_kernel(
    qo_ref, q_ref, k_ref, v_ref, o_ref, *rest,
    n_kv: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None, save_lse: bool,
):
    if save_lse:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # q_offset is a scalar-prefetch operand (one int per B*H row, in
    # SMEM before the grid runs) so a single compiled kernel serves every
    # decode depth — and, with a per-row vector, a continuous batch of
    # requests at heterogeneous depths.
    q_start = pl.program_id(1) * bq + qo_ref[pl.program_id(0)]
    k_start = kv_i * bk

    # Block-level skip: entirely above the causal diagonal or entirely
    # left of the sliding window -> no compute (DMA still streams, the
    # cost model in core/blocking charges it; see EXPERIMENTS §Perf).
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, d)
        k = k_ref[0].astype(jnp.float32)                  # (bk, d)
        v = v_ref[0].astype(jnp.float32)                  # (bk, d)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                               # (bq, LANES)
        s_max = jnp.max(s, axis=1, keepdims=True)         # (bq, 1)
        m_new = jnp.maximum(m_prev, s_max)                # broadcast
        alpha = jnp.exp(m_prev - m_new)                   # (bq, LANES)
        # masked keys weigh exactly 0 even while a row has seen no valid
        # key (m = -1e30, where exp(s - m) would be 1): a row that never
        # does (non-causal window) keeps l = 0 and flushes to 0.
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)   # (bq, bk)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(
            p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _flush():
        l = l_ref[:, :1]
        lsafe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / lsafe).astype(o_ref.dtype)
        if save_lse:
            # lse = m + log(l) in the scaled-logit units the backward
            # recomputes S in; empty rows get the +inf sentinel. m and l
            # are lane-replicated, so the (bq, LANES) tile transposes
            # into the lane-dense (1, bq) row the lse output stores.
            lf = l_ref[...]
            lse = jnp.where(lf > 0.0,
                            m_ref[...] + jnp.log(jnp.where(lf == 0.0, 1.0, lf)),
                            _LSE_EMPTY)
            lse_ref[0] = lse.T[:1]


def flash_attention(
    q: jnp.ndarray,           # [B*H,  Tq, D]
    k: jnp.ndarray,           # [B*Hkv, Tk, D]
    v: jnp.ndarray,           # [B*Hkv, Tk, D]
    *,
    group: int = 1,           # H // Hkv
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset=0,               # scalar, or (B*H,) per-row vector (decode)
    bq: int = 256,
    bk: int = 512,
    block=None,
    interpret: bool = False,
    return_lse: bool = False,
):
    """Returns o, or (o, lse) with the per-row logsumexp (bh, tq) f32
    residual the recompute-style backward consumes (return_lse=True)."""
    # `block` (core.blocking.FlashBlockConfig — e.g. an autotuner-cache
    # winner) overrides the bq/bk defaults.
    if block is not None:
        bq, bk = block.bq, block.bk
    bh, tq, d = q.shape
    bhkv, tk, dk = k.shape
    assert d == dk and v.shape == k.shape
    assert bh == bhkv * group, (bh, bhkv, group)
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
    n_kv = tk // bk

    kernel = functools.partial(
        _flash_kernel, n_kv=n_kv, bq=bq, bk=bk, scale=scale,
        causal=causal, window=window, save_lse=return_lse)

    o_spec = pl.BlockSpec((1, bq, d), lambda h, i, j, qo: (h, i, 0))
    o_shape = jax.ShapeDtypeStruct((bh, tq, d), q.dtype)
    out_specs = o_spec
    out_shape = o_shape
    if return_lse:
        # lse rows are stored (bh, 1, tq): a (1, bq) block is lane-dense
        # and satisfies the TPU (8, 128) block rule on its last two dims.
        out_specs = [o_spec, pl.BlockSpec((1, 1, bq),
                                          lambda h, i, j, qo: (h, 0, i))]
        out_shape = [o_shape,
                     jax.ShapeDtypeStruct((bh, 1, tq), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, tq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, i, j, qo: (h, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, j, qo, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda h, i, j, qo, g=group: (h // g, j, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="flash_fwd",
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
    )(_row_offsets(q_offset, bh), q, k, v)
    if return_lse:
        return out[0], out[1].reshape(bh, tq)
    return out


# ----------------------------------------------------------------------
# Recompute-style backward (no S matrix in HBM)
# ----------------------------------------------------------------------
#
# With qs = q * scale and the saved per-row lse = m + log(l):
#
#     S  = qs K^T              P  = exp(S - lse)      (masked entries 0)
#     dV = P^T dO              dP = dO V^T
#     dS = P * (dP - D),       D  = rowsum(dO * O)    (computed in XLA)
#     dK = dS^T qs             dQ = scale * (dS K)
#
# Two sweeps so every output block is revisited only along the LAST
# ("arbitrary") grid dim: sweep 1 holds (bk, d) dK/dV accumulators in
# VMEM while q/dO/lse/D blocks stream past; sweep 2 mirrors it for dQ.
# S and P are recomputed in VMEM from the streamed tiles — they never
# existed in HBM in the forward and never do here either.


def _bwd_tiles(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               q_start, k_start, bq, bk, scale, causal, window):
    """Shared recompute of the (bq, bk) P / dS tiles for both sweeps."""
    q = q_ref[0].astype(jnp.float32) * scale              # (bq, d) scaled
    k = k_ref[0].astype(jnp.float32)                      # (bk, d)
    v = v_ref[0].astype(jnp.float32)                      # (bk, d)
    do = do_ref[0].astype(jnp.float32)                    # (bq, d)
    lse = row_to_column(lse_ref[0])                       # (bq, 1)
    delta = row_to_column(delta_ref[0])                   # (bq, 1)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bq, bk)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)            # (bq, bk)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # (bq, bk)
    ds = p * (dp - delta)                                 # (bq, bk)
    return q, k, do, p, ds


def _flash_bwd_dkv_kernel(
    qo_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_acc, dv_acc,
    *, n_q: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None,
):
    q_i = pl.program_id(2)

    @pl.when(q_i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = q_i * bq + qo_ref[pl.program_id(0)]
    k_start = pl.program_id(1) * bk
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q, _, do, p, ds = _bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, bq, bk, scale, causal, window)
        dv_acc[...] += jax.lax.dot_general(             # P^T dO  (bk, d)
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(             # dS^T qs (bk, d)
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(q_i == n_q - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(
    qo_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_acc,
    *, n_kv: int, bq: int, bk: int, scale: float,
    causal: bool, window: int | None,
):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = pl.program_id(1) * bq + qo_ref[pl.program_id(0)]
    k_start = kv_i * bk
    run = True
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _body():
        _, k, _, _, ds = _bwd_tiles(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
            q_start, k_start, bq, bk, scale, causal, window)
        dq_acc[...] += jax.lax.dot_general(             # dS K  (bq, d)
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_i == n_kv - 1)
    def _flush():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def flash_attention_bwd(
    q: jnp.ndarray,           # [B*H,  Tq, D]
    k: jnp.ndarray,           # [B*Hkv, Tk, D]
    v: jnp.ndarray,           # [B*Hkv, Tk, D]
    o: jnp.ndarray,           # [B*H,  Tq, D]  forward output
    do: jnp.ndarray,          # [B*H,  Tq, D]  output cotangent
    lse: jnp.ndarray,         # [B*H,  Tq] f32 forward logsumexp residual
    *,
    group: int = 1,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset=0,
    bq: int = 256,
    bk: int = 512,
    block=None,
    interpret: bool = False,
):
    """dQ/dK/dV in f32. dK/dV come back PER QUERY HEAD ([B*H, Tk, D]) —
    Pallas forbids revisiting an output block across non-consecutive
    grid steps, so the GQA group-sum over the h // group fan-in happens
    in the caller (kernels.ops), not here."""
    if block is not None:
        bq, bk = block.bq, block.bk
    bh, tq, d = q.shape
    bhkv, tk, dk_ = k.shape
    assert d == dk_ and v.shape == k.shape
    assert bh == bhkv * group, (bh, bhkv, group)
    assert o.shape == q.shape == do.shape
    assert lse.shape == (bh, tq), (lse.shape, bh, tq)
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, tq)
    bk = min(bk, tk)
    assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
    n_q, n_kv = tq // bq, tk // bk

    qo = _row_offsets(q_offset, bh)
    # lse and D = rowsum(dO * O) (one cheap XLA reduction instead of a
    # third sweep) stream into both kernels as lane-dense (bh, 1, tq) f32
    # rows, the layout the forward stores lse in.
    lse = lse.astype(jnp.float32).reshape(bh, 1, tq)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)
    params = compiler_params("parallel", "parallel", "arbitrary")

    qd_spec = pl.BlockSpec((1, bq, d), lambda h, j, i, qo: (h, i, 0))
    row_spec = pl.BlockSpec((1, 1, bq), lambda h, j, i, qo: (h, 0, i))
    kv_spec = pl.BlockSpec((1, bk, d),
                           lambda h, j, i, qo, g=group: (h // g, j, 0))
    dkv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, n_q=n_q, bq=bq, bk=bk, scale=scale,
            causal=causal, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_kv, n_q),
            in_specs=[qd_spec, kv_spec, kv_spec, qd_spec, row_spec,
                      row_spec],
            out_specs=[
                pl.BlockSpec((1, bk, d), lambda h, j, i, qo: (h, j, 0)),
                pl.BlockSpec((1, bk, d), lambda h, j, i, qo: (h, j, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)] * 2,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
            jax.ShapeDtypeStruct((bh, tk, d), jnp.float32),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
        compiler_params=params,
    )(qo, q, k, v, do, lse, delta)
    dk, dv = dkv

    # Sweep 2 swaps the roles: grid (bh, n_q, n_kv), so the same specs
    # serve with (j, i) now meaning (q-block, kv-block).
    qd_spec2 = pl.BlockSpec((1, bq, d), lambda h, i, j, qo: (h, i, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq), lambda h, i, j, qo: (h, 0, i))
    kv_spec2 = pl.BlockSpec((1, bk, d),
                            lambda h, i, j, qo, g=group: (h // g, j, 0))
    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, n_kv=n_kv, bq=bq, bk=bk, scale=scale,
            causal=causal, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh, n_q, n_kv),
            in_specs=[qd_spec2, kv_spec2, kv_spec2, qd_spec2, row_spec2,
                      row_spec2],
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda h, i, j, qo: (h, i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), jnp.float32),
        interpret=interpret,
        name="flash_bwd_dq",
        compiler_params=params,
    )(qo, q, k, v, do, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------------
# Decode-specialized kernel (q_len = 1 against a long cache)
# ----------------------------------------------------------------------
#
# The kernel reads the serving cache as it is stored, [B, Tk, Hkv, D],
# through the free row-major view [B, Tk*Hkv, D]: one grid step (slot,
# key block) takes a (bk*Hkv, D) block holding bk cache rows of every kv
# head, so each K/V byte is DMA'd once and serves all `group` query
# heads of its kv head while it sits in VMEM. Row r of the block is key
# r // Hkv of kv head r % Hkv; every query head is scored against every
# row, and the pairs of different kv heads are masked.


def _flash_decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, n_kv: int, bk: int, hkv: int, group: int, scale: float,
    window: int | None,
):
    kv_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[pl.program_id(0)]       # scalar-prefetch, (B,) SMEM
    k_start = kv_i * bk

    # THE decode win: only blocks intersecting the valid prefix
    # [max(0, pos-window+1), pos] run (and, by the index map's clamp,
    # only they are fetched) — a slot at depth 100 in a 4096 cache
    # touches one K/V block, not eight. pos < 0 (inactive slot) skips
    # every block; the flush's l == 0 guard keeps o finite.
    run = k_start <= pos
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > pos - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                                   # (h, d)
        k, v = k_ref[0], v_ref[0]                         # (bk*hkv, d)
        q = q.astype(jnp.promote_types(q.dtype, k.dtype))
        s = jax.lax.dot_general(
            q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (h, bk*hkv)
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
        k_pos = k_start + r // hkv
        mask = (r % hkv == head) & (k_pos <= pos)  # kv_len = pos + 1
        if window is not None:
            mask &= k_pos > pos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                               # (h, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kv_i == n_kv - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_kv_block(j, pos, bk: int, window: int | None = None):
    """The K/V block grid step j of a slot at depth `pos` fetches: j
    clamped to the blocks that hold keys in (pos - window, pos]. Past
    them the block index repeats, so the pipeline starts no DMA; an
    inactive slot (pos < 0) names block 0 only."""
    last = jnp.maximum(pos, 0) // bk
    if window is None:
        return jnp.minimum(j, last)
    first = jnp.maximum(pos - window + 1, 0) // bk
    return jnp.clip(j, first, last)


def flash_decode(
    q: jnp.ndarray,           # [B, 1, H, D]  one new token per slot
    k: jnp.ndarray,           # [B, Tk, Hkv, D]  the cache, max_len deep
    v: jnp.ndarray,           # [B, Tk, Hkv, D]
    *,
    window: int | None = None,
    scale: float | None = None,
    pos=0,                    # scalar, or (B,) per-slot depth vector;
                              # valid prefix is keys [0, pos] (causal)
    bk: int = 512,
    block=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """q_len=1 flash attention over the cache as stored. Equivalent to
    causal flash_attention with q_offset=pos at tq=1, on a (B, Tk/bk)
    grid: each step streams bk rows of every kv head of one slot once,
    and its index map clamps to the slot's valid prefix, so blocks past
    it are never fetched. GQA serves the `H // Hkv` query heads of a kv
    head from the same block — kv heads are never repeated. Returns
    [B, 1, H, D]; slots with pos < 0 (inactive) produce finite garbage
    the caller discards."""
    if block is not None:
        bk = block.bk
    b, tq, h, d = q.shape
    assert tq == 1, f"flash_decode is q_len=1 only, got tq={tq}"
    _, tk, hkv, dk_ = k.shape
    assert k.shape[0] == b and d == dk_ and v.shape == k.shape, \
        (q.shape, k.shape, v.shape)
    assert h % hkv == 0, (h, hkv)
    scale = scale if scale is not None else d ** -0.5
    bk = min(bk, tk)
    assert tk % bk == 0, (tk, bk)
    n_kv = tk // bk

    def kv_map(i, j, p):
        return (i, decode_kv_block(j, p[i], bk, window), 0)

    def row_map(i, j, p):
        return (i, 0, 0, 0)

    return pl.pallas_call(
        functools.partial(
            _flash_decode_kernel, n_kv=n_kv, bk=bk, hkv=hkv,
            group=h // hkv, scale=scale, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_kv),
            in_specs=[
                pl.BlockSpec((1, 1, h, d), row_map),
                pl.BlockSpec((1, bk * hkv, d), kv_map),
                pl.BlockSpec((1, bk * hkv, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, h, d), row_map),
            scratch_shapes=[
                pltpu.VMEM((h, d), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
                pltpu.VMEM((h, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, h, d), q.dtype),
        interpret=interpret,
        name="flash_decode",
        compiler_params=compiler_params("parallel", "arbitrary"),
    )(_row_offsets(pos, b), q, k.reshape(b, tk * hkv, d),
      v.reshape(b, tk * hkv, d))


# ----------------------------------------------------------------------
# Paged decode kernel (K/V gathered through a slot page table)
# ----------------------------------------------------------------------
#
# The serving cache is a pool of (Hkv, page_size, d) pages shared across
# slots (serving.kv_pool); each slot owns a page-table row mapping its
# logical pages onto pool indices. The table and the per-slot pos vector
# ride in as SCALAR-PREFETCH operands — they land in SMEM before the
# grid runs, so the K/V BlockSpec index maps can dereference them: grid
# step j of slot b streams pool page table[b, j // sub_per_page], one
# K/V page (or bk-sub-tile of it) per step. The dense kernel's
# `k_start <= pos` block skip carries over unchanged — j*bk is still the
# logical key offset — so a shallow slot touches only its own prefix no
# matter where its pages sit in the pool. int8 pools dequantize on the
# f32 accumulator inside the kernel: the per-(position, head) scales
# stream as (P, Hkv, page_size) planes sliced by the same index map.
# Head-major pages make a K/V block (bk, d) and a scale block (1, bk):
# tiles whose minor dims the TPU's (8, 128) rule accepts.

def _flash_decode_paged_kernel(
    table_ref, pos_ref,            # scalar-prefetch: (B, pp), (B,) SMEM
    *refs,
    n_steps: int, bk: int, scale: float, window: int | None, quant: bool,
):
    if quant:
        q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref \
            = refs
    else:
        q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[b]
    k_start = j * bk                  # logical key offset of this step

    # Same skip as the dense decode kernel: pages past the slot's valid
    # prefix [0, pos] never run (pos < 0 skips everything; the flush's
    # l == 0 guard keeps o finite). Unmapped table entries (-1) only
    # occur past the prefix, so the index-map clamp to page 0 is never
    # read by an active step.
    run = k_start <= pos
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > pos - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale          # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        if quant:
            k = k * row_to_column(ks_ref[0])              # dequant on f32
            v = v * row_to_column(vs_ref[0])
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (1, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        mask = k_pos <= pos
        if window is not None:
            mask &= k_pos > pos - window
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...]                               # (1, LANES)
        s_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, s_max)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_steps - 1)
    def _flush():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_decode_paged(
    q: jnp.ndarray,           # [B, H, D]  one new token per slot
    kp: jnp.ndarray,          # [P, Hkv, page_size, D]  K page pool
    vp: jnp.ndarray,          # [P, Hkv, page_size, D]  V page pool
    table: jnp.ndarray,       # [B, pages_per_slot] int32; -1 = unmapped
    *,
    group: int = 1,           # H // Hkv
    window: int | None = None,
    scale: float | None = None,
    pos=0,                    # scalar, or (B,) per-slot depth vector
    ks: jnp.ndarray | None = None,   # [P, Hkv, page_size] f32 K scales
    vs: jnp.ndarray | None = None,   # [P, Hkv, page_size] f32 V scales
    bk: int | None = None,    # sub-page tile; must divide page_size
    block=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """flash_decode against a paged KV pool: K/V blocks are gathered
    through `table` by the BlockSpec index maps (table + pos are
    scalar-prefetch SMEM operands), one page — or one bk-sub-tile of a
    page — per grid step. Pools may be int8 with per-(position, head)
    f32 scale planes (ks/vs): dequantization happens on the kernel's
    f32 accumulator, so HBM streams one byte per element. Returns
    [B, H, D]; rows with pos < 0 produce finite garbage the caller
    discards (same contract as flash_decode)."""
    if block is not None:
        bk = block.bk
    b, h, d = q.shape
    n_pages, hkv, ps, dk_ = kp.shape
    assert d == dk_ and vp.shape == kp.shape, (q.shape, kp.shape, vp.shape)
    assert h == hkv * group, (h, hkv, group)
    pp = table.shape[1]
    assert table.shape == (b, pp), (table.shape, b)
    quant = ks is not None
    if quant:
        assert vs is not None
        assert ks.shape == vs.shape == (n_pages, hkv, ps), \
            (ks.shape, n_pages, hkv, ps)
    bk = ps if bk is None else min(bk, ps)
    assert ps % bk == 0, (ps, bk)
    spp = ps // bk                    # grid sub-steps per page
    n_steps = pp * spp
    scale = scale if scale is not None else d ** -0.5

    table = jnp.asarray(table, jnp.int32)

    def page_map(bi, hi, j, t, p, g=group, s=spp):
        # -1 (unmapped) clamps to pool page 0; such steps never run.
        return (jnp.maximum(t[bi, j // s], 0), hi // g, j % s, 0)

    def scale_map(bi, hi, j, t, p, g=group, s=spp, n=hkv):
        return (jnp.maximum(t[bi, j // s], 0) * n + hi // g, 0, j % s)

    # q and o travel as (B*H, 1, d) rows: a (1, d) block per head
    def row_map(bi, hi, j, t, p, n=h):
        return (bi * n + hi, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, d), row_map),
        pl.BlockSpec((1, 1, bk, d), page_map),
        pl.BlockSpec((1, 1, bk, d), page_map),
    ]
    operands = [q.reshape(b * h, 1, d), kp, vp]
    if quant:
        # one (page, head) scale row per leading index: a (1, bk) block
        in_specs += [pl.BlockSpec((1, 1, bk), scale_map)] * 2
        operands += [ks.reshape(n_pages * hkv, 1, ps),
                     vs.reshape(n_pages * hkv, 1, ps)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, n_steps),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((1, d), jnp.float32),
            pltpu.VMEM((1, _LANES), jnp.float32),
            pltpu.VMEM((1, _LANES), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _flash_decode_paged_kernel, n_steps=n_steps, bk=bk,
            scale=scale, window=window, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        interpret=interpret,
        name="flash_decode_paged",
        compiler_params=compiler_params("parallel", "parallel", "arbitrary"),
    )(table, _row_offsets(pos, b), *operands).reshape(b, h, d)
