"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth for the allclose sweeps in tests/ and the
'sequential algorithm' stand-ins for the paper's CPU baselines.
"""

from __future__ import annotations

import jax.numpy as jnp
import jax


def matmul_ref(a: jnp.ndarray, b: jnp.ndarray, out_dtype=None) -> jnp.ndarray:
    """C = A @ B with f32 accumulation (the paper's Cauchy product)."""
    if out_dtype is None:
        out_dtype = a.dtype
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else (
        jnp.complex64 if jnp.issubdtype(a.dtype, jnp.complexfloating) else jnp.float32)
    return jnp.matmul(a, b, preferred_element_type=acc_dtype).astype(out_dtype)


def dequantize_ref(wq: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Reconstruct the float weight from int8 + per-channel scale."""
    return wq.astype(scale.dtype) * scale


def matmul_q_ref(a: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                 out_dtype=None) -> jnp.ndarray:
    """Dequantized GEMM oracle: ``(A @ Wq) * scale`` with the scale
    applied on the accumulator — per-channel scales are constant along
    k so they commute with the contraction, which is exactly where the
    tiled kernel applies them (the flush phase). Wq is cast to A's
    dtype in place of a dequantize pass: int8 magnitudes (<= 127) are
    exact in bf16 and f32 alike."""
    if out_dtype is None:
        out_dtype = a.dtype
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    acc = jnp.matmul(a, wq.astype(a.dtype), preferred_element_type=acc_dtype)
    return (acc * scale.reshape(1, -1).astype(acc_dtype)).astype(out_dtype)


def epilogue_ref(y: jnp.ndarray, epilogue: str,
                 bias: jnp.ndarray | None = None,
                 residual: jnp.ndarray | None = None) -> jnp.ndarray:
    """Unfused composition of the kernel epilogues (kernels.matmul
    EPILOGUES) — the XLA path and the parity oracle for the fused flush."""
    if epilogue == "none":
        return y
    if epilogue == "residual":
        return y + residual.astype(y.dtype)
    y = y + bias.reshape(-1).astype(y.dtype)
    if epilogue == "bias_gelu":
        y = jax.nn.gelu(y)
    elif epilogue == "bias_silu":
        y = jax.nn.silu(y)
    return y


def gated_matmul_ref(a: jnp.ndarray, w_gate: jnp.ndarray,
                     w_up: jnp.ndarray, out_dtype=None) -> jnp.ndarray:
    """silu(A @ Wg) * (A @ Wu) with f32 accumulation, gate product in
    the accumulator dtype — the oracle for the dual-GEMM kernel."""
    if out_dtype is None:
        out_dtype = a.dtype
    acc_dtype = jnp.float64 if a.dtype == jnp.float64 else jnp.float32
    g = jnp.matmul(a, w_gate, preferred_element_type=acc_dtype)
    u = jnp.matmul(a, w_up, preferred_element_type=acc_dtype)
    return (jax.nn.silu(g) * u).astype(out_dtype)


def add_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a + b


def sub_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return a - b


def saxpy_ref(alpha, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    return alpha * x + y


def attention_ref(
    q: jnp.ndarray,              # [B, Tq, H, D]
    k: jnp.ndarray,              # [B, Tk, Hkv, D]
    v: jnp.ndarray,              # [B, Tk, Hkv, D]
    *,
    causal: bool = True,
    window: int | None = None,   # sliding-window size (None = full)
    scale: float | None = None,
    q_offset=0,                  # absolute position of q[0] (decode):
                                 # scalar, or (B,) per-row vector
) -> jnp.ndarray:
    """Dense softmax attention oracle with GQA broadcast + masks."""
    b, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    assert h % hkv == 0
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5

    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    # broadcast kv heads across the query-head group
    kf = jnp.repeat(kf, g, axis=2)
    vf = jnp.repeat(vf, g, axis=2)

    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    q_off = jnp.asarray(q_offset)
    q_pos = jnp.arange(tq)[None, :, None] + \
        (q_off[:, None, None] if q_off.ndim else q_off)   # (Bm, Tq, 1)
    k_pos = jnp.arange(tk)[None, None, :]
    mask = jnp.ones((1, tq, tk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[:, None], logits, -1e30)
    # A row with no valid key attends to nothing: its output is zero,
    # as in every kernel, not the mean of V a softmax over -1e30 gives.
    p = jnp.where(mask[:, None].any(axis=-1, keepdims=True),
                  jax.nn.softmax(logits, axis=-1), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype)


_LSE_EMPTY = 1e30    # fully-masked-row sentinel; see flash_attention.py


def _attention_logits(q, k, *, causal, window, scale, q_offset):
    """(scaled, masked) logits + mask shared by the fwd/bwd oracles."""
    b, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    g = h // hkv
    scale = scale if scale is not None else d ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    q_off = jnp.asarray(q_offset)
    q_pos = jnp.arange(tq)[None, :, None] + \
        (q_off[:, None, None] if q_off.ndim else q_off)
    k_pos = jnp.arange(tk)[None, None, :]
    mask = jnp.ones((1, tq, tk), dtype=bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    return jnp.where(mask[:, None], logits, -1e30), mask, g, scale


def attention_fwd_ref(
    q: jnp.ndarray,              # [B, Tq, H, D]
    k: jnp.ndarray,              # [B, Tk, Hkv, D]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset=0,
):
    """attention_ref plus the (B, H, Tq) f32 logsumexp residual — the
    XLA twin of flash_attention(..., return_lse=True). Fully-masked
    rows get the +1e30 sentinel so the backward's P = exp(S - lse)
    vanishes for them."""
    logits, mask, g, _ = _attention_logits(
        q, k, causal=causal, window=window, scale=scale, q_offset=q_offset)
    any_valid = jnp.any(jnp.broadcast_to(mask[:, None], logits.shape),
                        axis=-1)
    lse = jnp.where(any_valid,
                    jax.scipy.special.logsumexp(logits, axis=-1),
                    _LSE_EMPTY)                            # (B, H, Tq)
    p = jnp.exp(logits - lse[..., None])
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype), lse


def paged_gather_ref(
    kp: jnp.ndarray,             # [P, Hkv, page_size, D] page pool
    table: jnp.ndarray,          # [B, pages_per_slot] int32; -1 unmapped
    scales: jnp.ndarray | None = None,   # [P, Hkv, page_size] f32
) -> jnp.ndarray:
    """Materialise each slot's logical K/V tensor from the page pool:
    logical page j of slot b is pool page table[b, j], covering key
    positions [j*page_size, (j+1)*page_size). Unmapped entries clamp to
    page 0 — the caller's causal mask (pos < j*page_size) hides them.
    int8 pools dequantize against the per-(position, head) scales.
    Returns [B, pages_per_slot*page_size, Hkv, D]."""
    b, pp = table.shape
    n_pages, hkv, ps, d = kp.shape
    idx = jnp.maximum(jnp.asarray(table, jnp.int32), 0)
    gathered = kp[idx]                          # (B, pp, Hkv, ps, D)
    if scales is not None:
        gathered = gathered.astype(jnp.float32) * scales[idx][..., None]
    return gathered.transpose(0, 1, 3, 2, 4).reshape(b, pp * ps, hkv, d)


def flash_decode_paged_ref(
    q: jnp.ndarray,              # [B, 1, H, D]
    kp: jnp.ndarray,             # [P, Hkv, page_size, D]
    vp: jnp.ndarray,
    table: jnp.ndarray,          # [B, pages_per_slot] int32
    *,
    pos=0,                       # scalar or (B,) per-slot depth
    window: int | None = None,
    scale: float | None = None,
    ks: jnp.ndarray | None = None,
    vs: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Dense oracle for the paged decode kernel: gather + dequantize
    the pool through the page table, then the masked attention_fwd_ref
    at q_offset=pos. Returns [B, 1, H, D]."""
    k = paged_gather_ref(kp, table, ks)
    v = paged_gather_ref(vp, table, vs)
    out, _ = attention_fwd_ref(q, k, v, causal=True, window=window,
                               scale=scale, q_offset=pos)
    return out.astype(q.dtype)


def ssd_ref(
    x: jnp.ndarray,              # (B, L, H, P) — dt-scaled
    a: jnp.ndarray,              # (B, L, H)    — dt * A (log-decay)
    b_: jnp.ndarray,             # (B, L, G, N)
    c_: jnp.ndarray,             # (B, L, G, N)
    chunk: int,                  # unused: the scan is chunk-free
    init_state: jnp.ndarray | None = None,   # (B, H, P, N)
):
    """Sequential per-token SSD oracle (the 'naive' backend): the plain
    rank-N linear recurrence s_t = s_{t-1}·exp(a_t) + x_t b_tᵀ,
    y_t = s_t c_t, in f32 with no chunking at all — ground truth for
    every chunked formulation (chunking is algebraically exact, so
    `chunk` is accepted for signature parity and ignored). Returns
    (y in x.dtype, final_state f32 (B, H, P, N))."""
    bsz, l, h, p = x.shape
    g, n = b_.shape[-2:]
    rep = h // g
    acc = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
    xf = x.astype(acc)
    af = a.astype(acc)
    bf = jnp.repeat(b_.astype(acc), rep, axis=2)           # (B,L,H,N)
    cf = jnp.repeat(c_.astype(acc), rep, axis=2)
    s0 = (jnp.zeros((bsz, h, p, n), acc)
          if init_state is None else init_state.astype(acc))

    def step(s, inp):
        x_t, a_t, b_t, c_t = inp                           # (B,H,P)/(B,H)/...
        s = s * jnp.exp(a_t)[..., None, None] \
            + jnp.einsum("bhp,bhn->bhpn", x_t, b_t)
        y_t = jnp.einsum("bhn,bhpn->bhp", c_t, s)
        return s, y_t

    s_final, ys = jax.lax.scan(
        step, s0,
        (xf.swapaxes(0, 1), af.swapaxes(0, 1),
         bf.swapaxes(0, 1), cf.swapaxes(0, 1)))
    return ys.swapaxes(0, 1).astype(x.dtype), s_final


def attention_bwd_ref(
    q: jnp.ndarray,              # [B, Tq, H, D]
    k: jnp.ndarray,              # [B, Tk, Hkv, D]
    v: jnp.ndarray,
    o: jnp.ndarray,              # [B, Tq, H, D]  forward output
    do: jnp.ndarray,             # [B, Tq, H, D]  output cotangent
    lse: jnp.ndarray,            # [B, H, Tq] f32 forward residual
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset=0,
):
    """Closed-form attention backward from the saved (o, lse) residuals
    — the dense oracle for the recompute-style Pallas kernel, GQA
    group-sum included. Returns (dq, dk, dv) in the input dtypes."""
    b, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    logits, _, g, scale = _attention_logits(
        q, k, causal=causal, window=window, scale=scale, q_offset=q_offset)
    p = jnp.exp(logits - lse.astype(jnp.float32)[..., None])  # (B,H,Tq,Tk)
    dof = do.astype(jnp.float32)
    of = o.astype(jnp.float32)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=2)
    qs = q.astype(jnp.float32) * scale

    dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof)             # per q-head
    dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vf)
    delta = jnp.sum(dof * of, axis=-1)                     # (B, Tq, H)
    ds = p * (dp - delta.transpose(0, 2, 1)[..., None])
    dq = scale * jnp.einsum("bhqk,bkhd->bqhd", ds, kf)
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qs)             # per q-head
    # GQA: each kv head accumulates its group of query heads
    dk = dk.reshape(b, tk, hkv, g, d).sum(axis=3)
    dv = dv.reshape(b, tk, hkv, g, d).sum(axis=3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)
