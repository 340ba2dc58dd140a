"""Attention layers: GQA/MQA, qk-norm, QKV bias, RoPE/M-RoPE, sliding
window, cross-attention, KV-cache decode.

Two execution paths, both memory-hierarchy-aware (the paper's tiling
insight):
  * XLA path — online-softmax over KV chunks via lax.scan; the S matrix
    never exceeds (q, chunk). Differentiable; what the dry-run lowers.
  * Pallas path — kernels/flash_attention.py, the TPU target; swapped
    in through kernels.ops (validated in interpret mode on CPU).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import policy as _pol
from repro.core import precision as _prec
from repro.core.policy import Policy
from repro.distributed.context import constrain, current_mesh
from repro.kernels import ops as kops
from repro.models import layers as L


def _constrain_bthd(x, cfg):
    """Shard a (B, T, H, D) attention tensor: heads over "model" when
    divisible, else (opt-in) the sequence dim — context parallelism for
    head counts like 40 that don't divide the 16-wide model axis."""
    mesh = current_mesh()
    if mesh is None:
        return x
    tp = mesh.shape["model"]
    fallback = None if cfg.constrain_mode == "replicate" else "free"
    if x.shape[2] % tp == 0:
        return constrain(x, "dp", None, "tp", None)
    if cfg.shard_attn_seq and x.shape[1] % tp == 0:
        return constrain(x, "dp", "tp", fallback, None)
    return constrain(x, "dp", None, fallback, None)


# ----------------------------------------------------------------------
# Chunked online-softmax attention (pure jnp, differentiable)
# ----------------------------------------------------------------------

def chunked_attention(
    q: jnp.ndarray,               # [B, Tq, H, D]
    k: jnp.ndarray,               # [B, Tk, Hkv, D]
    v: jnp.ndarray,               # [B, Tk, Hkv, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    chunk: int = 2048,
    q_offset=0,                   # int / traced scalar / (B,) vector (decode)
    kv_len=None,                  # valid-length mask: scalar or (B,) vector
    io_dtype=jnp.float32,         # bf16 = flash-kernel numerics (§Perf)
    scale: Optional[float] = None,  # softmax scale; None = D^-0.5
) -> jnp.ndarray:
    b, tq, h, d = q.shape
    _, tk, hkv, _ = k.shape
    g = h // hkv
    scale = d ** -0.5 if scale is None else scale
    chunk = min(chunk, tk)
    assert tk % chunk == 0, (tk, chunk)
    n_chunks = tk // chunk

    qf = (q.astype(io_dtype) * jnp.asarray(scale, io_dtype)) \
        .reshape(b, tq, hkv, g, d)
    kc = k.astype(io_dtype).reshape(b, n_chunks, chunk, hkv, d)
    vc = v.astype(io_dtype).reshape(b, n_chunks, chunk, hkv, d)

    # Position grids broadcast to (Bm, Tq, chunk) where Bm is 1 for the
    # uniform (scalar-offset) case and B for per-slot vectors. A slot
    # with kv_len == 0 (inactive, pos < 0) masks every key; its output
    # is finite garbage the caller discards.
    q_off = jnp.asarray(q_offset)
    q_pos = jnp.arange(tq)[None, :, None] + \
        (q_off[:, None, None] if q_off.ndim else q_off)     # [Bm, Tq, 1]
    kl = None
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        kl = kl[:, None, None] if kl.ndim else kl

    def step(carry, inp):
        m, l, acc = carry
        kci, vci, c_idx = inp
        s = jnp.einsum("bqkgd,bckd->bqkgc", qf, kci,
                       preferred_element_type=jnp.float32)
        k_pos = c_idx * chunk + jnp.arange(chunk)[None, None, :]
        mask = jnp.ones((1, tq, chunk), dtype=bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        if kl is not None:
            mask &= k_pos < kl
        s = jnp.where(mask[:, :, None, None, :], s, -1e30)
        s_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, s_max)
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(vci.dtype), vci,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((b, tq, hkv, g), -1e30, jnp.float32)
    l0 = jnp.zeros((b, tq, hkv, g), jnp.float32)
    a0 = jnp.zeros((b, tq, hkv, g, d), jnp.float32)
    if n_chunks == 1:
        (m, l, acc), _ = step((m0, l0, a0),
                              (kc[:, 0], vc[:, 0], jnp.int32(0)))
    else:
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0),
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1),
             jnp.arange(n_chunks, dtype=jnp.int32)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).reshape(b, tq, h, d)
    return out.astype(q.dtype)


def _resolve_attn_policy(policy, backend) -> Policy:
    """Attention follows the ambient execution policy like every other
    chokepoint: the flash kernel has a registered backward
    (flash_attention_bwd) and a decode kernel (flash_decode), so the
    historical fwd-only XLA-default carve-out is gone. Code that relied
    on the old opt-in contract — an ambient pallas scope silently
    getting the chunked XLA path here — gets a one-time deprecation
    notice the first time the new resolution changes its routing."""
    if policy is None and backend is None:
        pol = _pol.current_policy()
        if pol.backend != "xla":
            _pol.warn_deprecated(
                "attn_xla_default_carveout",
                "attention now follows the ambient execution policy: the "
                "flash kernel gained a fused backward and a decode kernel, "
                "so the old backward-unsupported XLA-default carve-out is "
                "removed — pass policy=Policy() explicitly to keep the "
                "chunked XLA path under a non-xla scope")
        return pol
    return _pol.resolve(policy, backend)


_XLA_POLICY = Policy()


def _route_dtype(pol: Policy, dtype) -> Policy:
    """The flash kernels accumulate in f32 by construction, so f64
    requests reroute to the XLA chunked path, which honours the wider
    dtype (mirrors core.gemm._route_dtype, but unconditional: interpret
    mode would silently downcast too)."""
    if jnp.dtype(dtype) == jnp.float64 and pol.backend != "xla":
        return pol.replace(backend="xla")
    return pol


def _flash_shapes_ok(tq: int, tk: int) -> bool:
    """The kernels require block sizes to divide the sequence lengths
    after clamping (flash_attention asserts it); ragged shapes fall
    back to the chunked path."""
    return tq % min(256, tq) == 0 and tk % min(512, tk) == 0


# The fused custom-VJP chokepoint. causal/window/policy/scale ride as
# nondiff arguments (hashable — the core.gemm pattern), so the backward
# op runs under the same execution policy as the forward.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _attention_fused(q, k, v, causal, window, pol, scale):
    o, _ = kops.flash_attention_fwd(
        q, k, v, causal=causal, window=window, policy=pol, scale=scale)
    return o


def _attention_fused_fwd(q, k, v, causal, window, pol, scale):
    o, lse = kops.flash_attention_fwd(
        q, k, v, causal=causal, window=window, policy=pol, scale=scale)
    return o, (q, k, v, o, lse)


def _attention_fused_bwd(causal, window, pol, scale, res, do):
    q, k, v, o, lse = res
    return kops.flash_attention_bwd(
        q, k, v, o, do, lse, causal=causal, window=window, policy=pol,
        scale=scale)


_attention_fused.defvjp(_attention_fused_fwd, _attention_fused_bwd)


def attention(q, k, v, *, causal, window, chunk, q_offset=0, kv_len=None,
              policy: Policy | None = None, backend: str | None = None,
              io_dtype=jnp.float32, decode: bool = False,
              scale: Optional[float] = None):
    """The attention chokepoint (né `attend`). Routing under the
    resolved policy:

      * pallas + decode step (t == 1, kv_len = pos + 1): the
        flash_decode kernel — K/V stream only over each slot's valid
        cache prefix.
      * pallas + full-kv (kv_len None, block-divisible shapes, zero
        q_offset): the fused custom-VJP path — flash forward saving the
        per-row logsumexp, flash_attention_bwd for gradients (replacing
        differentiate-through-chunked).
      * everything else (xla policy, f64, ragged shapes, masked
        prefill): the chunked online-softmax path, differentiable by
        construction.

    The XLA path is wrapped in a named_scope so the roofline analyzer
    can identify attention-interior traffic — on the TPU target this
    whole region is the Pallas flash kernel (same math, validated in
    interpret mode) whose intermediates never touch HBM. §Perf models
    that substitution from the tag. `scale` is the softmax scale on
    every path (None = D^-0.5).
    """
    pol = _route_dtype(_resolve_attn_policy(policy, backend), q.dtype)
    if pol.backend == "pallas":
        if decode and q.shape[1] == 1 and k.shape[1] % min(512, k.shape[1]) == 0:
            # kv_len = q_offset + 1 by the decode contract: the kernel's
            # per-row prefix mask IS causal masking at depth q_offset.
            return kops.flash_decode(
                q, k, v, pos=q_offset, window=window, policy=pol,
                scale=scale)
        if kv_len is None and _flash_shapes_ok(q.shape[1], k.shape[1]) \
                and isinstance(q_offset, int) and q_offset == 0:
            return _attention_fused(q, k, v, causal, window, pol, scale)
    elif pol.backend != "xla" and kv_len is None:
        # naive etc.: the forward-only op (registry raises for backends
        # with no flash impl, listing the registered ones)
        return kops.flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset,
            policy=pol, scale=scale)
    with jax.named_scope("flashsite"):
        return chunked_attention(
            q, k, v, causal=causal, window=window, chunk=chunk,
            q_offset=q_offset, kv_len=kv_len, io_dtype=io_dtype,
            scale=scale)


#: Backwards-compatible alias — attn_apply and external callers used
#: the old name; same function, same signature.
attend = attention


# ----------------------------------------------------------------------
# Attention layer (self + cross)
# ----------------------------------------------------------------------

def attn_init(key, cfg, *, d_model=None, cross: bool = False):
    d = d_model or cfg.d_model
    dh = cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": L.dense_init(ks[0], d, h * dh, dtype=dtype, bias=cfg.qkv_bias),
        "wk": L.dense_init(ks[1], d, hkv * dh, dtype=dtype, bias=cfg.qkv_bias),
        "wv": L.dense_init(ks[2], d, hkv * dh, dtype=dtype, bias=cfg.qkv_bias),
        "wo": L.dense_init(ks[3], h * dh, d, dtype=dtype,
                           scale=(h * dh) ** -0.5 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qk_norm:
        p["q_norm"] = L.rmsnorm_init(dh, dtype=dtype)
        p["k_norm"] = L.rmsnorm_init(dh, dtype=dtype)
    return p


def project_cross_kv(p, enc_out, cfg):
    """Project encoder output to (k, v) for cross-attention (Whisper)."""
    return _project_kv(p, enc_out, cfg)


def _project_kv(p, x, cfg):
    b, t, _ = x.shape
    dh = cfg.resolved_head_dim
    k = L.dense_apply(p["wk"], x).reshape(b, t, cfg.n_kv_heads, dh)
    v = L.dense_apply(p["wv"], x).reshape(b, t, cfg.n_kv_heads, dh)
    k = constrain(k, "dp", None, "tp", None)   # kv heads stay head-sharded
    v = constrain(v, "dp", None, "tp", None)   # (or replicated if MQA-ish)
    if cfg.qk_norm:
        k = L.rmsnorm_apply(p["k_norm"], k, eps=cfg.norm_eps)
    return k, v


def attn_apply(
    p,
    x: jnp.ndarray,               # [B, T, D]
    cfg,
    *,
    positions: Optional[jnp.ndarray] = None,
    causal: bool = True,
    use_rope: Optional[bool] = None,
    cache: Optional[dict] = None,  # {"k","v"} [B, Tmax, Hkv, Dh] (+pos)
    cache_pos=None,                # write offset: scalar, or (B,) per-slot
                                   # vector (decode; pos < 0 = inactive slot,
                                   # cache row left untouched)
    n_valid=None,                  # (B,) count of valid tokens in a multi-
                                   # token per-slot chunk (speculative verify):
                                   # row writes past n_valid are dropped and
                                   # their keys masked; None = all t valid
    enc_kv: Optional[tuple] = None,  # cross-attn: precomputed (k, v)
    kv_table: Optional[jnp.ndarray] = None,  # (B, pages_per_slot) page table:
                                   # cache is a PAGE POOL {"k","v"[,"ks","vs"]}
                                   # of (P, Hkv, page_size, Dh) pages instead
                                   # of per-slot rows (decode only)
    policy: Optional[Policy] = None,
    backend: Optional[str] = None,   # deprecated string shim
):
    """Returns (out, new_cache). new_cache is None unless cache given.

    Kernel selection comes from `policy` (or the deprecated `backend`
    string, or the ambient policy): no-cache paths take the fused
    flash fwd/bwd pair, single-token cached steps take flash_decode,
    and masked prefill-into-cache stays on the chunked XLA path (see
    attention())."""
    pol = _resolve_attn_policy(policy, backend)
    # the configuration's softmax scale on every path (None = dh^-0.5)
    attend = functools.partial(attention, scale=cfg.attention_multiplier)
    b, t, _ = x.shape
    dh = cfg.resolved_head_dim
    use_rope = cfg.use_rope if use_rope is None else use_rope

    q = L.dense_apply(p["wq"], x).reshape(b, t, cfg.n_heads, dh)
    q = _constrain_bthd(q, cfg)
    if cfg.qk_norm:
        q = L.rmsnorm_apply(p["q_norm"], q, eps=cfg.norm_eps)

    io_dtype = jnp.float32 if cfg.attn_f32_io else jnp.bfloat16

    if enc_kv is not None:                      # cross attention
        k, v = enc_kv
        out = attend(q, k, v, causal=False, window=None,
                     chunk=cfg.attn_chunk, policy=pol,
                     io_dtype=io_dtype)
        out = out.reshape(b, t, cfg.n_heads * dh)
        return L.dense_apply(p["wo"], out), None

    k, v = _project_kv(p, x, cfg)

    pos_vec = cache_pos is not None and jnp.asarray(cache_pos).ndim == 1

    if positions is None:
        off = cache_pos if cache_pos is not None else 0
        positions = L.default_positions(b, t, off)
    if use_rope:
        q = L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    new_cache = None
    if cache is not None and kv_table is not None:
        # Paged decode / verify: `cache` is this layer's page POOL, not
        # per-slot rows. Each slot's k/v row for chunk index j lands at
        # (table[slot, (pos+j)//ps], (pos+j)%ps) — the engine's
        # prepare_write has already made every written page privately
        # writable (CoW), so the scatter never touches shared bytes.
        # Inactive slots (pos < 0), rows past n_valid, and unmapped
        # table entries route out of bounds; mode="drop" skips them.
        assert pos_vec, "paged KV cache requires per-slot positions"
        pos = jnp.asarray(cache_pos, jnp.int32)
        n_pages, page_sz = cache["k"].shape[0], cache["k"].shape[2]
        bidx = jnp.arange(pos.shape[0])
        wpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # (B,T)
        drop = pos[:, None] < 0
        if n_valid is not None:
            drop |= jnp.arange(t)[None] >= jnp.asarray(n_valid,
                                                       jnp.int32)[:, None]
        pj = jnp.where(drop, 0, wpos // page_sz)
        phys = kv_table[bidx[:, None], pj]
        phys = jnp.where(drop | (phys < 0), n_pages, phys)
        off = wpos % page_sz
        new_cache = dict(cache)
        if "ks" in cache:
            # int8 pages: quantize at page-write; the kernel dequantizes
            # on the f32 accumulator. Scale planes are (P, Hkv, ps) so a
            # page's scales sit lane-contiguous next to its rows.
            kq, ksc = _prec.quantize_kv(k)
            vq, vsc = _prec.quantize_kv(v)
            new_cache["k"] = cache["k"].at[phys, :, off].set(
                kq, mode="drop")
            new_cache["v"] = cache["v"].at[phys, :, off].set(
                vq, mode="drop")
            new_cache["ks"] = cache["ks"].at[phys, :, off].set(
                ksc, mode="drop")
            new_cache["vs"] = cache["vs"].at[phys, :, off].set(
                vsc, mode="drop")
        else:
            new_cache["k"] = cache["k"].at[phys, :, off].set(
                k.astype(cache["k"].dtype), mode="drop")
            new_cache["v"] = cache["v"].at[phys, :, off].set(
                v.astype(cache["v"].dtype), mode="drop")
        if t == 1:
            # Only pallas/xla have a paged gather; other backends
            # reroute to the dense XLA oracle (paged_gather_ref math).
            pol_r = pol if pol.backend in ("pallas", "xla") \
                else pol.replace(backend="xla")
            out = kops.flash_decode_paged(
                q, new_cache["k"], new_cache["v"], kv_table, pos=pos,
                window=cfg.window, ks=new_cache.get("ks"),
                vs=new_cache.get("vs"), policy=pol_r)
        else:
            # Multi-token verify (speculative decoding): gather each
            # slot's pages into a dense per-slot view (dequantizing int8
            # pages) and run the chunked masked path — exactly the dense
            # composition the paged kernel conformance-tests against.
            # The gather materializes (B, Tmax) rows once per verify
            # round; a paged multi-query kernel is the TPU follow-up.
            tclamp = jnp.maximum(kv_table, 0)
            kd = new_cache["k"][tclamp]       # (B, Ps, Hkv, ps, Dh)
            vd = new_cache["v"][tclamp]
            if "ks" in cache:
                kd = kd.astype(jnp.float32) \
                    * new_cache["ks"][tclamp][..., None]
                vd = vd.astype(jnp.float32) \
                    * new_cache["vs"][tclamp][..., None]
            b_, ps_ = tclamp.shape
            kd = kd.transpose(0, 1, 3, 2, 4).reshape(
                b_, ps_ * page_sz, cfg.n_kv_heads, dh)
            vd = vd.transpose(0, 1, 3, 2, 4).reshape(
                b_, ps_ * page_sz, cfg.n_kv_heads, dh)
            nv = jnp.asarray(t if n_valid is None else n_valid, jnp.int32)
            kv_len = jnp.where(pos < 0, 0, pos + nv)
            # pool width Ps*ps need not divide attn_chunk; page_sz does.
            ch = cfg.attn_chunk \
                if (ps_ * page_sz) % min(cfg.attn_chunk, ps_ * page_sz) == 0 \
                else page_sz
            out = attend(q, kd.astype(io_dtype), vd.astype(io_dtype),
                         causal=True, window=cfg.window,
                         chunk=ch, q_offset=pos,
                         kv_len=kv_len, io_dtype=io_dtype, policy=pol)
    elif cache is not None and pos_vec:
        # Continuous-batching decode (t == 1) or speculative verify
        # (t == k+1): each slot scatters its k/v rows at its own
        # positions — O(B*t) rows written, not O(cache). pos < 0
        # (inactive slot) and rows past n_valid map out of bounds and
        # mode="drop" skips the write entirely.
        pos = jnp.asarray(cache_pos, jnp.int32)
        bidx = jnp.arange(pos.shape[0])
        wpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]  # (B,T)
        drop = pos[:, None] < 0
        if n_valid is not None:
            drop |= jnp.arange(t)[None] >= jnp.asarray(n_valid,
                                                       jnp.int32)[:, None]
        widx = jnp.where(drop, cache["k"].shape[1], wpos)
        ck = cache["k"].at[bidx[:, None], widx].set(
            k.astype(cache["k"].dtype), mode="drop")
        cv = cache["v"].at[bidx[:, None], widx].set(
            v.astype(cache["v"].dtype), mode="drop")
        new_cache = {"k": ck, "v": cv}
        nv = jnp.asarray(t if n_valid is None else n_valid, jnp.int32)
        kv_len = jnp.where(pos < 0, 0, pos + nv) if t > 1 else pos + 1
        # Per-row masks subsume the SWA fast path (window via mask).
        out = attend(q, ck, cv, causal=True, window=cfg.window,
                     chunk=cfg.attn_chunk, q_offset=pos,
                     kv_len=kv_len, io_dtype=io_dtype,
                     policy=pol, decode=(t == 1))
    elif cache is not None:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"],
                                                 k.astype(cache["k"].dtype),
                                                 cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"],
                                                 v.astype(cache["v"].dtype),
                                                 cache_pos, axis=1)
        new_cache = {"k": ck, "v": cv}
        if (pol.backend == "pallas" and isinstance(cache_pos, int)
                and cache_pos == 0 and _flash_shapes_ok(t, t)):
            # Prefill into an empty cache: the valid keys are exactly
            # this chunk's k/v, so the fused flash kernel runs on them
            # instead of the chunked path masking the whole cache depth.
            out = attend(q, k, v, causal=True, window=cfg.window,
                         chunk=cfg.attn_chunk, io_dtype=io_dtype,
                         policy=pol)
        elif cfg.window is not None and t == 1 and cache["k"].shape[1] > 2 * cfg.window:
            # SWA decode fast-path: only the last `window` cache entries
            # can attend — slice them out instead of scanning 500k keys.
            start = jnp.maximum(cache_pos + 1 - cfg.window, 0)
            kw = jax.lax.dynamic_slice_in_dim(ck, start, cfg.window, axis=1)
            vw = jax.lax.dynamic_slice_in_dim(cv, start, cfg.window, axis=1)
            out = attend(q, kw, vw, causal=False, window=None,
                         chunk=cfg.attn_chunk,
                         kv_len=jnp.minimum(cache_pos + 1 - start,
                                            cfg.window),
                         io_dtype=io_dtype, policy=pol)
        else:
            out = attend(q, ck, cv, causal=True, window=cfg.window,
                         chunk=cfg.attn_chunk, q_offset=cache_pos,
                         kv_len=cache_pos + t, io_dtype=io_dtype,
                         policy=pol, decode=(t == 1))
    else:
        out = attend(q, k, v, causal=causal, window=cfg.window,
                     chunk=cfg.attn_chunk, policy=pol,
                     io_dtype=io_dtype)

    out = out.reshape(b, t, cfg.n_heads * dh)
    return L.dense_apply(p["wo"], out), new_cache
