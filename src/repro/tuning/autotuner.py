"""Benchmark-driven tile search.

The paper fixes one block size per experiment and shows blocking wins;
this module closes the loop: for a concrete (M, N, K, dtype, backend)
it times every feasible tile config (tuning.space) with the shared
timing harness (tuning.timing, also behind benchmarks/), and persists
the winner to the fingerprint-keyed cache (tuning.cache) that the
`tuned` backend in kernels/ops.py consults.

Entry points:
  tune_matmul / tune_flash_attention  — sweep one shape, cache winner
  warm_start                          — launcher hook: load the cache
      for a model config's hot GEMM shapes, optionally tuning misses
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
import jax.numpy as jnp

from repro.core import hw
from repro.core import policy as _pol
from repro.core import precision as _prec
from repro.core.blocking import BlockConfig, FlashBlockConfig
from repro.core.policy import Policy
from repro.kernels import ops as _ops
from repro.tuning import space as _space
from repro.tuning.cache import TuningCache, get_cache
from repro.tuning.timing import time_jax


def default_exec_policy() -> Policy:
    """The Pallas execution policy timings are valid for on this host:
    compiled on a real TPU, interpreter otherwise (interpret=None is
    exactly that auto rule). Interpret-mode timings exercise the full
    mechanism but are not TPU wall-clock — the cache-key fingerprint
    keeps the two populations apart."""
    return Policy(backend="pallas")


def default_exec_backend() -> str:
    """Deprecated string twin of default_exec_policy() (its
    kernel_fingerprint), kept for pre-Policy callers."""
    return default_exec_policy().kernel_fingerprint


def _exec_policy(policy, backend) -> Policy:
    """Explicit policy > deprecated backend string > this host's
    default execution policy."""
    if policy is None and backend is None:
        return default_exec_policy()
    return _pol.resolve(policy, backend)


@dataclasses.dataclass(frozen=True)
class TuneResult:
    op: str                      # "matmul" | "flash"
    key: str                     # cache key the winner was stored under
    backend: str                 # policy.kernel_fingerprint the sweep ran on
    best: object                 # BlockConfig | FlashBlockConfig
    best_s: float
    baseline: object             # the static chooser's config
    baseline_s: float
    trials: tuple                # ((config, seconds), ...) in sweep order

    @property
    def speedup(self) -> float:
        return self.baseline_s / self.best_s if self.best_s > 0 else 1.0


def _timer(fn, args, interpret: bool, warmup: int, iters: int):
    # jit with the operands as real arguments — closing over them would
    # embed them as compile-time constants (one bloated recompile per
    # candidate, and XLA could fold parts of the graph it should time).
    if not interpret:
        fn = jax.jit(fn)
    return time_jax(fn, *args, warmup=warmup, iters=iters)


def _timing_meta(best_s: float, baseline_s: float) -> dict:
    """Advisory timing metadata, kept strictly JSON-finite: the static
    baseline config may itself have failed (inf) on this backend."""
    meta = {"time_us": round(best_s * 1e6, 2)}
    if math.isfinite(baseline_s) and best_s > 0:
        meta["baseline_us"] = round(baseline_s * 1e6, 2)
        meta["speedup"] = round(baseline_s / best_s, 4)
    return meta


def _sweep(op: str, desc: str, candidates, time_one, put_winner,
           cache: TuningCache, save: bool, backend: str) -> TuneResult:
    """Shared sweep skeleton for every tune_* entry point: time each
    candidate (an infeasible one scores inf and can never win), pick
    the winner against the static-chooser baseline (always candidate
    #0), persist it via put_winner, and package the TuneResult."""
    trials = []
    for cfg in candidates:
        try:
            t = time_one(cfg)
        except Exception:  # infeasible on this backend: never the winner
            t = float("inf")
        trials.append((cfg, t))

    baseline_cfg, baseline_s = trials[0]
    best_cfg, best_s = min(trials, key=lambda ct: ct[1])
    if not math.isfinite(best_s):
        raise RuntimeError(
            f"all {len(trials)} tile candidates failed for "
            f"{desc} on {backend}")
    key = put_winner(best_cfg, _timing_meta(best_s, baseline_s))
    if save:
        cache.save()
    return TuneResult(op, key, backend, best_cfg, best_s,
                      baseline_cfg, baseline_s, tuple(trials))


def tune_matmul(
    m: int,
    n: int,
    k: int,
    dtype="float32",
    *,
    epilogue: str = "none",
    quant: str | None = None,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep tile configs for one GEMM shape and cache the winner.

    `epilogue` times the fused-flush variant (bias / bias_gelu /
    bias_silu / residual) with synthetic epilogue operands — the extra
    operand DMA and VPU work shift the optimum, so each variant gets
    its own cache entry (tuning.cache.matmul_key — keyed by the
    policy's kernel fingerprint).

    `quant` (None = the policy's quant field) selects the op: "int8"
    quantizes the weight operand and sweeps the matmul_q kernel — the
    1-byte weight stream shifts the optimum again, so winners land
    under the separate matmul_q key population (cache.matmul_q_key).
    Pass quant="off" with an int8 policy to tune the PLAIN kernel under
    that policy's fingerprint (the cotangent GEMMs of dense_q's
    backward run unquantized)."""
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    quant = pol.quant if quant is None else quant
    if quant not in _pol.QUANT_MODES:
        raise ValueError(f"unknown quant mode {quant!r}; expected one of "
                         f"{_pol.QUANT_MODES}")
    quantized = quant == "int8"
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    rng = np.random.default_rng(seed)
    if np.dtype(dtype) == np.complex64:
        raise ValueError("tune the underlying real GEMMs (core.gemm "
                         "decomposes complex64 into 3 f32 GEMMs)")
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    itemsize = jnp.dtype(dtype).itemsize
    # epilogue operands ride the args tuple, NOT a closure: _timer jits
    # with real arguments so the operand DMA being tuned for is timed,
    # not constant-folded (see _timer's methodology note).
    args = (a, b) if not quantized else \
        (a,) + _prec.quantize_int8(b)
    ep_name = None
    if epilogue == "residual":
        ep_name = "residual"
        args += (jnp.asarray(rng.normal(size=(m, n)), dtype),)
    elif epilogue != "none":
        ep_name = "bias"
        args += (jnp.asarray(rng.normal(size=(n,)), dtype),)

    if quantized:
        time_one = lambda cfg: _timer(
            lambda x, w, s, *e, c=cfg: _ops.matmul_q(
                x, w, s, policy=pol, block=c, epilogue=epilogue,
                **({ep_name: e[0]} if ep_name else {})),
            args, interpret, warmup, iters)
        put_winner = lambda cfg, meta: cache.put_matmul_q(
            m, n, k, dtype, pol, cfg, epilogue=epilogue, **meta)
    else:
        time_one = lambda cfg: _timer(
            lambda x, y, *e, c=cfg: _ops.matmul(
                x, y, policy=pol, block=c, epilogue=epilogue,
                **({ep_name: e[0]} if ep_name else {})),
            args, interpret, warmup, iters)
        put_winner = lambda cfg, meta: cache.put_matmul(
            m, n, k, dtype, pol, cfg, epilogue=epilogue, **meta)

    op = "matmul_q" if quantized else "matmul"
    return _sweep(
        op,
        f"{op} {m}x{n}x{k} {np.dtype(dtype).name} epilogue={epilogue}",
        _space.matmul_candidates(m, n, k, itemsize, chip=chip,
                                 max_candidates=max_candidates),
        time_one, put_winner,
        cache, save, pol.kernel_fingerprint)


def tune_gated_matmul(
    m: int,
    n: int,
    k: int,
    dtype="float32",
    *,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep tiles for the dual-GEMM SwiGLU kernel and cache the winner
    (the doubled B-side working set makes its optimum distinct from the
    plain GEMM's)."""
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    wg = jnp.asarray(rng.normal(size=(k, n)), dtype)
    wu = jnp.asarray(rng.normal(size=(k, n)), dtype)
    itemsize = jnp.dtype(dtype).itemsize

    return _sweep(
        "gated", f"gated {m}x{n}x{k} {np.dtype(dtype).name}",
        _space.gated_matmul_candidates(m, n, k, itemsize, chip=chip,
                                       max_candidates=max_candidates),
        lambda cfg: _timer(lambda x, g, u, c=cfg: _ops.gated_matmul(
            x, g, u, policy=pol, block=c),
            (a, wg, wu), interpret, warmup, iters),
        lambda cfg, meta: cache.put_gated(m, n, k, dtype, pol, cfg,
                                          **meta),
        cache, save, pol.kernel_fingerprint)


def tune_flash_attention(
    tq: int,
    tk: int,
    d: int,
    dtype="float32",
    *,
    heads: int = 1,
    causal: bool = True,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep (bq, bk) flash-attention tiles for one shape; cache winner."""
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, tq, heads, d)), dtype)
    kv = jnp.asarray(rng.normal(size=(1, tk, heads, d)), dtype)
    itemsize = jnp.dtype(dtype).itemsize

    return _sweep(
        "flash", f"flash {tq}x{tk}xd{d} {np.dtype(dtype).name}",
        _space.flash_candidates(tq, tk, d, itemsize, chip=chip,
                                max_candidates=max_candidates),
        lambda cfg: _timer(lambda x, y, c=cfg: _ops.flash_attention(
            x, y, y, causal=causal, policy=pol, block=c),
            (q, kv), interpret, warmup, iters),
        lambda cfg, meta: cache.put_flash(tq, tk, d, dtype, pol, cfg,
                                          **meta),
        cache, save, pol.kernel_fingerprint)


def tune_flash_decode(
    tk: int,
    d: int,
    dtype="float32",
    *,
    batch: int = 4,
    heads: int = 1,
    kv_heads: int | None = None,
    pos: int | None = None,
    window: int | None = None,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep K/V tile sizes for the q_len=1 decode kernel over a
    depth-tk cache of `kv_heads` kv heads (default: `heads`) and persist
    the winner under flash_decode_key.

    `pos` defaults to tk - 1 (a full cache): that is the worst case for
    DMA volume and the regime the steady-state serving loop lives in, so
    it is what the timer should optimise. The `batch` slots share one
    pos — per-slot raggedness moves block-skip work, not the optimum.
    """
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    hkv = heads if kv_heads is None else kv_heads
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(batch, 1, heads, d)), dtype)
    kv = jnp.asarray(rng.normal(size=(batch, tk, hkv, d)), dtype)
    pos_v = jnp.full((batch,), tk - 1 if pos is None else pos, jnp.int32)
    itemsize = jnp.dtype(dtype).itemsize

    return _sweep(
        "flash_decode",
        f"flash_decode {tk}xh{hkv}xd{d} {np.dtype(dtype).name}",
        _space.flash_decode_candidates(tk, hkv, d, itemsize, chip=chip,
                                       max_candidates=max_candidates),
        lambda cfg: _timer(lambda x, y, p, c=cfg: _ops.flash_decode(
            x, y, y, pos=p, window=window, policy=pol, block=c),
            (q, kv, pos_v), interpret, warmup, iters),
        lambda cfg, meta: cache.put_flash_decode(tk, hkv, d, dtype, pol,
                                                 cfg, **meta),
        cache, save, pol.kernel_fingerprint)


def tune_flash_decode_paged(
    page_size: int,
    d: int,
    dtype="float32",
    *,
    batch: int = 4,
    heads: int = 1,
    pages_per_slot: int = 4,
    pos: int | None = None,
    window: int | None = None,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep sub-page K/V tiles for the paged decode kernel and persist
    the winner under flash_decode_paged_key — keyed by (page_size,
    head_dim), the only dims the tile space depends on (bk must divide
    the page; pool size and slot count just scale the grid).

    The synthetic pool maps slot b's pages identity-style (page b*pp+j)
    at full depth, the steady-state worst case. policy.quant_kv="int8"
    times the dequantizing variant: the int8 pool + scale planes are
    what streams, and the winner lands under the _kvint8-suffixed
    fingerprint so full-width winners are never served to it."""
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    rng = np.random.default_rng(seed)
    pp = pages_per_slot
    n_pages = batch * pp
    depth = pp * page_size
    q = jnp.asarray(rng.normal(size=(batch, 1, heads, d)), dtype)
    kp = jnp.asarray(rng.normal(size=(n_pages, heads, page_size, d)), dtype)
    vp = jnp.asarray(rng.normal(size=(n_pages, heads, page_size, d)), dtype)
    table = jnp.arange(n_pages, dtype=jnp.int32).reshape(batch, pp)
    pos_v = jnp.full((batch,), depth - 1 if pos is None else pos, jnp.int32)
    ks = vs = None
    if pol.quant_kv == "int8":
        kp, ks = _prec.quantize_kv(kp)      # ks: (P, Hkv, page_size)
        vp, vs = _prec.quantize_kv(vp)
    itemsize = 1 if pol.quant_kv == "int8" else jnp.dtype(dtype).itemsize

    return _sweep(
        "flash_decode_paged",
        f"flash_decode_paged p{page_size}xd{d} {np.dtype(dtype).name}",
        _space.flash_decode_paged_candidates(
            page_size, d, itemsize, chip=chip,
            max_candidates=max_candidates),
        lambda cfg: _timer(
            lambda x, kk, vv, t, p, c=cfg: _ops.flash_decode_paged(
                x, kk, vv, t, pos=p, window=window, ks=ks, vs=vs,
                policy=pol, block=c),
            (q, kp, vp, table, pos_v), interpret, warmup, iters),
        lambda cfg, meta: cache.put_flash_decode_paged(
            page_size, d, dtype, pol, cfg, **meta),
        cache, save, pol.kernel_fingerprint)


def tune_flash_bwd(
    tq: int,
    tk: int,
    d: int,
    dtype="float32",
    *,
    heads: int = 1,
    causal: bool = True,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep (bq, bk) for the two-sweep recompute backward and persist
    the winner under flash_bwd_key — a separate population from the
    forward's (the dK/dV accumulators + q/do re-streams shift the
    optimum). Residuals (o, lse) come from one un-timed forward call so
    the sweep times exactly what training's backward pass runs."""
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(1, tq, heads, d)), dtype)
    kv = jnp.asarray(rng.normal(size=(1, tk, heads, d)), dtype)
    do = jnp.asarray(rng.normal(size=(1, tq, heads, d)), dtype)
    o, lse = _ops.flash_attention_fwd(q, kv, kv, causal=causal, policy=pol)
    itemsize = jnp.dtype(dtype).itemsize

    return _sweep(
        "flash_bwd", f"flash_bwd {tq}x{tk}xd{d} {np.dtype(dtype).name}",
        _space.flash_bwd_candidates(tq, tk, d, itemsize, chip=chip,
                                    max_candidates=max_candidates),
        lambda cfg: _timer(
            lambda x, y, oo, g, l, c=cfg: _ops.flash_attention_bwd(
                x, y, y, oo, g, l, causal=causal, policy=pol, block=c),
            (q, kv, o, do, lse), interpret, warmup, iters),
        lambda cfg, meta: cache.put_flash_bwd(tq, tk, d, dtype, pol, cfg,
                                              **meta),
        cache, save, pol.kernel_fingerprint)


def tune_ssd(
    chunk: int,
    p: int,
    n: int,
    dtype="float32",
    *,
    heads: int = 4,
    groups: int = 1,
    batch: int = 1,
    seqlen: int | None = None,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    cache: TuningCache | None = None,
    chip: hw.ChipSpec | None = None,
    warmup: int = 1,
    iters: int = 3,
    max_candidates: int | None = None,
    save: bool = True,
    seed: int = 0,
) -> TuneResult:
    """Sweep (q, bp) execution tiles for the SSD intra-chunk kernel
    over one (model chunk, head dim P, state dim N) shape and persist
    the winner under ssd_key.

    Because chunking is algebraically exact, every candidate computes
    the same output — the sweep is purely a perf vote between "bigger
    intra-chunk matmuls" (large q: quadratic (q, q) decay/score blocks,
    few scan steps) and "cheaper masks, longer scan" (small q). `seqlen`
    (default 4 model chunks) sets the timed sequence; decays are drawn
    negative, as mamba_apply's -exp(A_log)*dt always is."""
    pol = _exec_policy(policy, backend)
    if chip is not None:        # explicit kwarg overrides the policy's chip
        pol = pol.replace(chip=chip)
    chip = pol.chip
    cache = get_cache() if cache is None else cache
    interpret = pol.resolved_interpret
    rng = np.random.default_rng(seed)
    l = seqlen or 4 * chunk
    if l % chunk:
        raise ValueError(f"seqlen {l} must be a multiple of chunk {chunk}")
    x = jnp.asarray(rng.normal(size=(batch, l, heads, p)), dtype)
    a = jnp.asarray(-np.abs(rng.normal(size=(batch, l, heads))) * 0.1,
                    jnp.float32)
    b = jnp.asarray(rng.normal(size=(batch, l, groups, n)), dtype)
    c = jnp.asarray(rng.normal(size=(batch, l, groups, n)), dtype)
    itemsize = jnp.dtype(dtype).itemsize

    return _sweep(
        "ssd", f"ssd Q{chunk}xP{p}xN{n} {np.dtype(dtype).name}",
        _space.ssd_candidates(chunk, p, n, itemsize, chip=chip,
                              max_candidates=max_candidates),
        lambda cfg: _timer(lambda xx, aa, bb, cc, c=cfg: _ops.ssd(
            xx, aa, bb, cc, chunk, policy=pol, block=c),
            (x, a, b, c), interpret, warmup, iters),
        lambda cfg, meta: cache.put_ssd(chunk, p, n, dtype, pol, cfg,
                                        **meta),
        cache, save, pol.kernel_fingerprint)


def model_ssd_shapes(cfg, batch: int = 1, seq: int = 1) -> list[tuple]:
    """The SSD shapes a step of `cfg` routes through core.ssd, as
    deduplicated ``(op, chunk, P, N, "-")`` entries mirroring the other
    model_*_shapes 5-tuple layout. Both pure-SSM and hybrid families
    contribute (every mamba layer shares one shape); attention-only
    configs contribute nothing. `batch`/`seq` are accepted for signature
    symmetry — the SSD tile space depends only on (chunk, P, N)."""
    del batch, seq
    sc = getattr(cfg, "ssm", None)
    if sc is None or getattr(cfg, "family", None) not in ("ssm", "hybrid"):
        return []
    return [("ssd", sc.chunk, sc.head_dim, sc.d_state, "-")]


def model_attention_shapes(cfg, batch: int, seq: int,
                           backward: bool = False,
                           decode_len: int | None = None) -> list[tuple]:
    """The flash-kernel shapes a (batch, seq) step of `cfg` runs, as
    deduplicated ``(op, tq, tk, d, "-")`` entries — op "flash" (fused
    forward), "flash_bwd" (training backward, with backward=True) or
    "flash_decode" (``(op, n_kv_heads, decode_len, d, "-")``, when a
    cache depth is given: its query length is 1 by construction, and its
    tile is keyed by the kv heads each grid step streams). Entries
    mirror model_gemm_shapes' 5-tuple layout so warm_start can
    interleave the two lists in one report.

    Attention shapes are per (batch x head) slice, so `batch` does not
    enter the keys — it is accepted for signature symmetry. Pure-SSM
    configs (no attention anywhere) contribute nothing."""
    del batch
    if getattr(cfg, "family", None) == "ssm" or \
            not getattr(cfg, "n_heads", 0):
        return []
    head_dim = getattr(cfg, "resolved_head_dim",
                       cfg.head_dim or cfg.d_model // cfg.n_heads)
    entries = set()
    if seq > 1:
        entries.add(("flash", seq, seq, head_dim, "-"))
        if backward:
            entries.add(("flash_bwd", seq, seq, head_dim, "-"))
    if decode_len:
        entries.add(("flash_decode", cfg.n_kv_heads, decode_len, head_dim,
                     "-"))
    return sorted(entries)


def model_gemm_shapes(cfg, batch: int, seq: int,
                      backward: bool = False,
                      quant: bool = False) -> list[tuple]:
    """The dense contractions a (batch, seq) step of `cfg` pushes
    through the core.gemm chokepoint, as deduplicated
    ``(op, m, n, k, epilogue)`` entries — op "matmul" (epilogue-variant
    GEMM), "matmul_q" (int8-weight GEMM) or "gated" (the dual-GEMM
    SwiGLU kernel, epilogue "-"). Covers attention projections, the FFN
    (fused: gated hidden + residual/bias down-projection, per cfg.mlp),
    and the logits GEMM at the PADDED vocab — the lm_head the model
    actually allocates.

    quant=True describes the model AFTER models.model.quantize_params:
    dense layers run matmul_q, gated layers decompose into two dense_q
    GEMMs of the hidden shape (models.layers.gated_apply), and —
    crucially — a TIED lm_head keeps running the PLAIN kernel (the
    embedding is in QUANT_EXCLUDE and embed_attend routes through
    gemm.matmul), so its entry stays op "matmul".

    backward=True adds the custom-VJP cotangent GEMMs per forward
    shape: da = g @ w.T is (m, k, n) and dw = x.T @ g is (k, n, m),
    plus the plain recompute GEMMs the fused paths' backward passes
    route through the chokepoint — without these, a tuned training run
    would only serve the forward third of its GEMM flops from the cache.
    dense_q's backward also differentiates through PLAIN matmuls (on
    the dequantized weights), so these stay op "matmul" under quant.
    """
    m = batch * seq
    head_dim = getattr(cfg, "resolved_head_dim",
                       cfg.head_dim or cfg.d_model // cfg.n_heads)
    vocab = getattr(cfg, "padded_vocab", cfg.vocab)
    qkv_ep = "bias" if getattr(cfg, "qkv_bias", False) else "none"
    dense_op = "matmul_q" if quant else "matmul"
    logits_op = "matmul" if getattr(cfg, "tie_embeddings", False) \
        else dense_op
    entries = {
        (dense_op, m, cfg.n_heads * head_dim, cfg.d_model, qkv_ep),    # Q
        (dense_op, m, cfg.n_kv_heads * head_dim, cfg.d_model, qkv_ep),  # K/V
        (dense_op, m, cfg.d_model, cfg.n_heads * head_dim, "none"),    # O
        (logits_op, m, vocab, cfg.d_model, "none"),                    # logits
    }
    if getattr(cfg, "mlp", "swiglu") == "swiglu":
        if quant:   # gated_apply decomposes into two dense_q GEMMs
            entries.add(("matmul_q", m, cfg.d_ff, cfg.d_model, "none"))
        else:
            entries.add(("gated", m, cfg.d_ff, cfg.d_model, "-"))
        entries.add((dense_op, m, cfg.d_model, cfg.d_ff, "residual"))
    else:  # gelu MLP: bias+act fused up, bias fused down (+residual xla)
        entries.add((dense_op, m, cfg.d_ff, cfg.d_model, "bias_gelu"))
        entries.add((dense_op, m, cfg.d_model, cfg.d_ff, "bias"))
    if backward:
        # fused backward passes recompute/differentiate through plain
        # GEMMs: each forward (m, n, k) contributes its unfused triple
        # and both cotangent triples, all epilogue-free.
        fwd = {(mm, nn, kk) for (_, mm, nn, kk, _) in entries}
        entries |= {("matmul", mm, nn, kk, "none")
                    for t in fwd
                    for (mm, nn, kk) in (t, (t[0], t[2], t[1]),
                                         (t[2], t[1], t[0]))}
    return sorted(entries)


def warm_start(
    cfg,
    batch: int,
    seq,
    *,
    policy: Policy | None = None,
    backend: str | None = None,         # deprecated string shim
    autotune: bool = False,
    backward: bool = False,
    decode_len: int | None = None,
    cache: TuningCache | None = None,
    iters: int = 2,
    max_candidates: int = 8,
) -> dict:
    """Launcher startup hook (launch/serve.py, launch/train.py).

    Loads the tuning cache and checks it for the model's hot GEMM
    shapes AND flash-attention shapes — `seq` may be an int or an
    iterable of sequence lengths (serving warms both the prefill rows
    batch*prompt_len and the decode rows batch*1); `decode_len` (the KV
    cache depth) adds the flash_decode shape, and backward=True adds
    both the cotangent GEMMs and the flash_bwd shape. With
    autotune=False this only reports coverage — misses fall back to the
    static chooser at run time, so serving never blocks on a sweep.
    With autotune=True the misses are tuned and persisted before the
    first step; a shape whose sweep fails outright is reported under
    "failed" and left to the fallback.

    `policy` is the execution policy whose kernel fingerprint keys the
    cache entries (launchers pass the policy they will run under;
    default: this host's execution policy).
    """
    pol = _exec_policy(policy, backend)
    cache = get_cache() if cache is None else cache
    dtype = getattr(cfg, "dtype", "float32")
    seqs = (seq,) if isinstance(seq, int) else tuple(seq)
    shapes = sorted({s for q in seqs
                     for s in model_gemm_shapes(cfg, batch, q,
                                                backward=backward,
                                                quant=pol.quant == "int8")}
                    | {s for q in seqs
                       for s in model_attention_shapes(
                           cfg, batch, q, backward=backward,
                           decode_len=decode_len)}
                    | set(model_ssd_shapes(cfg, batch)))
    hits, misses, tuned, failed = [], [], [], []
    for entry in shapes:
        op, m, n, k, ep = entry
        if op == "gated":
            hit = cache.get_gated(m, n, k, dtype, pol) is not None
        elif op == "matmul_q":
            hit = cache.get_matmul_q(m, n, k, dtype, pol,
                                     epilogue=ep) is not None
        elif op == "flash":
            hit = cache.get_flash(m, n, k, dtype, pol) is not None
        elif op == "flash_bwd":
            hit = cache.get_flash_bwd(m, n, k, dtype, pol) is not None
        elif op == "flash_decode":
            hit = cache.get_flash_decode(n, m, k, dtype, pol) is not None
        elif op == "ssd":
            hit = cache.get_ssd(m, n, k, dtype, pol) is not None
        else:
            hit = cache.get_matmul(m, n, k, dtype, pol,
                                   epilogue=ep) is not None
        if hit:
            hits.append(entry)
        elif autotune:
            try:
                if op == "gated":
                    tune_gated_matmul(m, n, k, dtype, policy=pol,
                                      cache=cache, iters=iters,
                                      max_candidates=max_candidates,
                                      save=False)
                elif op == "flash":
                    tune_flash_attention(m, n, k, dtype, policy=pol,
                                         cache=cache, iters=iters,
                                         max_candidates=max_candidates,
                                         save=False)
                elif op == "flash_bwd":
                    tune_flash_bwd(m, n, k, dtype, policy=pol,
                                   cache=cache, iters=iters,
                                   max_candidates=max_candidates,
                                   save=False)
                elif op == "flash_decode":
                    tune_flash_decode(n, k, dtype, kv_heads=m,
                                      heads=cfg.n_heads, policy=pol,
                                      cache=cache, iters=iters,
                                      max_candidates=max_candidates,
                                      save=False)
                elif op == "ssd":
                    tune_ssd(m, n, k, dtype, policy=pol,
                             cache=cache, iters=iters,
                             max_candidates=max_candidates,
                             save=False)
                else:
                    tune_matmul(m, n, k, dtype, epilogue=ep,
                                quant="int8" if op == "matmul_q" else "off",
                                policy=pol, cache=cache, iters=iters,
                                max_candidates=max_candidates, save=False)
                tuned.append(entry)
            except RuntimeError:  # every candidate failed: use fallback
                failed.append(entry)
        else:
            misses.append(entry)
    if tuned:
        cache.save()
    return {
        "path": cache.path,
        "fingerprint": cache.fingerprint,
        "backend": pol.kernel_fingerprint,
        "policy": pol.fingerprint(),
        "hits": hits,
        "misses": misses,
        "tuned": tuned,
        "failed": failed,
    }


def describe_warm_start(rep: dict) -> str:
    """One-line launcher log for a warm_start report."""
    line = (f"tuning cache {rep['path']} [{rep['backend']}]: "
            f"{len(rep['hits'])} hits, {len(rep['misses'])} misses, "
            f"{len(rep['tuned'])} tuned at startup")
    if rep.get("failed"):
        line += f", {len(rep['failed'])} failed (static fallback)"
    return line
