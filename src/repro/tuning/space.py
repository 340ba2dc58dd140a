"""Candidate tile spaces for the autotuner.

The static chooser in core.blocking picks ONE config from the paper's
VMEM-budget argument; this module enumerates the feasible neighbourhood
around it so the autotuner can let the hardware vote. Constraints are
the same as the chooser's (MXU/lane alignment, double-buffered VMEM
fit) — the sweep only reorders configs the analysis already admits.
"""

from __future__ import annotations

from repro.core import blocking, hw
from repro.core.blocking import BlockConfig, FlashBlockConfig, SSDBlockConfig

_BM = (128, 256, 512)
_BN = (128, 256, 512)
_BK = (128, 256, 512, 1024, 2048)
_BQ = (128, 256, 512)
_FBK = (128, 256, 512, 1024)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def matmul_candidates(
    m: int,
    n: int,
    k: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
    n_rhs: int = 1,
) -> list[BlockConfig]:
    """Feasible (bm, bn, bk) tiles for an (m, k) x (k, n) GEMM.

    The static default comes first so a tuner that times the list in
    order always has the fallback as its baseline. Tile dims larger than
    the (padded) problem are clamped, which collapses many grid points —
    duplicates are dropped.

    n_rhs=2 sizes the space for the fused dual-GEMM (gated) kernel:
    double B-side tiles and accumulators shrink the feasible set, and
    the default comes from the n_rhs-aware static chooser.
    """
    budget = int(chip.vmem_bytes * vmem_fraction)
    sub = chip.sublane(itemsize)
    lane = chip.lane

    default = blocking.choose_block_config(
        m, n, k, itemsize, chip=chip, vmem_fraction=vmem_fraction,
        n_rhs=n_rhs)
    out = [default]
    seen = {(default.bm, default.bn, default.bk)}
    for bm in _BM:
        bm = min(bm, _round_up(m, sub))
        for bn in _BN:
            bn = min(bn, _round_up(n, lane))
            for bk in _BK:
                bk = min(bk, _round_up(k, lane))
                cfg = BlockConfig(bm, bn, bk)
                key = (bm, bn, bk)
                if key in seen or \
                        cfg.vmem_bytes(itemsize, n_rhs=n_rhs) > budget:
                    continue
                seen.add(key)
                out.append(cfg)
    if max_candidates is not None:
        # Keep the default plus the highest-AI survivors: AI is the
        # paper's own proxy for which tiles can be compute-bound.
        rest = sorted(out[1:],
                      key=lambda c: -c.arithmetic_intensity(itemsize, n_rhs))
        out = out[:1] + rest[:max(0, max_candidates - 1)]
    return out


def gated_matmul_candidates(
    m: int,
    n: int,
    k: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
) -> list[BlockConfig]:
    """Feasible tiles for the dual-GEMM SwiGLU kernel ((m, k) staged
    against two (k, n) operands) — matmul_candidates with n_rhs=2."""
    return matmul_candidates(m, n, k, itemsize, chip=chip,
                             vmem_fraction=vmem_fraction,
                             max_candidates=max_candidates, n_rhs=2)


def flash_candidates(
    tq: int,
    tk: int,
    d: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
) -> list[FlashBlockConfig]:
    """Feasible (bq, bk) tiles for flash attention. The kernel requires
    block sizes to divide the (padded) sequence lengths, so candidates
    are filtered to divisors after clamping."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    default = blocking.choose_flash_config(tq, tk, d, itemsize, chip=chip)
    out = [default]
    seen = {(default.bq, default.bk)}
    for bq in _BQ:
        bq = min(bq, tq)
        if tq % bq:
            continue
        for bk in _FBK:
            bk = min(bk, tk)
            if tk % bk:
                continue
            cfg = FlashBlockConfig(bq, bk)
            if (bq, bk) in seen or cfg.vmem_bytes(d, itemsize) > budget:
                continue
            seen.add((bq, bk))
            out.append(cfg)
    if max_candidates is not None:
        out = out[:max(1, max_candidates)]
    return out


def flash_decode_candidates(
    tk: int,
    hkv: int,
    d: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
) -> list[FlashBlockConfig]:
    """Feasible K/V tiles for the q_len=1 decode kernel. bq is pinned to
    1 by construction, so the space is one-dimensional: bk divisors of
    the cache depth whose (bk, hkv, d) K and V blocks fit VMEM. Larger
    bk deepens the DMA pipeline but coarsens the prefix clamp (a
    near-empty cache still streams one full block), which is exactly
    the trade the timer should settle."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    default = blocking.choose_decode_config(tk, hkv, d, itemsize, chip=chip)
    out = [default]
    seen = {default.bk}
    for bk in _FBK:
        bk = min(bk, tk)
        if tk % bk or bk in seen:
            continue
        if blocking.decode_vmem_bytes(bk, hkv, d, itemsize) > budget:
            continue
        seen.add(bk)
        out.append(FlashBlockConfig(1, bk))
    if max_candidates is not None:
        out = out[:max(1, max_candidates)]
    return out


def flash_decode_paged_candidates(
    page_size: int,
    d: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
) -> list[FlashBlockConfig]:
    """Feasible K/V tiles for the PAGED decode kernel, keyed by
    (page_size, head_dim): the grid streams one pool page per step, so
    bk must divide the page — the space is the divisor lattice of
    page_size, not of the cache depth. The whole-page default comes
    first (fewest grid steps per page); smaller sub-tiles trade grid
    overhead for a finer prefix skip on the slot's final page."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    default = FlashBlockConfig(1, page_size)
    out = [default]
    seen = {page_size}
    for bk in sorted({min(b, page_size) for b in (16, 32, 64) + _FBK},
                     reverse=True):
        if page_size % bk or bk in seen:
            continue
        cfg = FlashBlockConfig(1, bk)
        if cfg.vmem_bytes(d, itemsize) > budget:
            continue
        seen.add(bk)
        out.append(cfg)
    if max_candidates is not None:
        out = out[:max(1, max_candidates)]
    return out


def _halving_divisors(x: int, floor: int) -> list[int]:
    out = [x]
    while x % 2 == 0 and x // 2 >= floor:
        x //= 2
        out.append(x)
    return out


def ssd_candidates(
    chunk: int,
    p: int,
    n: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
) -> list[SSDBlockConfig]:
    """Feasible (q, bp) execution tiles for the SSD intra-chunk kernel.

    Chunking is exact (DESIGN §6: the dual form is a blocked matmul
    along time), so the execution chunk q may be ANY divisor of the
    model chunk without changing the output — smaller q shrinks the
    quadratic (q, q) decay mask and CB score block quadratically at the
    cost of more inter-chunk scan steps; bp tiles the head dim P for
    VMEM headroom. The static chooser's pick comes first as the
    baseline; the rest is the halving-divisor lattice under the
    double-buffered VMEM budget."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    default = blocking.choose_ssd_config(
        chunk, p, n, itemsize, chip=chip, vmem_fraction=vmem_fraction)
    out = [default]
    seen = {(default.q, default.bp)}
    for q in _halving_divisors(chunk, 8):
        for bp in _halving_divisors(p, 8):
            cfg = SSDBlockConfig(q, bp)
            if (q, bp) in seen or cfg.vmem_bytes(n, itemsize) > budget:
                continue
            seen.add((q, bp))
            out.append(cfg)
    if max_candidates is not None:
        out = out[:max(1, max_candidates)]
    return out


def flash_bwd_candidates(
    tq: int,
    tk: int,
    d: int,
    itemsize: int,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = 0.5,
    max_candidates: int | None = None,
) -> list[FlashBlockConfig]:
    """Feasible (bq, bk) tiles for the two-sweep flash backward. Same
    divisor lattice as the forward, but the working set is heavier: the
    dK/dV sweep double-buffers q AND do tiles against the k/v residents
    and carries two f32 (bk, d) accumulators, so the VMEM filter adds
    those terms on top of the forward model."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    out = []
    seen = set()
    for cfg in flash_candidates(tq, tk, d, itemsize, chip=chip,
                                vmem_fraction=1.0):
        extra = (cfg.bq * d * itemsize * 2      # do stream, double-buffered
                 + 2 * cfg.bk * d * 4           # dk/dv f32 accumulators
                 + 4 * cfg.bq * 4)              # lse + delta rows
        if (cfg.bq, cfg.bk) in seen or \
                cfg.vmem_bytes(d, itemsize) + extra > budget:
            continue
        seen.add((cfg.bq, cfg.bk))
        out.append(cfg)
    if not out:
        out = [blocking.choose_flash_config(tq, tk, d, itemsize, chip=chip)]
    if max_candidates is not None:
        out = out[:max(1, max_candidates)]
    return out
