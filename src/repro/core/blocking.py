"""Tile-size selection for the blocked GEMM — the paper's shared-memory
sizing argument ("2 * 16 * 16 * 8 B = 4 KB <= 48 KB") redone for the TPU
memory hierarchy.

On the GPU the block size trades shared-memory footprint against
occupancy; on TPU it trades VMEM footprint against DMA pipeline depth
and MXU alignment. The constraints implemented here:

  * every tile dim is a multiple of the MXU edge (128) where possible,
    and at least the (sublane, lane) minimum for the dtype;
  * A-tile + B-tile (double-buffered) + f32 accumulator must fit a VMEM
    budget (VMEM_FRACTION of VMEM, leaving room for Mosaic);
  * maximise arithmetic intensity  AI = 2*bm*bn*bk / (bm*bk + bk*bn + bm*bn)
    which is what makes the kernel compute-bound (paper claim C2).

Also provides the HBM-traffic model used by the Fig.-8 reproduction:
tiled GEMM reads A ceil(N/bn) times and B ceil(M/bm) times, which is the
paper's reuse argument in byte form.
"""

from __future__ import annotations

import dataclasses
import math

from repro.core import hw

#: Share of a chip's VMEM one kernel's working set may take. The tile
#: choosers size against it, and the kernels compile under a scoped VMEM
#: limit of exactly this many bytes (kernels.compiler_params).
VMEM_FRACTION = 0.5


def vmem_budget(chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> int:
    return int(chip.vmem_bytes * VMEM_FRACTION)


@dataclasses.dataclass(frozen=True)
class BlockConfig:
    bm: int
    bn: int
    bk: int

    def vmem_bytes(self, itemsize: int, double_buffer: bool = True,
                   n_rhs: int = 1) -> int:
        """Working set of the tiled kernel. n_rhs > 1 models the fused
        dual-GEMM variants (kernels.matmul.gated_matmul_tiled): one A
        tile staged against n_rhs B operands, one accumulator each."""
        mult = 2 if double_buffer else 1
        tiles = (self.bm * self.bk
                 + n_rhs * self.bk * self.bn) * itemsize * mult
        acc = n_rhs * self.bm * self.bn * 4  # f32 accumulator scratch
        return tiles + acc

    def arithmetic_intensity(self, itemsize: int, n_rhs: int = 1) -> float:
        flops = 2.0 * n_rhs * self.bm * self.bn * self.bk
        bytes_moved = (self.bm * self.bk
                       + n_rhs * self.bk * self.bn) * itemsize
        return flops / bytes_moved


@dataclasses.dataclass(frozen=True)
class FlashBlockConfig:
    """Tile sizes for the flash-attention kernel: (bq, d) query tiles
    resident in VMEM, (bk, d) key/value tiles streamed through."""
    bq: int
    bk: int

    def vmem_bytes(self, d: int, itemsize: int,
                   double_buffer: bool = True) -> int:
        mult = 2 if double_buffer else 1
        tiles = (self.bq * d + 2 * self.bk * d) * itemsize * mult
        # f32 scratch: output accumulator + running max + denominator.
        acc = (self.bq * d + 2 * self.bq * 128) * 4
        return tiles + acc


def choose_flash_config(
    tq: int,
    tk: int,
    d: int,
    itemsize: int = 2,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
) -> FlashBlockConfig:
    """Default (bq, bk) for flash attention — the kernel's historical
    constants, clamped to the sequence lengths. The autotuner
    (repro.tuning) sweeps alternatives and caches per-shape winners."""
    return FlashBlockConfig(bq=min(256, tq), bk=min(512, tk))


def decode_vmem_bytes(bk: int, hkv: int, d: int, itemsize: int) -> int:
    """Working set of the decode kernel: a (bk, hkv, d) K block and a V
    block, double-buffered. The per-head query, output and f32 scratch
    rows are a few KB and are left out."""
    return 2 * 2 * bk * hkv * d * itemsize


def choose_decode_config(
    tk: int,
    hkv: int,
    d: int,
    itemsize: int = 2,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
) -> FlashBlockConfig:
    """Default K/V tile for the q_len=1 decode kernel. One grid step
    streams bk cache rows of every kv head (bk * hkv * d elements per
    side), so bk starts at min(512, tk) and halves while K and V,
    double-buffered, exceed the VMEM budget; it stays a divisor of tk.
    512 keeps the DMA pipeline deep while the prefix clamp bounds the
    rows fetched past a slot's depth to one block."""
    bk = min(512, tk)
    while (decode_vmem_bytes(bk, hkv, d, itemsize) > vmem_budget(chip)
           and bk % 2 == 0 and tk % (bk // 2) == 0):
        bk //= 2
    return FlashBlockConfig(bq=1, bk=bk)


@dataclasses.dataclass(frozen=True)
class SSDBlockConfig:
    """Tile sizes for the SSD intra-chunk kernel: `q` is the execution
    chunk along time (any divisor of the model chunk computes the same
    function — SSD chunking is exact), `bp` tiles the head dim (each
    p-tile recomputes the (q, q) decay/score matrices)."""
    q: int
    bp: int

    def vmem_bytes(self, n: int, itemsize: int,
                   double_buffer: bool = True) -> int:
        mult = 2 if double_buffer else 1
        # streamed per grid cell: x (q, bp), a (q,), b/c (q, n)
        tiles = (self.q * self.bp + self.q + 2 * self.q * n) * itemsize * mult
        # f32 scratch: decay mask + score matrix (q, q) each, y (q, bp),
        # chunk state (n, bp)
        acc = (2 * self.q * self.q + self.q * self.bp + n * self.bp) * 4
        return tiles + acc


def choose_ssd_config(
    chunk: int,
    p: int,
    n: int,
    itemsize: int = 4,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = VMEM_FRACTION,
) -> SSDBlockConfig:
    """Default (q, bp) for the SSD kernel: run at the model's configured
    chunk with the full head dim, halving the time tile while the
    working set (dominated by the two (q, q) f32 matrices) exceeds the
    VMEM budget. The autotuner (tuning.tune_ssd) sweeps alternatives."""
    budget = int(chip.vmem_bytes * vmem_fraction)
    q = chunk
    cfg = SSDBlockConfig(q=q, bp=p)
    while cfg.vmem_bytes(n, itemsize) > budget and q % 2 == 0 and q > 8:
        q //= 2
        cfg = SSDBlockConfig(q=q, bp=p)
    return cfg


def ssd_traffic_bytes(
    l: int, h: int, p: int, n: int, cfg: SSDBlockConfig, itemsize: int
) -> int:
    """Bytes moved HBM<->VMEM by the Pallas SSD composition for one
    (batch, layer): the kernel streams x/a and the head-broadcast b/c
    once per head-tile column (`ceil(p/bp)` — b/c re-stream when the
    head dim is tiled), writes the chunk-diagonal y and the per-chunk
    states in f32, and the tiny rank-N inter-chunk pass reads the states
    + y_diag and writes y. The (q, q) decay mask and CB score matrices
    are VMEM-resident and never exist in HBM — the term this model
    conspicuously lacks, mirroring flash_traffic_bytes."""
    nc = math.ceil(l / cfg.q)
    n_p = math.ceil(p / cfg.bp)
    x_bytes = l * h * p * itemsize
    a_bytes = l * h * itemsize * n_p
    bc_bytes = 2 * l * h * n * itemsize * n_p
    y_diag = l * h * p * 4                      # kernel out, f32
    states = nc * h * n * p * 4                 # kernel out, f32
    # inter-chunk jnp pass: read states + y_diag + c, write y
    inter = states + y_diag + l * h * n * itemsize + l * h * p * itemsize
    return x_bytes + a_bytes + bc_bytes + y_diag + states + inter


def ssd_unfused_traffic_bytes(
    l: int, h: int, p: int, n: int, chunk: int, itemsize: int
) -> int:
    """The XLA lowering of the chunked composition (kernels.ssd
    ssd_chunked): the per-chunk (Q, Q) f32 decay mask is written + read
    and the CB score matrix is written + read twice (once masked for
    y_diag, once raw) — four quadratic f32 trips per (chunk, head),
    `4 * Q*Q * 4` bytes, exactly the flash_unfused_traffic_bytes
    pattern along the time axis — plus the linear operand streams, the
    f32 decay vectors and the per-chunk state round trip."""
    nc = math.ceil(l / chunk)
    operands = (l * h * p + l * h + 2 * l * h * n) * itemsize
    s_bytes = nc * h * 4 * chunk * chunk * 4    # ldec + cb round trips
    decays = 3 * l * h * 4                      # a_cum, decay_to_end, ...
    states = 2 * nc * h * n * p * 4             # written, re-read by scan
    y_bytes = 2 * l * h * p * 4 + l * h * p * itemsize  # y_diag+y_off+y
    return operands + s_bytes + decays + states + y_bytes


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _round_down_pow2_mult(x: int, m: int) -> int:
    """Largest multiple of m that is <= x (at least m)."""
    return max(m, (x // m) * m)


def choose_block_config(
    m: int,
    n: int,
    k: int,
    itemsize: int = 2,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    vmem_fraction: float = VMEM_FRACTION,
    n_rhs: int = 1,
) -> BlockConfig:
    """Pick (bm, bn, bk) for an (m, k) x (k, n) GEMM.

    Strategy: start from MXU-aligned 512x512x512-ish tiles, clamp to the
    problem, then shrink the largest dim until the double-buffered
    working set fits the VMEM budget. bk is kept >= 512 when possible so
    the k-grid is short (fewer accumulator passes), mirroring the
    paper's 'one long k loop inside the block' structure.

    n_rhs=2 sizes tiles for the fused dual-GEMM (gated) kernel, whose
    working set carries two B tiles and two accumulators per A tile.
    """
    budget = int(chip.vmem_bytes * vmem_fraction)
    lane = chip.lane
    sub = chip.sublane(itemsize)

    bm = min(_round_up(m, sub), 512)
    bn = min(_round_up(n, lane), 512)
    bk = min(_round_up(k, lane), 2048)
    bm = _round_down_pow2_mult(bm, sub)
    bn = _round_down_pow2_mult(bn, lane)
    bk = _round_down_pow2_mult(bk, lane)

    cfg = BlockConfig(bm, bn, bk)
    while cfg.vmem_bytes(itemsize, n_rhs=n_rhs) > budget:
        # Shrink the dim that frees the most bytes while hurting AI least:
        # prefer shrinking bk first below 512, then the larger of bm/bn.
        if cfg.bk > 512:
            cfg = BlockConfig(cfg.bm, cfg.bn, _round_down_pow2_mult(cfg.bk // 2, lane))
        elif cfg.bm >= cfg.bn and cfg.bm > sub:
            cfg = BlockConfig(_round_down_pow2_mult(cfg.bm // 2, sub), cfg.bn, cfg.bk)
        elif cfg.bn > lane:
            cfg = BlockConfig(cfg.bm, _round_down_pow2_mult(cfg.bn // 2, lane), cfg.bk)
        elif cfg.bk > lane:
            cfg = BlockConfig(cfg.bm, cfg.bn, _round_down_pow2_mult(cfg.bk // 2, lane))
        else:
            break  # minimum tile; give up shrinking
    return cfg


def hbm_traffic_bytes(
    m: int, n: int, k: int, cfg: BlockConfig, itemsize: int
) -> int:
    """Bytes moved HBM->VMEM by the tiled kernel (the Fig.-8 model).

    A is streamed once per N-block column, B once per M-block row, C is
    written once. This is exactly the paper's reuse argument: blocking
    divides global-memory traffic by the block edge.
    """
    n_m = math.ceil(m / cfg.bm)
    n_n = math.ceil(n / cfg.bn)
    a_bytes = m * k * itemsize * n_n
    b_bytes = k * n * itemsize * n_m
    c_bytes = m * n * itemsize
    return a_bytes + b_bytes + c_bytes


def gated_traffic_bytes(
    m: int, n: int, k: int, cfg: BlockConfig, itemsize: int
) -> int:
    """Bytes moved HBM->VMEM by the fused dual-GEMM (gated) kernel.

    One A stream feeds BOTH weight operands (A read once per N-block
    column, exactly as in the single-GEMM model), each of the two B
    operands is read once per M-block row, and only the final gated
    product is written — the two (m, n) intermediates of the unfused
    composition never touch HBM.
    """
    n_m = math.ceil(m / cfg.bm)
    n_n = math.ceil(n / cfg.bn)
    a_bytes = m * k * itemsize * n_n
    b_bytes = 2 * k * n * itemsize * n_m
    c_bytes = m * n * itemsize
    return a_bytes + b_bytes + c_bytes


def quant_traffic_bytes(
    m: int, n: int, k: int, cfg: BlockConfig, itemsize: int,
    w_itemsize: int = 1, scale_itemsize: int = 4,
) -> int:
    """Bytes moved HBM->VMEM by the int8-weight tiled kernel
    (kernels.matmul.matmul_q_tiled).

    Same reuse structure as hbm_traffic_bytes, but the B operand is
    stored at `w_itemsize` (1 for int8) and a (1, N) per-channel scale
    row rides along once per M-block row — the whole point of the
    quantized path is that the weight stream shrinks itemsize/w_itemsize
    x while A, C and the arithmetic stay full precision.
    """
    n_m = math.ceil(m / cfg.bm)
    n_n = math.ceil(n / cfg.bn)
    a_bytes = m * k * itemsize * n_n
    b_bytes = k * n * w_itemsize * n_m
    s_bytes = n * scale_itemsize * n_m
    c_bytes = m * n * itemsize
    return a_bytes + b_bytes + s_bytes + c_bytes


def naive_traffic_bytes(m: int, n: int, k: int, itemsize: int) -> int:
    """Traffic model for the hierarchy-blind kernel (paper Listing 3).

    Each output element streams a full row of A and column of B with no
    cross-thread reuse: A read n times, B read m times.
    """
    return (m * k * n + k * n * m + m * n) * itemsize


def flash_traffic_bytes(
    tq: int, tk: int, d: int, cfg: FlashBlockConfig, itemsize: int
) -> int:
    """Bytes moved HBM<->VMEM by the fused flash-attention forward, per
    (batch x head) slice — multiply by B*H for a layer.

    The q grid axis is outer and the kv axis inner, and the Q block index
    is constant across consecutive kv steps, so Mosaic keeps each Q tile
    resident: Q and O move once. K and V re-stream once per Q block row.
    The S and P matrices never exist in HBM — that is the whole point,
    and the term this model conspicuously lacks."""
    n_q = math.ceil(tq / cfg.bq)
    q_bytes = tq * d * itemsize
    kv_bytes = 2 * tk * d * itemsize * n_q
    o_bytes = tq * d * itemsize
    return q_bytes + kv_bytes + o_bytes


def flash_unfused_traffic_bytes(tq: int, tk: int, d: int,
                                itemsize: int) -> int:
    """The materialised-softmax baseline: one pass writes S = QK^T, a
    second normalises it to P, a third contracts with V. Operands move
    once (XLA fuses the row softmax into one read-modify-write), but the
    (tq, tk) score matrix makes four f32 HBM trips: S written + read,
    P written + read."""
    qkv_bytes = (tq + 2 * tk) * d * itemsize
    s_bytes = 4 * tq * tk * 4
    o_bytes = tq * d * itemsize
    return qkv_bytes + s_bytes + o_bytes


def decode_traffic_bytes(pos: int, tk: int, d: int, cfg: FlashBlockConfig,
                         itemsize: int, *, h: int = 1,
                         hkv: int = 1) -> int:
    """Fused decode-step traffic per slot: the h query and output rows
    bracket a K/V stream of each kv head's valid cache prefix, read
    once (every query head of a kv head's group is served from the same
    block) and rounded up to whole bk blocks. The kernel's index map
    clamps to the block holding `pos`, so blocks past the write head are
    never DMA'd: a depth-4096 cache at pos=127 moves ceil(128/bk)*bk
    rows per kv head, not 4096."""
    n_blocks = math.ceil((pos + 1) / cfg.bk)
    kv_bytes = 2 * hkv * n_blocks * cfg.bk * d * itemsize
    return kv_bytes + 2 * h * d * itemsize


def decode_unfused_traffic_bytes(pos: int, tk: int, d: int,
                                 itemsize: int, *, h: int = 1,
                                 hkv: int = 1) -> int:
    """The masked-dense decode baseline (chunked/XLA over the whole
    cache buffer), per slot: padding cannot be skipped because the mask
    is data, so all tk cache rows of every kv head stream, plus each
    query head's (1, tk) f32 score row round trips. `pos` is accepted
    for signature symmetry — the baseline's traffic does not depend on
    it, which is exactly the problem."""
    del pos
    kv_bytes = 2 * hkv * tk * d * itemsize
    s_bytes = h * 4 * tk * 4
    return kv_bytes + s_bytes + 2 * h * d * itemsize


def flash_bwd_traffic_bytes(
    tq: int, tk: int, d: int, cfg: FlashBlockConfig, itemsize: int
) -> int:
    """Recompute-style flash backward, per (batch x head): two sweeps,
    neither of which ever reads or writes the (tq, tk) matrices.

    Sweep 1 (dK/dV, kv-outer grid): K/V move once, the q-side streams
    (q, do + the f32 lse/delta rows) re-read per kv block row, dK/dV
    written once in f32. Sweep 2 (dQ, q-outer grid): mirror image.
    delta = rowsum(do * o) is a pre-pass in XLA: o and do read once more.
    """
    n_q = math.ceil(tq / cfg.bq)
    n_k = math.ceil(tk / cfg.bk)
    rows = 2 * tq * 4                          # lse + delta, f32
    q_stream = 2 * tq * d * itemsize + rows    # q + do + rows
    sweep1 = 2 * tk * d * itemsize + n_k * q_stream + 2 * tk * d * 4
    sweep2 = q_stream + n_q * 2 * tk * d * itemsize + tq * d * 4
    delta_pass = 2 * tq * d * itemsize + tq * 4
    return sweep1 + sweep2 + delta_pass


def flash_bwd_stored_traffic_bytes(tq: int, tk: int, d: int,
                                   itemsize: int) -> int:
    """Stored-S attention backward: the classic formulation keeps the
    (tq, tk) probability matrix from the forward and replays it. P is
    read twice (dV and dS), dS is written then re-read for dQ/dK — four
    f32 trips of the quadratic matrix, dwarfing the linear operands."""
    operands = (3 * tq + 2 * tk) * d * itemsize   # q, do, o, k, v
    s_bytes = 4 * tq * tk * 4
    outs = (tq + 2 * tk) * d * 4                  # dq, dk, dv in f32
    return operands + s_bytes + outs + 2 * tq * 4


def gemm_time_model(
    m: int,
    n: int,
    k: int,
    itemsize: int,
    cfg: BlockConfig | None,
    chip: hw.ChipSpec = hw.DEFAULT_CHIP,
) -> dict:
    """Roofline time estimate for one GEMM on `chip`.

    cfg=None means the naive traffic model. Returns both terms plus the
    bound classification — the machinery behind the modeled Table-2
    reproduction.
    """
    flops = 2.0 * m * n * k
    if cfg is None:
        traffic = naive_traffic_bytes(m, n, k, itemsize)
    else:
        traffic = hbm_traffic_bytes(m, n, k, cfg, itemsize)
    t_compute = flops / chip.peak_flops(itemsize)
    t_memory = traffic / chip.hbm_bw
    return {
        "flops": flops,
        "bytes": traffic,
        "t_compute": t_compute,
        "t_memory": t_memory,
        "t_total": max(t_compute, t_memory),
        "bound": "compute" if t_compute >= t_memory else "memory",
        "arithmetic_intensity": flops / traffic,
    }
