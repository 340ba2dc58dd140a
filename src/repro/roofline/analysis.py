"""Roofline terms per (arch x shape x mesh) from a compiled dry-run.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = ICI_bytes_per_device / link_bw

HLO_FLOPs / bytes / collective bytes come from roofline.hlo (the
while-loop-aware static analyzer; compiled.cost_analysis() undercounts
scanned stacks — verified, see EXPERIMENTS §Dry-run). MODEL_FLOPS is
the 6·N·D / 2·N·D convention (N = active params for MoE), so the
MODEL_FLOPS/HLO_FLOPs ratio exposes remat and redundant compute.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional

import jax

from repro.core import blocking, hw
from repro.roofline import hlo as H


def count_params(cfg) -> tuple[int, int]:
    """(total, active) parameter counts via eval_shape (no allocation)."""
    from repro.models import model as M

    shapes = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    total = 0
    expert_total = 0
    flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for path, leaf in flat:
        n = 1
        for d in leaf.shape:
            n *= d
        total += n
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if "moe/w_" in pstr:
            expert_total += n
    active = total
    if cfg.moe is not None and expert_total:
        frac = cfg.moe.top_k / cfg.moe.n_experts
        active = total - expert_total + int(expert_total * frac)
    return total, active


def model_flops(cfg, cell, *, kind: str) -> float:
    """6·N·D (train) / 2·N·D (prefill) / 2·N·B (one decode step),
    N = active params (MoE), D = tokens processed. Attention flops
    excluded by convention (noted in EXPERIMENTS)."""
    _, active = count_params(cfg)
    if kind == "train":
        tokens = cell.global_batch * cell.seq_len
        return 6.0 * active * tokens
    if kind == "prefill":
        tokens = cell.global_batch * cell.seq_len
        return 2.0 * active * tokens
    return 2.0 * active * cell.global_batch         # decode: one token/seq


# ----------------------------------------------------------------------
# Fused-kernel HBM accounting (EXPERIMENTS §HBM-traffic accounting)
#
# The fused-epilogue / dual-GEMM wins are bandwidth wins, so they are
# assertable on this CPU-only container from the same static traffic
# models the Fig.-8 reproduction uses (core.blocking) — no TPU needed.
# ----------------------------------------------------------------------

def epilogue_traffic_bytes(m: int, n: int, k: int, itemsize: int,
                           epilogue: str, cfg=None,
                           chip: hw.ChipSpec = hw.DEFAULT_CHIP,
                           fused: bool = True) -> int:
    """HBM bytes for one GEMM + epilogue (bias/activation/residual).

    Unfused, the epilogue is a separate elementwise pass: the (m, n)
    GEMM result is written, re-read together with the epilogue operand,
    and written again. Fused, the epilogue runs in the kernel's flush on
    the VMEM accumulator: only the operand read is added — the (m, n)
    intermediate never round-trips, saving 2*m*n*itemsize bytes.
    """
    if cfg is None:
        cfg = blocking.choose_block_config(m, n, k, itemsize, chip=chip)
    total = blocking.hbm_traffic_bytes(m, n, k, cfg, itemsize)
    if epilogue == "none":
        return total
    operand = m * n * itemsize if epilogue == "residual" else n * itemsize
    total += operand
    if not fused:
        total += 2 * m * n * itemsize   # write + re-read the intermediate
    return total


def gated_mlp_traffic(m: int, d_model: int, d_ff: int, itemsize: int,
                      *, fused: bool,
                      chip: hw.ChipSpec = hw.DEFAULT_CHIP,
                      cfg_hidden=None, cfg_down=None) -> dict:
    """HBM bytes for one SwiGLU MLP call, fused vs unfused.

    Unfused (the XLA composition): two tiled GEMMs each write their
    (m, d_ff) result, and the gate product reads both and writes a
    third — three full (m, d_ff) round-trips beyond the fused path.
    Fused (kernels.matmul.gated_matmul_tiled): one A stream feeds both
    weight operands and only the gated product is written
    (core.blocking.gated_traffic_bytes). The down-projection GEMM is
    identical in both and included so the ratio is per MLP *call*.
    """
    if cfg_hidden is None:
        cfg_hidden = blocking.choose_block_config(
            m, d_ff, d_model, itemsize, chip=chip, n_rhs=2 if fused else 1)
    if cfg_down is None:
        cfg_down = blocking.choose_block_config(
            m, d_model, d_ff, itemsize, chip=chip)
    if fused:
        hidden = blocking.gated_traffic_bytes(
            m, d_ff, d_model, cfg_hidden, itemsize)
    else:
        one = blocking.hbm_traffic_bytes(m, d_ff, d_model, cfg_hidden,
                                         itemsize)
        ew = 3 * m * d_ff * itemsize    # read gate, read up, write product
        hidden = 2 * one + ew
    down = blocking.hbm_traffic_bytes(m, d_model, d_ff, cfg_down, itemsize)
    return {
        "hidden_bytes": hidden,
        "down_bytes": down,
        "total_bytes": hidden + down,
        "cfg_hidden": cfg_hidden,
        "cfg_down": cfg_down,
    }


def gated_mlp_savings(m: int, d_model: int, d_ff: int,
                      itemsize: int,
                      chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> dict:
    """Fractional HBM-byte saving of the fused SwiGLU MLP — the number
    benchmarks/bench_fused_epilogue.py asserts (>= 40% at its shape)."""
    unfused = gated_mlp_traffic(m, d_model, d_ff, itemsize, fused=False,
                                chip=chip)
    fused = gated_mlp_traffic(m, d_model, d_ff, itemsize, fused=True,
                              chip=chip)
    saved = 1.0 - fused["total_bytes"] / unfused["total_bytes"]
    return {"unfused_bytes": unfused["total_bytes"],
            "fused_bytes": fused["total_bytes"],
            "saved_frac": saved,
            "unfused": unfused, "fused": fused}


def quant_gemm_traffic(m: int, n: int, k: int, itemsize: int,
                       *, quant: bool,
                       chip: hw.ChipSpec = hw.DEFAULT_CHIP,
                       cfg=None) -> int:
    """HBM bytes for one dense-layer GEMM, full-width vs int8 weights.

    Quantized, the weight stream is 1 byte/element plus a (1, N) f32
    scale row per M-block row (core.blocking.quant_traffic_bytes);
    activations, output and the f32 accumulation are untouched — the
    reduction is pure weight-side bandwidth, which is why it is
    assertable from the static model on a CPU-only container exactly
    like the fused-epilogue wins.
    """
    if cfg is None:
        cfg = blocking.choose_block_config(m, n, k, itemsize, chip=chip)
    if quant:
        return blocking.quant_traffic_bytes(m, n, k, cfg, itemsize)
    return blocking.hbm_traffic_bytes(m, n, k, cfg, itemsize)


def quant_gemm_savings(m: int, n: int, k: int, itemsize: int,
                       chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> dict:
    """Fractional HBM-byte saving of the int8-weight GEMM — the number
    benchmarks/bench_quant_matmul.py asserts. The same BlockConfig is
    used for both sides (apples-to-apples reuse structure); weight-bound
    shapes (decode: small m, big n*k) approach the full itemsize/1
    reduction, activation-bound shapes see less."""
    cfg = blocking.choose_block_config(m, n, k, itemsize, chip=chip)
    full = quant_gemm_traffic(m, n, k, itemsize, quant=False, chip=chip,
                              cfg=cfg)
    quant = quant_gemm_traffic(m, n, k, itemsize, quant=True, chip=chip,
                               cfg=cfg)
    return {"full_bytes": full,
            "quant_bytes": quant,
            "saved_frac": 1.0 - quant / full,
            "weight_bytes_full": k * n * itemsize,
            "weight_bytes_quant": k * n * 1,
            "cfg": cfg}


def dense_q_layer_savings(m: int, d_model: int, d_ff: int, itemsize: int,
                          chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> dict:
    """Whole-MLP view of the int8 win, against the model's REAL
    before state: unquantized SwiGLU runs the fused dual-GEMM kernel
    (one A stream feeds both weights — blocking.gated_traffic_bytes),
    while the quantized path decomposes into two dense_q GEMMs
    (models.layers.gated_apply has no int8 dual-GEMM variant, so the A
    stream is paid twice) + the int8 down-projection. The weight-side
    shrink usually still wins, but decomposition claws some back —
    this is the honest before-to-after delta for Policy(quant="int8")."""
    cfg_hidden = blocking.choose_block_config(m, d_ff, d_model, itemsize,
                                              chip=chip, n_rhs=2)
    full = (blocking.gated_traffic_bytes(m, d_ff, d_model, cfg_hidden,
                                         itemsize)
            + quant_gemm_traffic(m, d_model, d_ff, itemsize, quant=False,
                                 chip=chip))
    quant = (2 * quant_gemm_traffic(m, d_ff, d_model, itemsize, quant=True,
                                    chip=chip)
             + quant_gemm_traffic(m, d_model, d_ff, itemsize, quant=True,
                                  chip=chip))
    return {"full_bytes": full, "quant_bytes": quant,
            "saved_frac": 1.0 - quant / full}


def attention_fwd_savings(tq: int, tk: int, d: int, itemsize: int,
                          cfg: blocking.FlashBlockConfig | None = None,
                          chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> dict:
    """Fractional HBM-byte saving of the fused flash forward over the
    materialised-softmax baseline, per (batch x head) slice. The win is
    the missing 4*tq*tk*4 S/P round trips, bought back by re-streaming
    K/V once per Q block row — net positive whenever tq*tk dwarfs the
    linear operand terms, i.e. every training shape."""
    if cfg is None:
        cfg = blocking.choose_flash_config(tq, tk, d, itemsize, chip=chip)
    fused = blocking.flash_traffic_bytes(tq, tk, d, cfg, itemsize)
    unfused = blocking.flash_unfused_traffic_bytes(tq, tk, d, itemsize)
    return {"fused_bytes": fused, "unfused_bytes": unfused,
            "saved_frac": 1.0 - fused / unfused, "cfg": cfg}


def decode_attention_savings(pos: int, tk: int, d: int, itemsize: int,
                             cfg: blocking.FlashBlockConfig | None = None,
                             chip: hw.ChipSpec = hw.DEFAULT_CHIP, *,
                             h: int = 1, hkv: int = 1) -> dict:
    """Fractional HBM-byte saving of the decode kernel over the masked
    dense scan, per slot of h query and hkv kv heads — the number
    benchmarks/bench_flash_attention.py asserts. Two independent terms:
    the prefix clamp (only ceil((pos+1)/bk)*bk of tk cache rows of each
    kv head stream, the dominant win early in a long-max-length cache)
    and the skipped (1, tk) f32 score-row round trips."""
    if cfg is None:
        cfg = blocking.choose_decode_config(tk, hkv, d, itemsize, chip=chip)
    fused = blocking.decode_traffic_bytes(pos, tk, d, cfg, itemsize,
                                          h=h, hkv=hkv)
    unfused = blocking.decode_unfused_traffic_bytes(pos, tk, d, itemsize,
                                                    h=h, hkv=hkv)
    return {"fused_bytes": fused, "unfused_bytes": unfused,
            "saved_frac": 1.0 - fused / unfused, "cfg": cfg}


def attention_bwd_savings(tq: int, tk: int, d: int, itemsize: int,
                          cfg: blocking.FlashBlockConfig | None = None,
                          chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> dict:
    """Fractional HBM-byte saving of the recompute-style flash backward
    over the stored-S formulation, per (batch x head). Recompute trades
    the four quadratic f32 trips (P read twice, dS written + re-read)
    for linear re-streams of the operands across both sweeps — the
    classic flash-attention bandwidth argument, backward edition."""
    if cfg is None:
        cfg = blocking.choose_flash_config(tq, tk, d, itemsize, chip=chip)
    fused = blocking.flash_bwd_traffic_bytes(tq, tk, d, cfg, itemsize)
    unfused = blocking.flash_bwd_stored_traffic_bytes(tq, tk, d, itemsize)
    return {"fused_bytes": fused, "unfused_bytes": unfused,
            "saved_frac": 1.0 - fused / unfused, "cfg": cfg}


def ssd_savings(l: int, h: int, p: int, n: int, chunk: int,
                itemsize: int = 4,
                cfg: blocking.SSDBlockConfig | None = None,
                chip: hw.ChipSpec = hw.DEFAULT_CHIP) -> dict:
    """Fractional HBM-byte saving of the fused SSD intra-chunk kernel
    over the XLA chunked lowering — the number
    benchmarks/bench_ssd.py asserts. The unfused composition
    materialises, per chunk and head, the (Q, Q) decay mask and CB
    score block in f32 (write + re-read apiece, the flash-attention
    story with Q = chunk); the fused kernel keeps both VMEM-resident,
    paying only the operand streams and the per-chunk state/diag
    outputs that feed the inter-chunk scan."""
    if cfg is None:
        cfg = blocking.choose_ssd_config(chunk, p, n, itemsize, chip=chip)
    fused = blocking.ssd_traffic_bytes(l, h, p, n, cfg, itemsize)
    unfused = blocking.ssd_unfused_traffic_bytes(l, h, p, n, chunk, itemsize)
    return {"fused_bytes": fused, "unfused_bytes": unfused,
            "saved_frac": 1.0 - fused / unfused, "cfg": cfg}


# ----------------------------------------------------------------------
# KV-cache traffic + capacity models (paged / quantized serving)
# ----------------------------------------------------------------------

def kv_decode_traffic_bytes(pos: int, heads: int, d: int, itemsize: int,
                            *, quant_kv: str = "off") -> int:
    """HBM bytes ONE decode step streams from the KV cache for one slot
    at depth `pos`, summed over K and V: (pos + 1) resident rows per
    side, each `heads * d` elements. quant_kv="int8" rows are 1
    byte/element plus a 4-byte f32 scale per (position, head) — the
    scale planes ride along with the pages, so they are charged here."""
    rows = 2 * (pos + 1) * heads
    if quant_kv == "int8":
        return rows * (d + 4)
    return rows * d * itemsize


def ssm_decode_state_bytes(heads: int, p: int, n: int) -> int:
    """HBM bytes ONE decode step streams for one slot's SSD recurrent
    state: the (H, P, N) f32 state is read and written back once,
    independent of position — the O(1)-state contrast to
    kv_decode_traffic_bytes' O(pos) growth that the serving benchmark's
    long_context rows assert."""
    return 2 * heads * p * n * 4


def kv_quant_savings(pos: int, heads: int, d: int, itemsize: int) -> dict:
    """Fractional KV-byte saving per decode step of int8 pages over
    full-width rows — the number benchmarks/bench_serving.py asserts
    (>= 40%). Decode attention is KV-bandwidth-bound (q is one row, the
    cache is thousands), so byte savings here are latency savings to
    first order: d=64 bf16 rows shrink 128 -> 68 bytes/(row, head)
    (46.9%), f32 rows 256 -> 68 (73.4%)."""
    full = kv_decode_traffic_bytes(pos, heads, d, itemsize)
    quant = kv_decode_traffic_bytes(pos, heads, d, itemsize,
                                    quant_kv="int8")
    return {"full_bytes": full, "quant_bytes": quant,
            "saved_frac": 1.0 - quant / full,
            "row_bytes_full": d * itemsize, "row_bytes_quant": d + 4}


def kv_capacity_model(pool_bytes: int, *, max_len: int, page_size: int,
                      heads: int, d: int, itemsize: int, prompt_len: int,
                      shared_prefix_len: int, gen: int,
                      quant_kv: str = "off") -> dict:
    """Concurrent-slot capacity of one layer's KV memory under three
    layouts at EQUAL byte budget — the static model behind the paged
    engine's >= 2x admission win on prefix-heavy traces.

    * dense: every slot pins max_len rows whether used or not.
    * paged: slots pin ceil((prompt+gen)/page_size) pages; the
      shared-prefix pages are paid once pool-wide.
    * paged + int8: same page count but each page is ~itemsize/1
      smaller, so the same bytes buy proportionally more pages.
    """
    row_full = 2 * heads * d * itemsize          # K + V, one position
    row = 2 * heads * (d + 4) if quant_kv == "int8" else row_full
    dense_slots = pool_bytes // (max_len * row_full)
    n_pages = pool_bytes // (page_size * row)
    shared_pages = shared_prefix_len // page_size   # full pages only
    per_req = -(-(prompt_len + gen) // page_size) - shared_pages
    paged_slots = max(0, (n_pages - shared_pages) // max(per_req, 1))
    return {"dense_slots": int(dense_slots),
            "paged_slots": int(paged_slots),
            "n_pages": int(n_pages),
            "shared_pages": int(shared_pages),
            "pages_per_request": int(per_req),
            "capacity_ratio": paged_slots / max(dense_slots, 1)}


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    kind: str
    n_devices: int
    hlo_flops_per_device: float
    hbm_bytes_per_device: float
    ici_bytes_per_device: float
    collectives: dict
    t_compute: float
    t_memory: float
    t_collective: float
    bound: str
    model_flops_total: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * devices)
    mfu_roofline: float          # useful-compute-time / dominant term
    memory_analysis: dict
    note: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    def summary_line(self) -> str:
        return (f"{self.arch:16s} {self.shape:12s} {self.mesh:10s} "
                f"tc={self.t_compute*1e3:9.3f}ms tm={self.t_memory*1e3:9.3f}ms "
                f"tcoll={self.t_collective*1e3:9.3f}ms bound={self.bound:10s} "
                f"useful={self.useful_ratio:6.3f} mfu*={self.mfu_roofline:6.3f}")


def build_report(
    cfg, cell, *, kind: str, mesh_name: str, n_devices: int,
    hlo_text: str, memory_analysis=None, chip: hw.ChipSpec = hw.DEFAULT_CHIP,
    note: str = "",
) -> RooflineReport:
    costs = H.analyze(hlo_text, n_devices)
    peak = chip.peak_flops_bf16
    t_c = costs.flops / peak
    t_m = costs.hbm_bytes / chip.hbm_bw
    t_coll = costs.ici_bytes / chip.ici_link_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_coll}
    bound = max(terms, key=terms.get)
    mf = model_flops(cfg, cell, kind=kind)
    useful = mf / max(costs.flops * n_devices, 1.0)
    t_useful = mf / n_devices / peak
    mfu = t_useful / max(max(terms.values()), 1e-30)

    ma = {}
    if memory_analysis is not None:
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes"):
            ma[k] = getattr(memory_analysis, k, None)

    return RooflineReport(
        arch=cfg.name, shape=cell.name, mesh=mesh_name, kind=kind,
        n_devices=n_devices,
        hlo_flops_per_device=costs.flops,
        hbm_bytes_per_device=costs.hbm_bytes,
        ici_bytes_per_device=costs.ici_bytes,
        collectives=costs.collective_summary(),
        t_compute=t_c, t_memory=t_m, t_collective=t_coll, bound=bound,
        model_flops_total=mf, useful_ratio=useful, mfu_roofline=mfu,
        memory_analysis=ma, note=note,
    )


def save_report(report: RooflineReport, path: str) -> None:
    with open(path, "w") as f:
        json.dump(report.to_json(), f, indent=2)
