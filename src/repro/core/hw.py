"""Hardware model for the roofline / blocking analysis.

TPU v5e is the chip this repository runs on. All sizing decisions (the
paper's shared-memory-budget argument redone for VMEM) and all roofline
terms are computed against these specs. Measured devices are looked up
by the `device_kind` JAX reports (`chip_for`); a device that is not in
that table is an error, never a default. The paper's Tesla C2050/C1060
stay as named targets for its modeled byte tables only.

TPU v5e peaks (Google Cloud documentation, "TPU v5e",
cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16 and 393 TOP/s int8 per
chip, 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect
(4 links, so ~50 GB/s per link per direction); 128 MiB VMEM per core.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    # Peak matmul throughput per chip, FLOP/s, by dtype.
    peak_flops_bf16: float
    peak_flops_f32: float
    # HBM bandwidth, bytes/s.
    hbm_bw: float
    hbm_bytes: int
    # VMEM (scratchpad) per core — the paper's "shared memory" analogue.
    vmem_bytes: int
    # ICI: per-link bandwidth (bytes/s, one direction) and links per chip
    # on a 2D torus (v5e: 4 neighbours × ~50 GB/s).
    ici_link_bw: float
    ici_links: int
    # MXU native tile (systolic array edge).
    mxu_dim: int
    # Minimum sublane×lane tile per dtype ((8,128) f32, (16,128) bf16, ...)
    lane: int = 128

    def sublane(self, itemsize: int) -> int:
        return {4: 8, 2: 16, 1: 32}.get(itemsize, 8)

    def peak_flops(self, dtype_bytes: int) -> float:
        # f32 matmul on v5e-class MXUs runs as 3-pass bf16 (~1/3 rate);
        # f64 would be software-emulated (~1/10 of f32) — Fermi's 1/2-rate
        # DP has no native analogue on v5e (recorded in DESIGN.md §2).
        if dtype_bytes <= 2:
            return self.peak_flops_bf16
        if dtype_bytes == 4:
            return self.peak_flops_f32
        return self.peak_flops_f32 / 10.0


TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=197e12 / 3.0,
    hbm_bw=819e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
    ici_link_bw=50e9,
    ici_links=4,
    mxu_dim=128,
)

# The paper's own accelerators, used by the modeled Table-2 reproduction.
TESLA_C2050 = ChipSpec(
    name="tesla-c2050",
    peak_flops_bf16=1.03e12,     # no bf16 in 2010; use SP rate
    peak_flops_f32=1.03e12,
    hbm_bw=144e9,
    hbm_bytes=3 * 1024**3,
    vmem_bytes=48 * 1024,        # shared memory per SM
    ici_link_bw=8e9,             # PCIe 2.0 x16
    ici_links=1,
    mxu_dim=32,
)

TESLA_C1060 = ChipSpec(
    name="tesla-c1060",
    peak_flops_bf16=0.622e12,
    peak_flops_f32=0.622e12,
    hbm_bw=102e9,
    hbm_bytes=4 * 1024**3,
    vmem_bytes=16 * 1024,
    ici_link_bw=4e9,
    ici_links=1,
    mxu_dim=8,
)

DEFAULT_CHIP = TPU_V5E

#: Measured chips keyed by the `device_kind` string JAX reports.
DEVICE_KINDS = {"TPU v5 lite": TPU_V5E}


def chip_for(device_kind: str) -> ChipSpec:
    """The spec (peaks, VMEM) of a device JAX reports; raises for a
    device kind with no table entry, so no measurement is ever scored
    against another chip's peaks."""
    try:
        return DEVICE_KINDS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {device_kind!r}; "
            f"known: {sorted(DEVICE_KINDS)}") from None

#: Name -> spec registry (core.policy parses `chip=` policy fields
#: against this, so REPRO_POLICY can select any modeled chip).
CHIPS = {c.name: c for c in (TPU_V5E, TESLA_C2050, TESLA_C1060)}


def fingerprint(chip: ChipSpec | None = None) -> str:
    """Hardware identity string keying the tuning cache (repro.tuning).

    Tile timings only transfer between identical stacks, so the key
    combines the modeled chip, the physical device actually executing
    (platform + kind — interpret-mode timings on CPU must never be
    served to a real TPU), and the jax version (Mosaic codegen changes
    shift optima). Cache entries recorded under a different fingerprint
    are ignored and the static chooser in core.blocking is used instead.
    """
    import jax  # local: keep this module importable without jax

    chip = chip or DEFAULT_CHIP
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "unknown").replace(" ", "-")
    return f"{chip.name}|{dev.platform}|{kind}|jax-{jax.__version__}"
