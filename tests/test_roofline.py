"""Roofline/HLO-analyzer tests: trip-count awareness, remat detection,
collective parsing, report construction."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hw
from repro.roofline import hlo as H
from repro.roofline.analysis import build_report, count_params, model_flops
import repro.configs as C
from repro.configs.base import get_shape


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_flops_scaled_by_trip_count():
    def layer(x, w):
        return jnp.tanh(x @ w)

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (layer(c, w), None), x, ws)[0]

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
    c = _compile(f, xs, ws)
    costs = H.analyze(c.as_text(), 1)
    expect = 2 * 64 * 128 * 128 * 12
    assert abs(costs.flops - expect) / expect < 0.02
    # XLA's own number undercounts by the trip count (the known gap)
    ca = c.cost_analysis()
    if isinstance(ca, list):  # jax <= 0.4.x wraps it per-device
        ca = ca[0]
    assert ca["flops"] * 6 < costs.flops


def test_remat_recompute_visible():
    def layer(x, w):
        return jnp.tanh(x @ w)

    def f(x, ws):
        return jax.lax.scan(lambda c, w: (layer(c, w), None), x, ws)[0]

    def f_remat(x, ws):
        body = jax.checkpoint(lambda c, w: (layer(c, w), None))
        return jax.lax.scan(body, x, ws)[0]

    xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    g = lambda fn: (lambda x, w: jnp.sum(fn(x, w) ** 2))
    plain = H.analyze(_compile(jax.grad(g(f), argnums=1), xs, ws).as_text(), 1)
    remat = H.analyze(_compile(jax.grad(g(f_remat), argnums=1), xs, ws)
                      .as_text(), 1)
    # remat adds ~1 extra forward: 4/3 of the plain grad flops
    ratio = remat.flops / plain.flops
    assert 1.25 < ratio < 1.45, ratio


def test_collective_parse_and_ici_model():
    hlo_text = """
HloModule test

ENTRY %main (a: f32[16,128]) -> f32[16,128] {
  %a = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%a), replica_groups=[4,2]<=[8], to_apply=%x
  ROOT %ag = f32[16,128]{1,0} all-gather(%ar), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
}
"""
    costs = H.analyze(hlo_text, 8)
    summ = costs.collective_summary()
    assert summ["all-reduce"]["count"] == 1
    ar = [c for c in costs.collectives if c.op.startswith("all-reduce")][0]
    ag = [c for c in costs.collectives if c.op.startswith("all-gather")][0]
    assert ar.group_size == 2
    assert ag.group_size == 4
    n = 16 * 128 * 4
    assert abs(ar.ici_bytes - 2 * n * 1 / 2) < 1
    assert abs(ag.ici_bytes - n * 3 / 4) < 1


def test_model_flops_conventions():
    cfg = C.get_config("qwen3-0.6b")
    cell = get_shape("train_4k")
    total, active = count_params(cfg)
    assert active == total                      # dense
    mf = model_flops(cfg, cell, kind="train")
    assert mf == 6.0 * total * cell.global_batch * cell.seq_len

    moe_cfg = C.get_config("mixtral-8x22b")
    t2, a2 = count_params(moe_cfg)
    assert a2 < t2 / 2                          # top-2 of 8 experts


def test_report_bounds_and_terms():
    def f(x, w):
        return jnp.sum(jnp.tanh(x @ w))

    xs = jax.ShapeDtypeStruct((256, 512), jnp.bfloat16)
    ws = jax.ShapeDtypeStruct((512, 512), jnp.bfloat16)
    c = _compile(f, xs, ws)
    cfg = C.get_config("qwen3-0.6b")
    rep = build_report(cfg, get_shape("train_4k"), kind="train",
                       mesh_name="1", n_devices=1, hlo_text=c.as_text())
    assert rep.bound in ("compute", "memory", "collective")
    assert rep.t_compute > 0 and rep.t_memory > 0
    assert rep.t_collective == 0.0              # no collectives on 1 dev


def test_decode_traffic_reads_each_kv_head_prefix_once():
    from repro.core.blocking import (FlashBlockConfig, choose_decode_config,
                                     decode_traffic_bytes)
    from repro.roofline.analysis import decode_attention_savings
    cfg = FlashBlockConfig(bq=1, bk=512)
    # one slot of qwen3-0.6b (16 query heads over 8 kv heads, d 128):
    # each kv head's prefix once, in whole blocks, plus q and o rows
    rows = lambda pos: decode_traffic_bytes(pos, 2560, 128, cfg, 2, h=16,
                                            hkv=8) - 2 * 16 * 128 * 2
    assert rows(0) == rows(511) == 2 * 8 * 512 * 128 * 2
    assert rows(512) == 2 * rows(511)
    assert rows(2559) == 2 * 8 * 2560 * 128 * 2        # full cache
    # the group does not re-read K/V: twice the query heads, same bytes
    assert decode_traffic_bytes(127, 2560, 128, cfg, 2, h=32, hkv=8) \
        - decode_traffic_bytes(127, 2560, 128, cfg, 2, h=16, hkv=8) \
        == 2 * 16 * 128 * 2
    # the chooser keeps a power-of-two divisor of the depth, at most 512
    assert choose_decode_config(2560, 8, 128, 2).bk == 512
    assert choose_decode_config(96, 8, 128, 2).bk == 96
    # early in a long cache the prefix clamp is the win
    s = decode_attention_savings(127, 4096, 128, 2, h=16, hkv=8)
    assert s["saved_frac"] >= 0.80, s


def test_kv_traffic_and_quant_savings_thresholds():
    from repro.roofline.analysis import kv_decode_traffic_bytes, \
        kv_quant_savings
    # exact bookkeeping: (pos + 1) rows per side, heads * d elements
    assert kv_decode_traffic_bytes(15, 4, 64, 2) == 2 * 16 * 4 * 64 * 2
    assert kv_decode_traffic_bytes(15, 4, 64, 2, quant_kv="int8") == \
        2 * 16 * 4 * (64 + 4)
    # acceptance bar: int8 KV pages cut decode KV traffic by >= 40%
    for d in (64, 128):
        for itemsize in (2, 4):
            s = kv_quant_savings(255, 8, d, itemsize)
            assert s["saved_frac"] >= 0.40, (d, itemsize, s)
    # wider rows amortize the per-row scale better
    assert kv_quant_savings(255, 8, 128, 2)["saved_frac"] > \
        kv_quant_savings(255, 8, 64, 2)["saved_frac"]


def test_ssd_traffic_model_thresholds():
    from repro.core.blocking import SSDBlockConfig, choose_ssd_config
    from repro.roofline.analysis import ssd_savings, ssm_decode_state_bytes
    # exact bookkeeping: one (H, P, N) f32 state, read + write, per step
    assert ssm_decode_state_bytes(4, 8, 16) == 2 * 4 * 8 * 16 * 4
    # acceptance bar: the fused intra-chunk kernel cuts modeled HBM
    # bytes >= 40% at the mamba2-2.7b layer shape (the quadratic decay
    # mask + CB score round trips stay VMEM-resident)
    s = ssd_savings(4096, 40, 64, 128, 256, 4)
    assert s["saved_frac"] >= 0.40, s
    assert s["fused_bytes"] < s["unfused_bytes"]
    # the static chooser's pick must fit the double-buffered VMEM budget
    cfg = choose_ssd_config(256, 64, 128, 4)
    from repro.core.hw import TPU_V5E
    assert cfg.vmem_bytes(128, 4) <= TPU_V5E.vmem_bytes * 0.5 + 1
    assert 256 % cfg.q == 0 and 64 % cfg.bp == 0
    # longer chunks round-trip quadratically more unfused bytes; the
    # fused side only grows linearly in the extra scan traffic
    s_long = ssd_savings(4096, 40, 64, 128, 512, 4,
                         cfg=SSDBlockConfig(q=256, bp=64))
    assert s_long["unfused_bytes"] > s["unfused_bytes"]


def test_kv_capacity_model_prefix_heavy_2x():
    from repro.roofline.analysis import kv_capacity_model
    kw = dict(max_len=64, page_size=16, heads=4, d=64, itemsize=4,
              prompt_len=40, shared_prefix_len=32, gen=8)
    pool = 2 * 64 * (2 * 4 * 64 * 4)        # exactly 2 dense slots' bytes
    f32 = kv_capacity_model(pool, **kw)
    q8 = kv_capacity_model(pool, quant_kv="int8", **kw)
    assert f32["dense_slots"] == 2
    # acceptance bar: >= 2x concurrent slots on the prefix-heavy trace
    assert f32["capacity_ratio"] >= 2.0
    assert q8["capacity_ratio"] >= 2.0
    assert q8["paged_slots"] > f32["paged_slots"]   # int8 pages stack up
    assert q8["n_pages"] > f32["n_pages"]


def test_chip_for_looks_up_measured_devices_by_device_kind():
    """Peaks for a measured device come from its device_kind; a kind
    with no table entry raises instead of scoring against another
    chip's peaks."""
    assert hw.chip_for("TPU v5 lite") is hw.TPU_V5E
    with pytest.raises(ValueError, match="no peak table entry"):
        hw.chip_for("cpu")
