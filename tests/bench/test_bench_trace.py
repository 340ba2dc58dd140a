"""The trace reduction (bench/harness/trace.py): busy/idle union,
per-kernel time by name, and idle gaps named by the host span they fall
in, on hand-made events and on a small trace recorded on a TPU v5e
(three 512^3 bf16 calls of the tiled GEMM kernel, each followed by a
2 ms host sleep; data/tiny_gemm.xplane.pb)."""

import pathlib

import pytest

import bench_tiny  # noqa: F401  (puts bench/ on the path)
from harness import trace as TR

DATA = pathlib.Path(__file__).resolve().parent / "data"
E = TR.Event


def test_union_merges_overlaps_and_nesting():
    evs = [E("%while.1 = x", 0, 100), E("%fusion.2 = y", 10, 20),
           E("%copy.3 = z", 90, 30), E("%add.4 = w", 200, 10)]
    assert TR.union(evs) == [(0, 120), (200, 210)]
    assert TR.busy_ns(evs) == 130


def test_kernel_names_and_time():
    evs = [E("%matmul_tiled.31 = f32[16,8] custom-call(bf16[16,4])", 0, 5),
           E("%gated_matmul_tiled.2 = bf16[4] custom-call(bf16[4])", 5, 7),
           E("%flash_decode = bf16[4] custom-call(bf16[4])", 12, 3),
           E("%fusion.7 = bf16[4] fusion(bf16[4])", 15, 100)]
    assert TR.kernel_name(evs[0].name) == "matmul_tiled"
    assert TR.kernel_name(evs[2].name) == "flash_decode"
    assert TR.kernel_name(evs[3].name) is None
    assert TR.kernel_ns(evs, lambda k: "matmul" in k) == 12
    assert TR.op_name(evs[3].name) == "fusion"


def test_self_times_exclude_nested_ops():
    evs = [E("%while.1 = x", 0, 100), E("%fusion.2 = y", 10, 20),
           E("%fusion.3 = y", 40, 10)]
    own = TR.self_times(evs)
    assert own == {"while": 70, "fusion": 30}


def test_idle_gaps_named_by_innermost_span():
    dev = [E("%a.1 = x", 0, 10), E("%a.2 = x", 30, 10), E("%a.3 = x", 90, 10)]
    spans = [E("bench.window", 0, 120), E("bench.engine_step", 0, 45),
             E("bench.idle", 50, 35)]
    gaps = TR.idle_gaps(dev, spans, 0, 120)
    assert [(n, round(s * 1e9)) for n, s in gaps] == [
        ("bench.engine_step", 20), ("bench.idle", 50), ("bench.window", 20)]


def test_reduce_on_window_span():
    us = 1000.0
    dev = {"/device:TPU:0": [
        E("%matmul_tiled.1 = b custom-call(a)", 5 * us, 10 * us),
        E("%fusion.1 = b", 50 * us, 20 * us)]}
    spans = [E("bench.traced", 0, 100 * us),
             E("bench.engine_step", 0, 40 * us)]
    red = TR.reduce(TR.Trace(dev, spans), "bench.traced")
    assert red["window_s"] == pytest.approx(100e-6)
    assert red["busy_s"] == pytest.approx(30e-6)
    assert red["kernel_s"] == {"matmul_tiled": pytest.approx(10e-6)}
    # the 5 us gap before the first op is under the 10 us that is named
    assert [(n, round(v * 1e6)) for n, v in red["idle_gaps"]] == [
        ("bench.engine_step", 35), ("bench.traced", 30)]
    back = TR.from_json(TR.to_json(TR.Trace(dev, spans)))
    assert back == TR.Trace(dev, spans)


def test_recorded_chip_trace():
    t = TR.load(str(DATA / "tiny_gemm.xplane.pb"))
    assert list(t.devices) == ["/device:TPU:0"]
    evs = t.devices["/device:TPU:0"]
    assert len(evs) == 3
    assert {TR.kernel_name(e.name) for e in evs} == {"matmul_tiled"}
    assert [s.name for s in t.spans] == ["bench.tiny_call",
                                         "bench.tiny_sleep"] * 3
    lo = min(e.start_ns for e in evs)
    hi = t.spans[-1].end_ns
    busy = TR.busy_ns(TR.clip(evs, lo, hi))
    assert busy == pytest.approx(sum(e.dur_ns for e in evs))
    assert TR.kernel_ns(evs, lambda k: k == "matmul_tiled") == busy
    gaps = TR.idle_gaps(evs, t.spans, lo, hi)
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(hi - lo - busy)
    assert {n for n, _ in gaps} <= {"bench.tiny_call", "bench.tiny_sleep",
                                    "no bench span"}
    # a 512^3 bf16 GEMM is 0.27 GFLOP: each call took 4.1-4.3 us
    assert all(4000 < e.dur_ns < 4500 for e in evs)
