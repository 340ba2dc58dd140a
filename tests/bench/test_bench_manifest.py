"""BENCHMARK.json holds what the harness finds by name: every cell's
configuration, traffic and limits file, every per-layer metric's
reader, and names and units of the allowed characters."""

import json
import re

import pytest

import bench_tiny
from harness import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MAN = spec.manifest()
BENCH = bench_tiny.ROOT / "bench"


def test_keys_and_names():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in MAN[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for k in ("end_to_end", "per_layer")
               for m in MAN[k])
    assert any(m["name"] == "setup_s" for m in MAN["end_to_end"])


@pytest.mark.parametrize("w", [w["name"] for w in MAN["workloads"]])
def test_cell_files_resolve(w):
    cell = spec.cell(w)
    assert cell.traffic["kind"] in ("serve", "train")
    assert (BENCH / "limits" / f"{w}.json").is_file()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.load_module("metrics", m["name"]).read)
    assert callable(spec.reference(cell.config).init_params)


def test_configs_hold_their_published_sizes():
    for c in MAN["configs"]:
        with open(bench_tiny.ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        p = cfg["program"]
        if p["family"] == "dense":
            assert (p["d_model"], p["d_ff"], p["n_layers"], p["vocab"]) == (
                cfg["hidden_size"], cfg["intermediate_size"],
                cfg["num_hidden_layers"], cfg["vocab_size"])
            assert (p["n_heads"], p["n_kv_heads"], p["head_dim"]) == (
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
        else:
            s = p["ssm"]
            assert (p["d_model"], p["n_layers"], p["vocab"]) == (
                cfg["d_model"], cfg["n_layer"], cfg["vocab_size"])
            assert (s["d_state"], s["head_dim"], s["expand"],
                    s["conv_width"], s["chunk"], s["n_groups"]) == (
                cfg["d_state"], cfg["headdim"], cfg["expand"],
                cfg["d_conv"], cfg["chunk_size"], cfg["ngroups"])


def test_per_layer_metrics_name_a_reported_end_to_end_metric():
    for m in MAN["per_layer"]:
        for w in m["workloads"]:
            assert m["moves"] in {e["name"] for e in spec.cell(w).end_to_end}
