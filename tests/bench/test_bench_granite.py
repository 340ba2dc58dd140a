"""The granite-4.0-h-micro doc-chat cell's parts of the benchmark at a
small width on the CPU: a serving run through the harness comes out
correct, and with a served token altered or a decode step that leaves
the state unchanged it does not; the per-layer readers read it; the
hybrid work counts agree with the reference's weights and the engine's
cache at the published widths; the configuration's mamba_ssm-named
sizes repeat the published ones."""


import copy
import json
import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
import faults as F
from harness import serve, spec, work_hybrid as H
from run import checks, layer_context

SEED = 2 ** 31 + 91
# the tiny float32 program serves the reference's own argmax (gap 0.0);
# a stale decode state reads 0.0039 and an altered token 0.038 on this
# seed (tiny CPU runs)
LIMITS = {"limits": {"max_gap": {"limit": 1e-3}}}
NAME = "granite-4.0-h-micro"
TYPES = ["mamba", "mamba", "attention", "mamba"] * 2
TINY = {"n_layers": len(TYPES), "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 250,
        "vocab_pad_to": 128, "dtype": "float32", "layer_types": TYPES,
        "ssm": {"d_state": 16, "head_dim": 16, "expand": 2,
                "conv_width": 4, "chunk": 16, "n_groups": 1}}
PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def _config(tiny: bool) -> dict:
    with open(bench_tiny.ROOT / "bench" / "configs" / f"{NAME}.json") as f:
        cfg = json.load(f)
    if tiny:
        cfg["program"].update(copy.deepcopy(TINY))
        cfg["layer_types"] = TYPES
    return cfg


def _cell(per_layer=()):
    return spec.Cell(name="tiny", chips=1, config_name=NAME,
                     config=_config(True), traffic_name="tiny",
                     traffic=bench_tiny.serve_traffic(), end_to_end=[],
                     per_layer=list(per_layer))


def _run(fault=None):
    cell = _cell()
    res = serve.run(cell, SEED, 0.6, False, jax.devices(),
                    time.perf_counter(), "", fault=fault)
    ok, chk = checks(res["readings"], LIMITS)
    return cell, res, ok and res["failed"] == 0, chk


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_sound_run_is_correct(sound):
    _, res, ok, chk = sound
    assert ok, chk
    assert res["readings"]["requests_checked"] >= 1


def test_broken_run_is_not_correct():
    _, _, ok, chk = _run(lambda eng: F.serve_token_altered(eng, every=4))
    assert not ok, chk


def test_stale_state_is_not_correct():
    _, _, ok, chk = _run(F.serve_state_unchanged)
    assert not ok, chk


def test_readers_read_the_run(sound):
    """Without a trace the device readers find nothing to read; the
    engine's counters and the HBM floor read the window."""
    cell, res, _, _ = sound
    names = [m["name"] for m in spec.manifest()["per_layer"]
             if f"{NAME}.doc-chat" in m.get("workloads", [])]
    assert len(names) == 5
    ctx = layer_context(cell, "serve", res, PEAK, None)
    got = {n: spec.load_module("metrics", n).read(ctx) for n in names}
    for n in ("decode_attention_roofline.doc-chat", "idle_share.doc-chat"):
        assert got[n] is None
    assert got["engine.admit_ms.doc-chat"] > 0
    assert got["engine.decode_step_ms.doc-chat"] > 0
    assert 0 < got["decode_hbm_share.doc-chat"] <= 100


def test_work_counts_agree_with_the_weights_and_the_cache():
    from repro.models import model as M
    from repro.serving.engine import _slot_bytes
    cfg_json = _config(False)
    ref = spec.reference(cfg_json)
    params = jax.eval_shape(lambda: ref.init_params(
        cfg_json, jax.random.PRNGKey(0), jnp.bfloat16))
    assert H.weight_bytes(cfg_json) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    cfg = spec.model_config(cfg_json, "bfloat16")
    slot = _slot_bytes(jax.eval_shape(lambda: M.init_cache(cfg, 1, 4608)))
    assert H.state_bytes(cfg_json) == slot["state_bytes"]
    assert H.kv_bytes(cfg_json, 4608) == slot["kv_bytes"]
    assert H.layer_counts(cfg_json) == (36, 4, 40)


def test_mamba_ssm_names_repeat_the_published_sizes():
    cfg = _config(False)
    assert (cfg["d_model"], cfg["n_layer"], cfg["d_state"], cfg["headdim"],
            cfg["expand"], cfg["d_conv"], cfg["chunk_size"],
            cfg["ngroups"]) == (
        cfg["hidden_size"], cfg["num_hidden_layers"], cfg["mamba_d_state"],
        cfg["mamba_d_head"], cfg["mamba_expand"], cfg["mamba_d_conv"],
        cfg["mamba_chunk_size"], cfg["mamba_n_groups"])
    p = cfg["program"]
    assert p["layer_types"] == cfg["layer_types"]
    assert (p["norm_eps"], p["embedding_multiplier"],
            p["attention_multiplier"], p["residual_multiplier"],
            p["logits_scaling"]) == (
        cfg["rms_norm_eps"], cfg["embedding_multiplier"],
        cfg["attention_multiplier"], cfg["residual_multiplier"],
        cfg["logits_scaling"])
    assert p["use_rope"] == (cfg["position_embedding_type"] == "rope")
