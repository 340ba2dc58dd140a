"""Work counts (bench/harness/work.py) tied to what is known of the
models and of a GEMM: parameter counts of qwen3-0.6b (0.6B, 0.44B
outside the embedding) and mamba2-2.7b (2.7B), the GEMM's closed form
at the paper's 4096^3, and the per-token operations of training."""

import json

import pytest

import bench_tiny
from harness import work as W

PEAK = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def config(name):
    with open(bench_tiny.ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def test_qwen3_parameter_count():
    c = config("qwen3-0.6b")
    n = W.param_count(c)
    assert 0.58e9 < n < 0.61e9
    emb = 151936 * 1024
    assert 0.43e9 < n - emb < 0.45e9
    # the tied head is a matrix product of its own
    assert W.matmul_params(c) == n - 28 * (2 * 1024 + 2 * 128) - 1024


def test_mamba2_parameter_count():
    c = config("mamba2-2.7b")
    assert 2.6e9 < W.param_count(c) < 2.8e9
    assert [g[0] for g in W.layer_gemms(c)] == ["in_proj", "in_proj_bc",
                                                "out_proj"]
    # in_proj of the published layer: z, x (2 x 5120), B, C (2 x 128), dt (80)
    assert sum(g[2] for g in W.layer_gemms(c)[:2]) == 2 * 5120 + 256 + 80


def test_gemm_closed_form_4096():
    n = 4096
    w = W.gemm(n, n, n)
    assert w.flops == 2 * n ** 3
    assert w.bytes == 3 * n * n * 2
    assert w.seconds(PEAK) == pytest.approx(2 * n ** 3 / 197e12)
    # a 1-row GEMM is bound by the weight bytes
    one = W.gemm(1, n, n)
    assert one.seconds(PEAK) == pytest.approx(one.bytes / 819e9)


def test_train_flops_per_token():
    c = config("qwen3-0.6b")
    per_tok = W.train_flops_step(c, 4, 1024) / (4 * 1024)
    assert 3.8e9 < per_tok < 4.0e9
    calls = W.train_gemm_calls(c, 4, 1024)
    assert sum(w.flops for w in calls) == pytest.approx(
        3 * 2 * W.matmul_params(c) * 4096)


def test_serving_calls_count_active_rows():
    c = config("qwen3-0.6b")
    # a prompt's operations are those of its tokens, one at a time
    assert W.forward_flops_prompt(c, 20) == pytest.approx(
        sum(W.forward_flops_token(c, i) for i in range(1, 21)))
    d = W.attention_decode(c, 1000)
    assert d.bytes == 2 * (2 * 1000 * 8 * 128 + 2 * 16 * 128)
    assert d.flops == 4 * 16 * 128 * 1000
    m = config("mamba2-2.7b")
    assert W.forward_flops_token(m, 10) == W.forward_flops_token(m, 5000)
