"""The control comes out not correct: the reference put in the
program's place and computed one precision below what the
configuration states (float8 e4m3 operands for every matrix product,
under bf16 compute), at a width a test run can hold. For serving, at
each position of the served requests the token the lower precision puts
first is read against the float32 reference; for training, its losses,
first gradient and change after three steps. The program's own readings
on the same run stay within the same limits."""

import time

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
from harness import serve, train, traffic as T
from run import checks

SEED = 2 ** 31 + 91
FP8 = jnp.float8_e4m3fn


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b"])
def test_serving_control_fails(config):
    cell = bench_tiny.cell(config, bench_tiny.serve_traffic())
    cfg, ref, params, engine = serve.build(cell, SEED)
    reqs = T.serve_requests(cell.traffic, cfg.vocab, SEED, 1.0)
    serve.warm(engine, cell, reqs)
    rec = serve.drive(engine, reqs, 1.0)
    # every finished request: a lower precision flips the top token at
    # a few positions only, at this width
    picked = serve.sample(engine, rec, SEED, len(reqs))
    prog = serve.readings(cell, ref, params, picked)
    ctrl = serve.readings(cell, ref, params, picked, mm_dtype=FP8)
    lim = {"limits": {"max_gap": {"limit": 0.02}}}
    assert checks(prog, lim)[0], prog
    assert not checks(ctrl, lim)[0], ctrl


def test_training_control_fails():
    cell = bench_tiny.cell("qwen3-0.6b", bench_tiny.train_traffic())
    _, _, _, _, init = train.build(cell, SEED)
    ref32 = train.reference_readings(cell, SEED, init)
    ref8 = train.reference_readings(cell, SEED, init, mm_dtype=FP8)
    lim = {"limits": {"loss_gap": {"limit": 5e-3},
                      "grad_gap": {"limit": 1e-2},
                      "change_gap": {"limit": 2e-2}}}
    ok, chk = checks(train.compare(ref8, ref32), lim)
    assert not ok, chk
