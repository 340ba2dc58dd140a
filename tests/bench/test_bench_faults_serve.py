"""A serving run with its timed path broken underneath comes out not
correct: the harness's look for a chip is skipped and the rest of a run
(set-up, window, sample, reference, check) is driven on the CPU at a
small width, with a decode step that returns its cache unchanged, or
with served tokens altered where they are produced. The same run
unbroken comes out correct."""

import time

import jax
import pytest

import bench_tiny
import faults as F
from harness import serve
from run import checks

SEED = 2 ** 31 + 77
# the tiny programs serve the reference's own argmax (gap 0.0); the
# faults read 0.06-3.6 (tiny CPU runs)
LIMITS = {"limits": {"max_gap": {"limit": 0.02}}}


def _correct(config, fault=None):
    cell = bench_tiny.cell(config, bench_tiny.serve_traffic())
    res = serve.run(cell, SEED, 0.6, False, jax.devices(),
                    time.perf_counter(), "", fault=fault)
    ok, chk = checks(res["readings"], LIMITS)
    return ok and res["failed"] == 0, chk


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b"])
def test_sound_run_is_correct(config):
    ok, chk = _correct(config)
    assert ok, chk


@pytest.mark.parametrize("config", ["qwen3-0.6b", "mamba2-2.7b"])
@pytest.mark.parametrize("fault", [
    F.serve_state_unchanged,
    lambda eng: F.serve_token_altered(eng, every=4)],
    ids=["state_unchanged", "token_altered"])
def test_broken_run_is_not_correct(config, fault):
    ok, chk = _correct(config, fault)
    assert not ok, chk
