"""A training run with its timed path broken underneath comes out not
correct (CPU, small width): a step that returns its state unchanged,
and a step that leaves out half of each batch and takes the mean over
the rest. The same run unbroken comes out correct."""

import time

import jax
import pytest

import bench_tiny
import faults as F
from harness import train
from repro.training import train_loop as TL
from run import checks

SEED = 2 ** 31 + 78
# tiny CPU readings: sound 7e-4 / 1.3e-3 / 2.7e-3; half batch 0.069 /
# 0.062 / 0.055; unchanged state 1.0 on the gradient and the change
LIMITS = {"limits": {"loss_gap": {"limit": 5e-3},
                     "grad_gap": {"limit": 1e-2},
                     "change_gap": {"limit": 2e-2}}}


def _correct(factory=None):
    cell = bench_tiny.cell("qwen3-0.6b", bench_tiny.train_traffic())
    res = train.run(cell, SEED, 0.3, False, jax.devices(),
                    time.perf_counter(), "", step_factory=factory)
    ok, chk = checks(res["readings"], LIMITS)
    return ok and res["failed"] == 0, chk


def test_sound_run_is_correct():
    ok, chk = _correct()
    assert ok, chk


@pytest.mark.parametrize("fault", [F.train_state_unchanged,
                                   F.train_half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_broken_run_is_not_correct(fault):
    ok, chk = _correct(fault(TL.make_train_step))
    assert not ok, chk
