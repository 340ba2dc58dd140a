"""Fault-tolerant serving under deterministic chaos.

Every fault here is *scripted* — the FaultInjector fires at fixed
decode-step counters and admission ordinals, never off a clock or an
RNG — so each recovery path is pinned by an exact-output assertion:

  * a NaN'd logits row quarantines exactly the poisoned slot while the
    co-scheduled streams stay token-exact vs the fault-free reference;
  * a preempted victim (pages reclaimed, re-prefilled on resume) ends
    byte-identical to an uninterrupted run;
  * repeated kernel faults degrade the engine to the xla registry
    backend (warning once) and the trace still completes exactly;
  * the report's fault counters and goodput stay sum-consistent.

The reference oracle is _reference_generate from test_serving: one
whole-prompt prefill + scalar-pos greedy decode, batch 1.
"""

import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import Policy
from repro.models import model as M
from repro.serving import FaultInjector, ServingEngine, SimulatedKernelFault
from repro.serving.request import (ACTIVE, CANCELLED, EXPIRED, FINISHED,
                                   QUARANTINED, WAITING)
from test_serving import _reference_generate


def _setup(arch="qwen3-0.6b", seed=0):
    # f32 activations: XLA:CPU accumulates a one-row matmul in another
    # order than a multi-row one (~1e-6 relative), so a bf16 model's
    # greedy picks can differ between the engine's batched decode and
    # the batch-1 reference on a near-tie. These tests pin the engine's
    # slot bookkeeping, which f32 shows token-exactly.
    cfg = dataclasses.replace(get_config(arch, reduced=True),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    return cfg, params


def _prompts(cfg, lengths, seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
            for l in lengths]


def _check_consistency(engine, report):
    """Acceptance (c): counters and goodput must sum consistently."""
    reqs = engine.requests
    n = len(reqs)
    by = {s: sum(1 for r in reqs if r.status == s)
          for s in (FINISHED, EXPIRED, CANCELLED, QUARANTINED)}
    assert report["n_finished"] == by[FINISHED]
    assert report["expired"] == by[EXPIRED]
    assert report["cancelled"] == by[CANCELLED]
    assert report["quarantined"] == by[QUARANTINED]
    assert sum(by.values()) == n, (by, n)
    assert engine.tokens_emitted == sum(r.n_generated for r in reqs)
    useful = sum(r.n_generated for r in reqs
                 if r.status == FINISHED and r.missed_deadline is not True)
    assert report["useful_tokens"] == useful
    assert report["goodput"] == useful / max(engine.tokens_emitted, 1)
    assert 0.0 <= report["goodput"] <= 1.0


# ---------------------------------------------------------------- injector

def test_fault_injector_scripting_and_fire_once():
    inj = FaultInjector(nan_rows={3: 1}, corrupt_pages={2: (0, 1)},
                        kernel_fail_steps=(5,), slow_steps={4: 0.0},
                        deny_admissions=(1,))
    # slot-map normalization: scalar -> tuple
    assert inj.nan_rows == {3: (1,)}
    assert inj.corrupt_pages == {2: (0, 1)}
    # wrong step / inactive slot: no-op, nothing fired
    rows = np.zeros((2, 4), np.float32)
    assert inj.poison_rows(0, rows, (0, 1)) is rows
    assert inj.poison_rows(3, rows, (0,)) is rows       # slot 1 not active
    # scripted step: returns a poisoned COPY, original untouched
    out = inj.poison_rows(3, rows, (0, 1))
    assert out is not rows and np.isfinite(rows).all()
    assert np.isnan(out[1]).all() and np.isfinite(out[0]).all()
    # fire-once: a second pass at the same step is clean
    assert inj.poison_rows(3, rows, (0, 1)) is rows
    assert inj.corrupt_slots(2, (0, 1, 2)) == (0, 1)
    assert inj.corrupt_slots(2, (0, 1, 2)) == ()
    with pytest.raises(SimulatedKernelFault):
        inj.before_kernel(5)
    inj.before_kernel(5)                                # retry sails through
    inj.before_kernel(4)                                # slow step (0s sleep)
    assert inj.deny_admission(1) and not inj.deny_admission(1)
    assert not inj.deny_admission(0)
    assert inj.report() == {"nan_rows": 1, "page_corruptions": 2,
                            "kernel_faults": 1, "slow_steps": 1,
                            "denied_admissions": 1}


# ------------------------------------------------------- NaN quarantine (a)

def test_nan_quarantines_exact_slot_others_token_exact():
    """Acceptance (a): the poisoned slot is quarantined at the scripted
    step with a diagnostic; every other stream — including the request
    admitted into the freed slot — matches the fault-free reference."""
    cfg, params = _setup()
    lens, gens = [12, 16, 10], [6, 6, 5]
    prompts = _prompts(cfg, lens)
    inj = FaultInjector(nan_rows={2: 0})        # slot 0 = request 0
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        fault_injector=inj)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    report = eng.run()

    bad = reqs[0]
    assert bad.status == QUARANTINED
    assert bad.error == "non-finite logits at decode step 2"
    # prefill + decode steps 0,1 emitted 3 tokens; poisoned step 2 did not
    assert len(bad.generated) == 3
    assert report["quarantined"] == 1 and report["n_finished"] == 2
    for req, prompt, g in zip(reqs[1:], prompts[1:], gens[1:]):
        assert req.status == FINISHED
        assert req.generated == _reference_generate(cfg, params, prompt, g)
    assert report["faults_injected"]["nan_rows"] == 1
    assert report["goodput"] < 1.0              # the 2 poisoned-slot tokens
    _check_consistency(eng, report)


def test_page_corruption_quarantines_through_attention_math():
    """A NaN'd PRIVATE page surfaces through real attention math and
    quarantines only the owning slot; the co-resident stream (whose
    pages are untouched by construction) stays token-exact."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [10, 14], seed=23)
    inj = FaultInjector(corrupt_pages={2: 1})   # slot 1, mid-page write pos
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        policy=Policy(kv_layout="paged"), page_size=8,
                        fault_injector=inj)
    r0, r1 = [eng.submit(p, 6) for p in prompts]
    report = eng.run()
    assert r1.status == QUARANTINED and r1.error
    assert r0.status == FINISHED
    assert r0.generated == _reference_generate(cfg, params, prompts[0], 6)
    assert report["faults_injected"]["page_corruptions"] == 1
    # quarantine released the slot's pages: the pool fully drains
    assert (eng.pool.refcount == 0).all()
    _check_consistency(eng, report)


# -------------------------------------------------- preempt + resume (b)

def test_preempt_resume_byte_identical():
    """Acceptance (b): forced pool exhaustion at a scripted admission
    preempts the lower-priority victim mid-decode (pages reclaimed);
    the victim re-prefills prompt+generated on resume and finishes
    BYTE-IDENTICAL to an uninterrupted run."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [12, 10], seed=31)
    inj = FaultInjector(deny_admissions=(1,))   # second admission sees
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,  # no pages
                        policy=Policy(kv_layout="paged"), page_size=8,
                        fault_injector=inj, preempt_backoff=0.005)
    victim = eng.submit(prompts[0], 8, priority=0)
    for _ in range(3):              # prefill token + 3 decode tokens
        eng.step()
    assert victim.status == ACTIVE and len(victim.generated) == 4
    vip = eng.submit(prompts[1], 4, priority=1)
    report = eng.run()

    assert report["preempted"] == 1 and victim.preemptions == 1
    assert report["faults_injected"]["denied_admissions"] == 1
    assert vip.status == FINISHED and victim.status == FINISHED
    assert vip.generated == _reference_generate(cfg, params, prompts[1], 4)
    assert victim.generated == _reference_generate(cfg, params, prompts[0], 8)
    assert (eng.pool.refcount == 0).all()
    _check_consistency(eng, report)


def test_equal_priority_exhaustion_defers_not_preempts():
    """A denied admission with no strictly-lower-priority victim must
    defer FCFS (no churn), exactly like organic pool exhaustion."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [12, 10], seed=37)
    inj = FaultInjector(deny_admissions=(1,))
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        policy=Policy(kv_layout="paged"), page_size=8,
                        fault_injector=inj)
    r0 = eng.submit(prompts[0], 6)
    eng.step()
    r1 = eng.submit(prompts[1], 4)              # same priority: no victim
    report = eng.run()
    assert report["preempted"] == 0 and r0.preemptions == 0
    assert r0.status == FINISHED and r1.status == FINISHED
    assert r0.generated == _reference_generate(cfg, params, prompts[0], 6)
    assert r1.generated == _reference_generate(cfg, params, prompts[1], 4)
    _check_consistency(eng, report)


# ------------------------------------------------ kernel faults -> degrade

def test_kernel_faults_degrade_to_xla_and_trace_completes():
    import repro.serving.engine as E
    cfg, params = _setup()
    prompts = _prompts(cfg, [10, 13], seed=41)
    inj = FaultInjector(kernel_fail_steps=(1, 3))
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        policy=Policy(backend="pallas", interpret=True),
                        fault_injector=inj, kernel_fault_threshold=2)
    reqs = [eng.submit(p, 5) for p in prompts]
    E._DEGRADE_WARNED = False
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        report = eng.run()
    degrade_warns = [x for x in w if "degraded to the 'xla'" in str(x.message)]
    assert len(degrade_warns) == 1              # once per process
    assert report["degraded"] and eng.policy.backend == "xla"
    assert report["kernel_faults"] == 2 and report["crashed_steps"] == 0
    assert report["n_finished"] == 2
    for req, prompt in zip(reqs, prompts):
        assert req.generated == _reference_generate(cfg, params, prompt, 5)
    _check_consistency(eng, report)


def test_kernel_fault_retry_without_degrade():
    """A single transient fault is retried in place: no degrade, no
    crash, token streams exact."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [10], seed=43)
    inj = FaultInjector(kernel_fail_steps=(2,))
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32,
                        fault_injector=inj)
    req = eng.submit(prompts[0], 6)
    report = eng.run()
    assert report["kernel_faults"] == 1 and not report["degraded"]
    assert report["crashed_steps"] == 0
    assert req.generated == _reference_generate(cfg, params, prompts[0], 6)


def test_kernel_fault_retry_exhaustion_counts_crashed_step():
    cfg, params = _setup()
    prompts = _prompts(cfg, [10], seed=47)
    inj = FaultInjector(kernel_fail_steps=(0, 1))
    # fire-once is per *scripted step*; with retries disabled both
    # scripted steps raise through and the run crashes loudly
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32,
                        fault_injector=inj, max_step_retries=0)
    eng.submit(prompts[0], 4)
    with pytest.raises(SimulatedKernelFault):
        eng.run()
    assert eng.crashed_steps == 1 and eng.kernel_faults == 1


def test_real_kernel_error_propagates_without_degrade():
    """Only injected faults degrade: a real error out of the jitted
    decode step fails the run at once, on the backend it was given."""
    cfg, params = _setup()
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32,
                        policy=Policy(backend="pallas", interpret=True))
    eng.submit(_prompts(cfg, [8], seed=49)[0], 4)   # prefill, then decode

    def broken_step(*args):
        raise RuntimeError("Mosaic failed to compile the kernel")

    eng._step = broken_step
    with pytest.raises(RuntimeError, match="Mosaic"):
        eng.run()
    assert not eng.degraded and eng.policy.backend == "pallas"
    assert eng.kernel_faults == 0 and eng.decode_steps == 0


def test_check_outputs_fails_a_degraded_run_without_injector():
    from repro.launch.serve import check_outputs
    cfg, params = _setup()
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32)
    req = eng.submit(_prompts(cfg, [10], seed=51)[0], 3)
    eng.run()
    check_outputs(cfg, eng, [req])
    eng.degraded = True
    with pytest.raises(AssertionError, match="no fault injector"):
        check_outputs(cfg, eng, [req])


# ------------------------------------------------- deadlines + cancellation

def test_deadline_expires_waiting_request():
    """A waiter whose deadline passes before a slot frees is dropped
    without ever being admitted; actives are never killed by deadline."""
    cfg, params = _setup()
    prompts = _prompts(cfg, [10, 10], seed=53)
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32)
    r0 = eng.submit(prompts[0], 8)
    r1 = eng.submit(prompts[1], 4, deadline=1e-4)   # expires in the queue
    report = eng.run()
    assert r0.status == FINISHED
    assert r1.status == EXPIRED and r1.t_admitted is None
    assert r1.missed_deadline is True and r1.n_generated == 0
    assert report["expired"] == 1
    assert report["deadline_miss_rate"] == 1.0      # only r1 had a deadline
    assert report["goodput"] == 1.0                 # r1 wasted no decode
    _check_consistency(eng, report)


def test_deadline_validation_and_finished_miss_accounting():
    cfg, params = _setup()
    prompts = _prompts(cfg, [8], seed=59)
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32)
    with pytest.raises(ValueError, match="deadline"):
        eng.submit(prompts[0], 4, arrival_time=1.0, deadline=0.5)
    # a FINISHED request that beat a generous deadline is not a miss
    req = eng.submit(prompts[0], 4, deadline=60.0)
    report = eng.run()
    assert req.status == FINISHED and req.missed_deadline is False
    assert report["deadline_miss_rate"] == 0.0 and report["goodput"] == 1.0


def test_cancel_waiting_active_and_terminal():
    cfg, params = _setup()
    prompts = _prompts(cfg, [10, 12, 10], seed=61)
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32,
                        policy=Policy(kv_layout="paged"), page_size=8)
    r0 = eng.submit(prompts[0], 6)
    r1 = eng.submit(prompts[1], 6)
    eng.step()                                  # r0 active, r1 waiting
    assert r0.status == ACTIVE and r1.status == WAITING
    assert eng.cancel(r1.rid)                   # cancel a waiter
    assert r1.status == CANCELLED and r1.slot == -1
    assert eng.cancel(r0.rid)                   # cancel the active request
    assert r0.status == CANCELLED
    assert (eng.pool.refcount == 0).all()       # pages reclaimed NOW
    assert eng.pool.n_reserved == 0
    assert not eng.cancel(r0.rid)               # terminal: no-op, False
    with pytest.raises(ValueError, match="unknown request"):
        eng.cancel(999)
    r2 = eng.submit(prompts[2], 4)              # engine still serves
    report = eng.run()
    assert r2.status == FINISHED
    assert r2.generated == _reference_generate(cfg, params, prompts[2], 4)
    assert report["cancelled"] == 2
    _check_consistency(eng, report)


def test_cancel_cow_sharer_keeps_survivor_exact():
    """Cancel one of two prefix-sharing requests right after its CoW
    split: refcounts on the shared pages drop but the survivor keeps
    decoding on intact pages, token-exact to the end."""
    cfg, params = _setup()
    rng = np.random.default_rng(67)
    prompt = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        policy=Policy(kv_layout="paged"), page_size=8)
    r0 = eng.submit(prompt.copy(), 6)
    r1 = eng.submit(prompt.copy(), 6)
    eng.step()                                  # both admitted; tail CoW'd
    assert eng.pool.stats.cow_copies == 1
    assert eng.cancel(r0.rid)
    report = eng.run()
    assert r1.status == FINISHED
    assert r1.generated == _reference_generate(cfg, params, prompt, 6)
    assert (eng.pool.refcount == 0).all()
    _check_consistency(eng, report)


# ------------------------------------------------------------- stragglers

def test_slow_step_flags_straggler():
    cfg, params = _setup()
    prompts = _prompts(cfg, [8], seed=71)
    inj = FaultInjector(slow_steps={5: 0.25})
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32,
                        fault_injector=inj)
    eng.submit(prompts[0], 8)
    report = eng.run()
    assert report["faults_injected"]["slow_steps"] == 1
    assert report["straggler_steps"] >= 1
    assert any(step == 5 for step, _, _ in eng.straggler.flagged)


# ------------------------------------------------------- combined chaos

def test_combined_chaos_counts_stay_consistent():
    """NaN + denial + kernel fault + cancel in one run: the engine keeps
    serving and every counter in the report stays sum-consistent."""
    cfg, params = _setup()
    lens = [12, 10, 14, 10, 8]
    prompts = _prompts(cfg, lens, seed=73)
    inj = FaultInjector(nan_rows={4: 1}, kernel_fail_steps=(6,),
                        deny_admissions=(2,))
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        policy=Policy(kv_layout="paged"), page_size=8,
                        fault_injector=inj, preempt_backoff=0.005)
    reqs = [eng.submit(p, 5, priority=i % 2, deadline=60.0)
            for i, p in enumerate(prompts)]
    eng.step()
    eng.cancel(reqs[2].rid)                     # cancel a waiter mid-run
    report = eng.run()
    assert report["cancelled"] == 1 and report["quarantined"] == 1
    assert report["kernel_faults"] == 1 and report["crashed_steps"] == 0
    assert report["n_finished"] == 3
    assert (eng.pool.refcount == 0).all() and eng.pool.n_reserved == 0
    _check_consistency(eng, report)
