"""Speculative decoding: token-exactness vs the non-spec engine,
batched verification, the leftover/residual acceptance rule, positional
KV rollback, and the workload scenario registry.

The exactness oracle is the plain continuous-batching engine: same
config, same prompts, no draft. A greedy spec engine — whatever the
draft proposes, however often it is rejected — must emit exactly the
same token streams, because greedy acceptance degenerates to argmax
agreement per position. The rollback oracle is sharper: two draft
decoders whose caches differ ONLY in stale rows past the pending
position must produce bitwise-identical rounds, proving the stale rows
are dead weight (never attended, always overwritten) rather than
rolled back transactionally.
"""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.policy import Policy
from repro.models import model as M
from repro.serving import (ServingEngine, SpecDecoder, make_sampler,
                           make_trace, residual_distribution,
                           bursty_trace, long_context_trace,
                           synthetic_trace, TRACES)
from repro.serving.faults import FaultInjector
from repro.serving.sampler import Sampler
from repro.serving.workload import get_trace

PROMPT_LENS = [8, 24, 13, 40]     # 13 exercises the bucket remainder
GENS = [5, 4, 7, 6]


def _prompts(cfg, seed=42, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
            for l in lens]


def _run(eng, prompts, gens):
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    rep = eng.run()
    return reqs, rep


def _spy_vstep(eng):
    """Wrap the engine's jitted verify step, counting invocations."""
    calls = []
    orig = eng._vstep

    def spy(*a):
        calls.append(1)
        return orig(*a)

    eng._vstep = spy
    return calls


# -- greedy token-exactness vs the non-spec engine ----------------------

def test_spec_greedy_exact_dense_self_draft_batched_verify():
    """Self-draft (draft params = target params): every greedy proposal
    is what the target would emit, so acceptance is 1.0, and the verify
    spy shows MANY tokens per verify call — the one-batched-forward
    claim, not k decode steps in a trench coat."""
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = _prompts(cfg)

    ref_eng = ServingEngine(cfg, params, max_slots=2, max_len=64)
    ref_reqs, _ = _run(ref_eng, prompts, GENS)

    eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                        draft=(cfg, params), spec_k=4)
    calls = _spy_vstep(eng)
    reqs, rep = _run(eng, prompts, GENS)

    for r, ref in zip(reqs, ref_reqs):
        assert r.generated == ref.generated
    assert rep["n_finished"] == len(reqs)
    assert rep["spec_rounds"] == len(calls) > 0
    assert rep["spec_acceptance_rate"] == 1.0
    # decode tokens (everything past the prefill token) per verify call:
    # batched verification must beat one-token-per-step decode
    decode_tokens = sum(len(r.generated) - 1 for r in reqs)
    assert decode_tokens > len(calls)
    assert rep["tokens_per_step"] > 1.5
    for r in reqs:
        assert r.acceptance_rate == 1.0 and r.draft_proposed > 0


def test_spec_greedy_exact_dense_mismatched_draft():
    """An unrelated random-weights draft is wrong about everything
    (~1/vocab acceptance) — the stream must STILL be token-exact; the
    rejection path re-emits the target argmax at every position."""
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    dcfg = get_config("granite-3-8b", reduced=True)
    dparams = M.init_params(dcfg, jax.random.PRNGKey(7))
    prompts = _prompts(cfg)

    ref_eng = ServingEngine(cfg, params, max_slots=2, max_len=64)
    ref_reqs, _ = _run(ref_eng, prompts, GENS)

    eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                        draft=(dcfg, dparams), spec_k=3)
    reqs, rep = _run(eng, prompts, GENS)
    for r, ref in zip(reqs, ref_reqs):
        assert r.generated == ref.generated
    assert rep["spec_acceptance_rate"] < 0.5


def test_spec_greedy_exact_paged_int8():
    """Spec decoding over the paged int8-KV target: the verify step
    scatters k+1 quantized rows per slot and attends through the page
    table. Exactness oracle is the non-spec engine under the SAME
    policy (int8 KV rounds logits identically in both)."""
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pol = Policy(kv_layout="paged", quant_kv="int8")
    prompts = _prompts(cfg)

    ref_eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                            policy=pol, page_size=8)
    ref_reqs, _ = _run(ref_eng, prompts, GENS)

    eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                        policy=pol, page_size=8,
                        draft=(cfg, params), spec_k=4)
    calls = _spy_vstep(eng)
    reqs, rep = _run(eng, prompts, GENS)
    for r, ref in zip(reqs, ref_reqs):
        assert r.generated == ref.generated
    assert rep["spec_rounds"] == len(calls) > 0
    # draft is dense f32 while the target sees int8-rounded KV, so the
    # two disagree on a few positions — acceptance is high, not 1.0
    assert rep["spec_acceptance_rate"] > 0.5
    assert rep["tokens_per_step"] > 1.5


# -- acceptance rule ----------------------------------------------------

def test_residual_distribution():
    p = np.array([0.5, 0.3, 0.2, 0.0])
    q = np.array([0.1, 0.6, 0.1, 0.2])
    r = residual_distribution(p, q)
    want = np.array([0.4, 0.0, 0.1, 0.0]) / 0.5
    np.testing.assert_allclose(r, want)
    # q covers p pointwise -> no residual mass -> falls back to p
    np.testing.assert_allclose(residual_distribution(p, p), p)


def test_speculative_accept_matches_residual_rule():
    """Mirror the sampler's rng stream and hand-roll the leftover rule:
    accept x_j iff u * q_j(x_j) <= p_j(x_j); first rejection draws from
    norm(max(p_j - q_j, 0)) and stops; full acceptance draws the bonus
    from the last target row."""
    rng = np.random.default_rng(3)
    vocab, k = 8, 4
    sampler = make_sampler("temperature", temperature=1.0, seed=11)
    mirror = np.random.default_rng(11)
    for _ in range(50):
        tl = rng.normal(size=(k + 1, vocab)).astype(np.float32)
        qp = rng.dirichlet(np.ones(vocab), size=k)
        dt = [int(rng.integers(vocab)) for _ in range(k)]

        ps = [sampler.probs(tl[j]) for j in range(k + 1)]
        want, want_acc = [], k
        for j in range(k):
            x, q = dt[j], qp[j]
            if q[x] > 0 and mirror.random() * q[x] <= ps[j][x]:
                want.append(x)
                continue
            res = residual_distribution(ps[j], q)
            want.append(int(mirror.choice(vocab, p=res)))
            want_acc = j
            break
        else:
            want.append(int(mirror.choice(vocab, p=ps[k])))

        got, n_acc = sampler.speculative_accept(tl, dt, qp)
        assert got == want and n_acc == want_acc


def test_speculative_accept_stream_is_distribution_identical():
    """The point of the rule: the emitted first token's distribution
    equals the target distribution, for ANY draft q. Empirical check on
    a small vocab with a deliberately bad draft."""
    vocab, trials = 4, 20000
    rng = np.random.default_rng(0)
    tl = np.array([[1.0, 0.2, -0.5, 0.1]], np.float32)  # k=0 won't do;
    tl = np.vstack([tl, np.zeros((1, vocab), np.float32)])  # k=1 + bonus
    q = np.array([[0.7, 0.1, 0.1, 0.1]])                # skewed draft
    sampler = make_sampler("temperature", temperature=1.0, seed=5)
    p = sampler.probs(tl[0])
    counts = np.zeros(vocab)
    for _ in range(trials):
        x = int(rng.choice(vocab, p=q[0]))              # draft proposes
        emitted, _ = sampler.speculative_accept(tl, [x], q)
        counts[emitted[0]] += 1
    np.testing.assert_allclose(counts / trials, p, atol=0.015)


def test_speculative_accept_greedy_is_argmax_exact():
    sampler = Sampler()
    tl = np.array([[0.0, 2.0, 1.0],     # argmax 1
                   [3.0, 0.0, 1.0],     # argmax 0
                   [0.0, 0.0, 9.0]],    # bonus row, argmax 2
                  np.float32)
    # both drafts right -> all accepted + bonus
    assert sampler.speculative_accept(tl, [1, 0]) == ([1, 0, 2], 2)
    # second draft wrong -> corrected in place, stream stops there
    assert sampler.speculative_accept(tl, [1, 2]) == ([1, 0], 1)
    # first draft wrong -> single corrected token
    assert sampler.speculative_accept(tl, [0, 0]) == ([1], 0)


# -- positional rollback ------------------------------------------------

def test_draft_rollback_is_positional_bitwise():
    """Two draft decoders with identical valid state but DIFFERENT
    stale rows past the pending position must produce bitwise-identical
    next rounds: decoder A ran a full k-draft round (stale rows
    pos+1..pos+k), decoder B a 1-draft round (stale row pos+1 only).
    After the same rejection-correction feed, drafts and the
    newly-written cache rows must agree exactly — stale rows are never
    attended and always overwritten, no transactional rollback."""
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    L = 12
    ctx = rng.integers(0, cfg.vocab, (L,)).astype(np.int32)
    k = 4

    def decoder():
        d = SpecDecoder(cfg, params, max_slots=1, max_len=48, spec_k=k)
        d.admit(0, ctx)
        return d

    pos = np.array([L], np.int32)
    tok = np.array([[5]], np.int32)

    da, db = decoder(), decoder()
    ra, _ = da.draft_round(tok, pos, np.array([k], np.int32))
    rb, _ = db.draft_round(tok, pos, np.array([1], np.int32))
    assert ra[0, 0] == rb[0, 0]         # same first draft either way

    # simulate rejecting draft 0: correction token c becomes pending at
    # pos+1 — overwrite the stale row and draft again from both caches
    c = np.array([[int(ra[0, 0]) ^ 1]], np.int32)   # any token != d0
    pos1 = np.array([L + 1], np.int32)
    kv = np.array([k], np.int32)
    r2a, _ = da.draft_round(c, pos1, kv)
    r2b, _ = db.draft_round(c, pos1, kv)
    np.testing.assert_array_equal(r2a, r2b)

    # the rows both rounds wrote (pos+1 .. pos+1+k) match bitwise even
    # though A's cache held k stale rows there and B's held one
    for name in ("k", "v"):
        xa = np.asarray(da.cache[name])[:, 0, : L + 2 + k]
        xb = np.asarray(db.cache[name])[:, 0, : L + 2 + k]
        np.testing.assert_array_equal(xa, xb)


def test_spec_target_cache_matches_nonspec_rows():
    """After a run full of rejections (mismatched draft), the spec
    engine's target cache valid rows [0, L+gen-1) must match the
    non-spec engine's — every stale verify write was overwritten by the
    corrected stream. Float tolerance, not bitwise: verify attends
    multi-token (chunked) where decode attends one-token (flash). The
    target runs in f32: XLA:CPU accumulates the (k+1)-row verify matmuls
    in another order than one-row decode, and bf16 cache rows would
    then differ by a rounding step, far above this tolerance."""
    cfg = dataclasses.replace(get_config("qwen3-0.6b", reduced=True),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    dcfg = get_config("granite-3-8b", reduced=True)
    dparams = M.init_params(dcfg, jax.random.PRNGKey(9))
    rng = np.random.default_rng(2)
    L, gen = 10, 6
    prompt = rng.integers(0, cfg.vocab, (L,)).astype(np.int32)

    ref = ServingEngine(cfg, params, max_slots=1, max_len=32)
    (ref_req,), _ = _run(ref, [prompt], [gen])
    eng = ServingEngine(cfg, params, max_slots=1, max_len=32,
                        draft=(dcfg, dparams), spec_k=3)
    (req,), rep = _run(eng, [prompt], [gen])

    assert req.generated == ref_req.generated
    assert rep["spec_acceptance_rate"] < 0.5    # rejections did happen
    n_valid = L + gen - 1       # the last emitted token is never fed
    for name in ("k", "v"):
        got = np.asarray(eng.cache[name])[:, 0, :n_valid]
        want = np.asarray(ref.cache[name])[:, 0, :n_valid]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# -- construction / validation ------------------------------------------

def test_spec_validation_errors():
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # chaos injection and speculation are mutually exclusive
    with pytest.raises(ValueError, match="injector"):
        ServingEngine(cfg, params, max_slots=1, max_len=32,
                      draft=(cfg, params),
                      fault_injector=FaultInjector(kernel_fail_steps=(1,)))
    # non-attention target family has no verify_step
    scfg = get_config("mamba2-2.7b", reduced=True)
    sparams = M.init_params(scfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ServingEngine(scfg, sparams, max_slots=1, max_len=32,
                      draft=(cfg, params))
    # the draft cache is dense by design
    with pytest.raises(ValueError, match="dense"):
        SpecDecoder(cfg, params, max_slots=1, max_len=32,
                    policy=Policy(kv_layout="paged"))
    with pytest.raises(ValueError, match="spec_k"):
        SpecDecoder(cfg, params, max_slots=1, max_len=32, spec_k=0)


# -- workload scenario registry -----------------------------------------

def test_traces_registry_dispatch():
    assert set(TRACES) == {"mixed", "prefix_heavy", "bursty",
                           "long_context"}
    cfg = get_config("qwen3-0.6b", reduced=True)
    a = make_trace("mixed", cfg, 5, rng=np.random.default_rng(3), gen=4)
    b = synthetic_trace(cfg, 5, rng=np.random.default_rng(3), gen=4)
    assert len(a) == len(b) == 5
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.prompt, y.prompt)
        assert x.arrival == y.arrival and x.gen == y.gen
    with pytest.raises(ValueError, match="unknown"):
        get_trace("nope")


def test_bursty_trace_groups_and_preserves_rate():
    cfg = get_config("qwen3-0.6b", reduced=True)
    n, rate = 600, 8.0
    tr = bursty_trace(cfg, n, rng=np.random.default_rng(0), gen=4,
                      arrival_rate=rate, burst_mean=4.0, deadline=9.0)
    arr = np.array([t.arrival for t in tr])
    assert (np.diff(arr) >= 0).all()
    # grouped: far fewer distinct arrival instants than requests
    assert len(np.unique(arr)) < n / 2
    # compound thinning is rate-preserving: n arrivals over ~n/rate s
    assert arr[-1] == pytest.approx(n / rate, rel=0.35)
    # deadline is relative to arrival; the item stores the absolute time
    assert all(t.deadline == pytest.approx(t.arrival + 9.0) for t in tr)


def test_long_context_trace_shape():
    cfg = get_config("qwen3-0.6b", reduced=True)
    tr = long_context_trace(cfg, 8, rng=np.random.default_rng(0))
    for t in tr:
        assert 96 <= len(t.prompt) <= 160 and t.gen == 4
