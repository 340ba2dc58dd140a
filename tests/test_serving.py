"""Continuous-batching engine: decode token-exactness vs whole-prompt
prefill, mid-stream admission, scheduler FCFS, and the samplers.

The equivalence oracle is the degenerate single-request path: one
batch-1 prefill over the whole prompt followed by scalar-pos lock-step
decode. The engine — bucketed prefill + per-slot vector-pos decode over
a shared slot pool, with requests admitted mid-stream into freed slots
— must emit exactly the same greedy tokens per request.

MoE archs are deliberately absent: expert capacity is contended by
whichever tokens share a decode batch, so continuous batching is not
token-exact vs an isolated run by construction (see serving/engine.py).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving import SamplerConfig, ServingEngine, SlotScheduler, \
    make_sampler
from repro.serving.request import ACTIVE, WAITING, Request


def _reference_generate(cfg, params, prompt, n_new, enc=None):
    """Whole-prompt prefill + scalar-pos greedy decode, batch 1."""
    L = len(prompt)
    a = cfg.attn_chunk
    max_len = L + n_new
    if max_len > a and max_len % a:    # same rounding as the engine
        max_len += a - max_len % a
    cache = M.init_cache(cfg, 1, max_len)
    batch = {"tokens": jnp.asarray(prompt[None])}
    if enc is not None:
        batch["enc_frames"] = jnp.asarray(enc[None])
    logits, cache = M.prefill(cfg, params, batch, cache)
    toks = [int(jnp.argmax(logits[0, -1, :cfg.vocab]))]
    for i in range(n_new - 1):
        tok = jnp.asarray([[toks[-1]]], jnp.int32)
        logits, cache = M.decode_step(cfg, params, tok, jnp.int32(L + i),
                                      cache)
        toks.append(int(jnp.argmax(logits[0, -1, :cfg.vocab])))
    return toks


def _run_engine(cfg, params, prompts, gens, max_slots, max_len, encs=None):
    eng = ServingEngine(cfg, params, max_slots=max_slots, max_len=max_len)
    encs = encs or [None] * len(prompts)
    reqs = [eng.submit(p, g, enc_frames=e)
            for p, g, e in zip(prompts, gens, encs)]
    report = eng.run()
    return eng, reqs, report


# prompt length 13 exercises the bucket-remainder (tail-decode) prefill
CASES = {
    "qwen3-0.6b": [8, 24, 13, 40],    # dense, GQA + qk-norm, RoPE
    "qwen2-vl-2b": [8, 16, 13, 24],   # vlm, M-RoPE degenerate text path
    "mamba2-2.7b": [8, 24, 16, 32],   # ssm, recurrent-state slot copy
}
GENS = [5, 4, 7, 6]


@pytest.mark.parametrize("arch", sorted(CASES))
def test_engine_decode_matches_whole_prompt_prefill(arch):
    cfg = get_config(arch, reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in CASES[arch]]

    # 4 requests over 2 slots: requests 2 and 3 are admitted mid-stream,
    # into slots freed while the other slot keeps decoding.
    eng, reqs, report = _run_engine(cfg, params, prompts, GENS,
                                    max_slots=2, max_len=64)

    assert report["n_finished"] == len(reqs)
    admitted = sorted(r.t_admitted for r in reqs)
    finished = sorted(r.t_finished for r in reqs)
    assert admitted[-1] > finished[0], "expected a mid-stream admission"

    for req, prompt, g in zip(reqs, prompts, GENS):
        want = _reference_generate(cfg, params, prompt, g)
        assert req.generated == want, (arch, req.rid, req.generated, want)
        assert all(0 <= t < cfg.vocab for t in req.generated)


def test_engine_encdec_with_cross_cache_slots():
    cfg = get_config("whisper-tiny", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    lengths = [8, 16, 11]
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in lengths]
    encs = [rng.normal(size=(cfg.enc_ctx, cfg.d_model)).astype(np.float32)
            for _ in lengths]
    eng, reqs, _ = _run_engine(cfg, params, prompts, [4, 3, 5],
                               max_slots=2, max_len=32, encs=encs)
    for req, prompt, g, enc in zip(reqs, prompts, [4, 3, 5], encs):
        assert req.generated == _reference_generate(cfg, params, prompt, g,
                                                    enc)


def test_vector_pos_uniform_batch_matches_scalar():
    """All slots at the same depth: the per-slot vector path must equal
    the scalar lock-step path bit-for-bit (degenerate case)."""
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 2, 16
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)}
    cache = M.init_cache(cfg, B, 24)
    logits, cache = M.prefill(cfg, params, batch, cache)
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    lg_s, c_s = M.decode_step(cfg, params, tok, jnp.int32(S), cache)
    lg_v, c_v = M.decode_step(cfg, params, tok,
                              jnp.full((B,), S, jnp.int32), cache)
    np.testing.assert_array_equal(np.asarray(lg_s), np.asarray(lg_v))
    for a, b in zip(jax.tree.leaves(c_s), jax.tree.leaves(c_v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_inactive_slot_leaves_cache_untouched():
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 2, 8
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32)}
    cache = M.init_cache(cfg, B, 16)
    _, cache = M.prefill(cfg, params, batch, cache)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.asarray([S, -1], jnp.int32)    # slot 1 inactive
    _, new_cache = M.decode_step(cfg, params, tok, pos, cache)
    for old, new in zip(jax.tree.leaves(cache), jax.tree.leaves(new_cache)):
        np.testing.assert_array_equal(np.asarray(old[:, 1]),
                                      np.asarray(new[:, 1]))


def test_engine_flash_decode_token_exact_pallas():
    """Serving under a pallas policy: every single-token step must route
    through the flash_decode kernel (spied at the kernel module), and
    the engine — bucketed prefill + per-slot vector-pos decode + a
    mid-stream admission — must emit exactly the reference tokens
    computed under the SAME policy (whole-prompt prefill + scalar-pos
    lock-step decode), i.e. the batching machinery adds nothing."""
    from repro.core.policy import Policy
    from repro.kernels import flash_attention as fa

    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pol = Policy(backend="pallas", interpret=True)
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in CASES["qwen3-0.6b"]]

    calls = []
    orig = fa.flash_decode

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return orig(*a, **kw)

    fa.flash_decode = spy
    try:
        eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                            policy=pol)
        reqs = [eng.submit(p, g) for p, g in zip(prompts, GENS)]
        report = eng.run()
    finally:
        fa.flash_decode = orig

    assert report["n_finished"] == len(reqs)
    assert calls, "pallas-policy decode never reached the flash kernel"
    assert all(shape[1] == 1 for shape in calls)   # q_len=1 by contract
    admitted = sorted(r.t_admitted for r in reqs)
    finished = sorted(r.t_finished for r in reqs)
    assert admitted[-1] > finished[0], "expected a mid-stream admission"

    with pol.scope():
        for req, prompt, g in zip(reqs, prompts, GENS):
            want = _reference_generate(cfg, params, prompt, g)
            assert req.generated == want, (req.rid, req.generated, want)


def test_engine_ssd_token_exact_pallas():
    """Serving mamba2 under a pallas policy: every prefill must route
    through the ssd_pallas kernel via the ("ssd", "pallas") registry
    entry (spied at the kernel module — the registered impl looks the
    symbol up at call time), and the engine must emit exactly the
    reference tokens computed under the SAME policy."""
    from repro.core.policy import Policy
    from repro.kernels import ssd as ssd_mod

    cfg = get_config("mamba2-2.7b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pol = Policy(backend="pallas", interpret=True)
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in CASES["mamba2-2.7b"]]

    calls = []
    orig = ssd_mod.ssd_pallas

    def spy(x, *a, **kw):
        calls.append(x.shape)
        return orig(x, *a, **kw)

    ssd_mod.ssd_pallas = spy
    try:
        eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                            policy=pol)
        reqs = [eng.submit(p, g) for p, g in zip(prompts, GENS)]
        report = eng.run()
    finally:
        ssd_mod.ssd_pallas = orig

    assert report["n_finished"] == len(reqs)
    assert calls, "pallas-policy prefill never reached the SSD kernel"
    assert all(len(shape) == 4 for shape in calls)   # (B, L, H, P) contract

    with pol.scope():
        for req, prompt, g in zip(reqs, prompts, GENS):
            want = _reference_generate(cfg, params, prompt, g)
            assert req.generated == want, (req.rid, req.generated, want)


def test_engine_short_prompt_conv_tail():
    """The conv-state bug this PR fixed: a prompt SHORTER than
    conv_width - 1 used to yield a mis-shaped conv-state tail from
    mamba_apply(return_state=True). Such prompts must admit cleanly
    through the engine and decode token-exactly vs the reference."""
    cfg = get_config("mamba2-2.7b", reduced=True)   # conv_width = 4
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    w1 = cfg.ssm.conv_width - 1
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in (1, w1 - 1, w1, 8)]
    gens = [4, 4, 4, 4]
    eng, reqs, report = _run_engine(cfg, params, prompts, gens,
                                    max_slots=2, max_len=32)
    assert report["n_finished"] == len(reqs)
    for req, prompt, g in zip(reqs, prompts, gens):
        want = _reference_generate(cfg, params, prompt, g)
        assert req.generated == want, (len(prompt), req.generated, want)


def test_scheduler_fcfs_and_release():
    sched = SlotScheduler(2)
    reqs = [Request(rid=i, prompt=np.zeros(4, np.int32), max_new_tokens=2,
                    arrival_time=float(i)) for i in range(3)]
    for r in reqs:
        sched.submit(r)
    assert sched.next_admission(now=0.5) is reqs[0]
    sched.admit(reqs[0])
    # FCFS: head (rid 1) hasn't arrived yet -> nothing, even though rid 2
    # would not fit anyway; at t=1.0 the head goes in.
    assert sched.next_admission(now=0.5) is None
    assert sched.next_admission(now=1.0) is reqs[1]
    sched.admit(reqs[1])
    assert sched.next_admission(now=5.0) is None      # no free slot
    sched.release(reqs[0].slot)
    assert sched.next_admission(now=5.0) is reqs[2]
    assert sched.n_free == 1 and sched.n_waiting == 1 and sched.n_active == 1


def test_engine_admits_one_request_per_step_while_a_slot_decodes():
    """With no slot decoding a step admits every ready request; once one
    decodes, a step admits one and decodes, so that no token gap spans
    two admissions. The tokens stay those of an isolated run (float32,
    so that no near-tie turns on the batch's shape)."""
    cfg = dataclasses.replace(get_config("qwen3-0.6b", reduced=True),
                              dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=4, max_len=64)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (7, 9, 11, 5)]
    r = [eng.submit(prompts[i], 6) for i in range(2)]
    eng.step()
    assert [q.status for q in r] == [ACTIVE] * 2
    r += [eng.submit(prompts[i], 6) for i in (2, 3)]
    eng.step()
    assert [q.status for q in r] == [ACTIVE] * 3 + [WAITING]
    assert [len(q.generated) for q in r] == [3, 3, 2, 0]
    eng.step()
    assert [len(q.generated) for q in r] == [4, 4, 3, 2]
    eng.run()
    for q, prompt in zip(r, prompts):
        assert q.generated == _reference_generate(cfg, params, prompt, 6)


def test_engine_rounds_max_len_to_attn_chunk():
    """max_len is trace-dependent; a length in (attn_chunk, 2*attn_chunk)
    that is not a chunk multiple must be rounded up, not crash decode."""
    cfg = get_config("qwen3-0.6b", reduced=True)   # attn_chunk = 64
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=1, max_len=86)
    assert eng.max_len == 128
    prompt = np.arange(60, dtype=np.int32) % cfg.vocab
    req = eng.submit(prompt, 10)                   # decodes past pos 64
    eng.run()
    assert req.generated == _reference_generate(cfg, params, prompt, 10)


def test_samplers():
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(64,)).astype(np.float32)
    greedy = make_sampler("greedy")
    assert greedy(logits) == int(np.argmax(logits))
    # top_k >= vocab degenerates to full-vocab sampling, no crash
    assert 0 <= make_sampler("temperature", top_k=100)(logits) < 64
    # temperature + top-k: support restricted to the k best logits
    topk = make_sampler("temperature", temperature=0.8, top_k=4, seed=1)
    allowed = set(np.argsort(logits)[-4:].tolist())
    assert all(topk(logits) in allowed for _ in range(32))
    # same seed -> same trace
    s1 = make_sampler("temperature", seed=5)
    s2 = make_sampler("temperature", seed=5)
    assert [s1(logits) for _ in range(8)] == [s2(logits) for _ in range(8)]
    with pytest.raises(ValueError):
        SamplerConfig(kind="nucleus")
    with pytest.raises(ValueError):
        SamplerConfig(kind="temperature", temperature=0.0)


# ------------------------------------------------------- paged KV cache


def test_kv_pool_prefix_sharing_refcounts_and_release():
    from repro.serving import KVPagePool
    pool = KVPagePool(n_pages=16, page_size=4, max_slots=4,
                      pages_per_slot=4)
    prompt = np.arange(12, dtype=np.int32)          # 3 full pages
    p0 = pool.admit_slot(0, prompt, 4)
    assert len(p0.private) == 3 and not p0.shared
    p1 = pool.admit_slot(1, prompt, 4)
    assert len(p1.shared) == 3 and not p1.private   # whole prompt shared
    for _, phys in p1.shared:
        assert pool.refcount[phys] == 2
    assert pool.sharing_ratio() == 2.0
    pool.release_slot(0)
    for _, phys in p1.shared:
        assert pool.refcount[phys] == 1             # survivor keeps pages
    pool.release_slot(1)
    assert (pool.refcount == 0).all()
    assert pool.n_free == pool.n_pages and pool.n_reserved == 0
    assert (pool.table == -1).all()
    assert not pool._by_hash and not pool._hash_of  # registry drained


def test_kv_pool_copy_on_write_preserves_sharer():
    from repro.serving import KVPagePool
    pool = KVPagePool(n_pages=16, page_size=4, max_slots=4,
                      pages_per_slot=4)
    prompt = np.arange(10, dtype=np.int32)          # 2 full + partial tail
    pool.admit_slot(0, prompt, 4)
    plan = pool.admit_slot(1, prompt, 4)
    tail = dict(plan.shared)[2]                     # shared partial page
    assert pool.refcount[tail] == 2
    # first generated token (pos 10) lands in the shared tail page -> CoW
    w = pool.prepare_write(1, 10)
    assert w is not None and w.kind == "cow"
    assert w.src == tail and w.dst != tail
    assert pool.table[1, 2] == w.dst                # writer retargeted
    assert pool.table[0, 2] == tail                 # sharer untouched
    assert pool.refcount[tail] == 1
    assert pool.stats.cow_copies == 1
    # subsequent writes into now-private pages need no directive
    assert pool.prepare_write(1, 11) is None
    assert pool.prepare_write(0, 10) is None
    # a write past the mapped range allocates a fresh page
    w2 = pool.prepare_write(1, 12)
    assert w2.kind == "alloc" and pool.table[1, 3] == w2.dst


def test_kv_pool_exhaustion_refuses_cleanly():
    from repro.serving import KVPagePool, KVPoolExhausted
    pool = KVPagePool(n_pages=2, page_size=4, max_slots=2,
                      pages_per_slot=4)
    pool.admit_slot(0, np.arange(4, dtype=np.int32), 4)  # 1 page + 1 rsvd
    assert not pool.can_admit(np.arange(8, dtype=np.int32), 4)
    with pytest.raises(KVPoolExhausted):
        pool.admit_slot(1, np.arange(8, dtype=np.int32), 4)
    assert pool.stats.refused == 1
    # refusal leaves state intact: slot 0's reservation still honored
    assert pool.prepare_write(0, 4).kind == "alloc"
    pool.release_slot(0)
    assert pool.n_free == pool.n_pages


def test_engine_cow_copies_bytes_and_leaves_shared_page_intact():
    """Two identical prompts share a partial tail page; the first decode
    step CoWs it for one writer. The copy must carry the prefix rows and
    the original page must keep serving the other slot byte-for-byte."""
    from repro.core.policy import Policy
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pol = Policy(kv_layout="paged")
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32, policy=pol,
                        page_size=8)
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
    r0 = eng.submit(prompt.copy(), 4)
    r1 = eng.submit(prompt.copy(), 4)
    eng.step()          # admits both (tail page shared), decodes pos 10
    assert eng.pool.stats.cow_copies == 1
    pa, pb = int(eng.pool.table[0, 1]), int(eng.pool.table[1, 1])
    assert pa != pb     # tail page diverged
    # prefix rows (pos 8, 9) identical across original and CoW copy, in
    # every layer of both pools
    for name in ("k", "v"):
        pages = np.asarray(eng.cache["pages"][name])
        np.testing.assert_array_equal(pages[:, pa, :2], pages[:, pb, :2])
    eng.run()
    want = _reference_generate(cfg, params, prompt, 4)
    assert r0.generated == want and r1.generated == want


def test_engine_paged_pool_deferral_and_submit_refusal():
    from repro.core.policy import Policy
    from repro.serving import KVPoolExhausted
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pol = Policy(kv_layout="paged")
    eng = ServingEngine(cfg, params, max_slots=3, max_len=64, policy=pol,
                        page_size=8, kv_pool_pages=6)
    # a request that fits max_len but can never fit the 6-page pool is
    # refused at submit, not queued
    with pytest.raises(KVPoolExhausted):
        eng.submit(np.arange(50, dtype=np.int32) % cfg.vocab, 10)
    # three requests whose pages exceed the pool: the third waits for a
    # release even though a scheduler slot is free the whole time
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (24,)).astype(np.int32)
               for _ in range(3)]
    reqs = [eng.submit(p, 6) for p in prompts]
    report = eng.run()
    assert report["n_finished"] == 3
    admitted = sorted(r.t_admitted for r in reqs)
    finished = sorted(r.t_finished for r in reqs)
    assert admitted[-1] > finished[0], "expected a pool-deferred admission"
    for req, prompt in zip(reqs, prompts):
        assert req.generated == _reference_generate(cfg, params, prompt, 6)


@pytest.mark.parametrize("arch", ["qwen3-0.6b", "qwen2-vl-2b"])
def test_engine_paged_int8_token_exact_pallas(arch):
    """Paged + int8-KV serving under the pallas policy must route every
    decode step through the paged flash kernel (spied) and emit exactly
    the tokens of the dense full-precision whole-prompt reference."""
    from repro.core.policy import Policy
    from repro.kernels import flash_attention as fa

    cfg = get_config(arch, reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pol = Policy(backend="pallas", interpret=True,
                 kv_layout="paged", quant_kv="int8")
    rng = np.random.default_rng(42)
    prompts = [rng.integers(0, cfg.vocab, (l,)).astype(np.int32)
               for l in CASES[arch]]

    calls = []
    orig = fa.flash_decode_paged

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return orig(*a, **kw)

    fa.flash_decode_paged = spy
    try:
        eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                            policy=pol, page_size=8)
        reqs = [eng.submit(p, g) for p, g in zip(prompts, GENS)]
        report = eng.run()
    finally:
        fa.flash_decode_paged = orig

    assert report["n_finished"] == len(reqs)
    assert calls, "paged decode never reached the paged flash kernel"
    # kernel-level q is (batch, heads, head_dim): q_len already squeezed
    assert all(len(shape) == 3 for shape in calls)
    assert report["kv_pool"]["cow_copies"] >= 0    # pool report wired up

    ref_pol = Policy(backend="pallas", interpret=True)   # dense f32 KV
    with ref_pol.scope():
        for req, prompt, g in zip(reqs, prompts, GENS):
            want = _reference_generate(cfg, params, prompt, g)
            assert req.generated == want, (arch, req.rid, req.generated,
                                           want)


def test_engine_paged_rejects_unsupported_combinations():
    from repro.core.policy import Policy
    cfg = get_config("mamba2-2.7b", reduced=True)    # ssm: no KV pages
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        ServingEngine(cfg, params, max_slots=2, max_len=32,
                      policy=Policy(kv_layout="paged"))
    cfg2 = get_config("qwen3-0.6b", reduced=True)
    params2 = M.init_params(cfg2, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):                  # int8 KV needs pages
        ServingEngine(cfg2, params2, max_slots=2, max_len=32,
                      policy=Policy(quant_kv="int8"))


def test_serve_cli_mixed_trace_smoke():
    from repro.launch.serve import main as serve_main
    report = serve_main(["--reduced", "--requests", "5", "--max-slots", "2",
                         "--gen", "4", "--prompt-len-min", "8",
                         "--prompt-len-max", "20", "--arrival-rate", "0"])
    assert report["n_finished"] == 5
    assert report["mean_occupancy"] <= 2.0


def test_scheduler_and_request_validation_errors():
    """Bare asserts became ValueErrors that NAME the offender: bad
    arguments fail with an actionable message, not an AssertionError."""
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 100, (8,)).astype(np.int32)
    with pytest.raises(ValueError, match="max_slots"):
        SlotScheduler(0)
    with pytest.raises(ValueError, match="request .*: empty prompt"):
        Request(rid=3, prompt=np.empty((0,), np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        Request(rid=4, prompt=prompt, max_new_tokens=0)
    with pytest.raises(ValueError, match="deadline"):
        Request(rid=5, prompt=prompt, max_new_tokens=4,
                arrival_time=2.0, deadline=1.0)

    sched = SlotScheduler(1)
    req = Request(rid=0, prompt=prompt, max_new_tokens=4)
    sched.submit(req)
    with pytest.raises(ValueError, match="request 0"):   # double submit
        sched.submit(req)
    sched.admit(req)
    with pytest.raises(ValueError, match="request 0"):   # not waiting
        sched.admit(req)
    with pytest.raises(ValueError, match="slot 7"):
        sched.release(7)
    with pytest.raises(ValueError, match="slot 5.*preempt"):
        sched.preempt(5, resume_at=0.0)
    sched.release(req.slot)
    with pytest.raises(ValueError, match="slot 0"):      # double release
        sched.release(0)


def test_kv_pool_release_during_cow_and_double_release():
    """Satellite: releasing a CoW participant mid-divergence leaves the
    survivor's mapping and refcounts intact; slot-level double release
    is a no-op while a page-level double release fails loudly."""
    from repro.serving import KVPagePool
    pool = KVPagePool(n_pages=16, page_size=4, max_slots=4,
                      pages_per_slot=4)
    prompt = np.arange(10, dtype=np.int32)     # 2 full pages + partial tail
    pool.admit_slot(0, prompt, 4)
    plan = pool.admit_slot(1, prompt, 4)
    tail = dict(plan.shared)[2]
    w = pool.prepare_write(1, 10)              # slot 1 CoWs the tail page
    assert w.kind == "cow" and pool.refcount[tail] == 1
    # release the ORIGINAL owner right after the split: the writer's
    # fully-shared prefix pages survive, its private CoW page survives
    pool.release_slot(0)
    for j in (0, 1):
        assert pool.refcount[pool.table[1, j]] == 1
    assert pool.refcount[w.dst] == 1 and pool.refcount[tail] == 0
    assert pool.table[1, 2] == w.dst
    # the survivor keeps writing into its now-private mapping
    assert pool.prepare_write(1, 11) is None
    # slot-level double release: table row already cleared -> no-op
    pool.release_slot(0)
    pool.release_slot(1)
    assert (pool.refcount == 0).all() and pool.n_free == pool.n_pages
    pool.release_slot(1)                       # still a no-op
    # page-level double release means table/refcount divergence: loud
    with pytest.raises(ValueError, match="double release of page"):
        pool._release_page(w.dst)


def test_engine_release_during_cow_device_bytes_intact():
    """Device-checked: cancelling the CoW *survivor's sharer* right
    after the split must not disturb the surviving slot's page bytes —
    its prefix rows still equal the released slot's original page."""
    from repro.core.policy import Policy
    cfg = get_config("qwen3-0.6b", reduced=True)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(cfg, params, max_slots=2, max_len=32,
                        policy=Policy(kv_layout="paged"), page_size=8)
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, cfg.vocab, (10,)).astype(np.int32)
    r0 = eng.submit(prompt.copy(), 4)
    r1 = eng.submit(prompt.copy(), 4)
    eng.step()                                 # tail page CoW'd for slot 1
    assert eng.pool.stats.cow_copies == 1
    pa, pb = int(eng.pool.table[0, 1]), int(eng.pool.table[1, 1])
    before = {n: np.asarray(eng.cache["pages"][n])[:, pb].copy()
              for n in ("k", "v")}
    assert eng.cancel(r0.rid)                  # release slot 0 mid-CoW
    for n in ("k", "v"):                       # survivor's page untouched
        np.testing.assert_array_equal(
            np.asarray(eng.cache["pages"][n])[:, pb], before[n])
    eng.run()
    assert r1.generated == _reference_generate(cfg, params, prompt, 4)
    assert (eng.pool.refcount == 0).all()
    _ = pa                                     # slot 0's page, now freed


def test_workload_bursty_deadlines_priorities():
    from repro.serving import TraceItem, synthetic_trace
    from repro.serving.workload import _arrivals
    cfg = get_config("qwen3-0.6b", reduced=True)
    rng = np.random.default_rng(0)
    trace = synthetic_trace(cfg, 12, rng=rng, len_range=(8, 16), gen=4,
                            arrival_rate=8.0, deadline=2.5,
                            priority_levels=(0, 1, 2), burst_size=4)
    assert all(isinstance(it, TraceItem) for it in trace)
    arr = np.array([it.arrival for it in trace])
    # bursty: groups of 4 arrive at the SAME instant, gaps between groups
    assert len(np.unique(arr)) == 3
    assert (np.diff(arr) >= 0).all()
    # deadline is stored ABSOLUTE (arrival + relative)
    assert all(abs(it.deadline - (it.arrival + 2.5)) < 1e-12
               for it in trace)
    assert {it.priority for it in trace} <= {0, 1, 2}
    # long-run rate preserved: burst gaps scale with the group size
    rng2 = np.random.default_rng(1)
    smooth = _arrivals(rng2, 4000, 8.0, 1)
    rng3 = np.random.default_rng(1)
    bursty = _arrivals(rng3, 4000, 8.0, 4)
    assert abs(smooth[-1] / bursty[-1] - 1.0) < 0.15
    with pytest.raises(ValueError, match="burst_size"):
        synthetic_trace(cfg, 4, rng=rng, burst_size=0)
    with pytest.raises(ValueError, match="priority_levels"):
        synthetic_trace(cfg, 4, rng=rng, priority_levels=())
