"""Serving CLI — a thin driver over the continuous-batching engine.

Mixed-length arrival trace (the production shape):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --requests 12 --max-slots 4 --arrival-rate 2

Uniform single batch (the degenerate case: all slots admitted at t=0,
equal lengths — byte-compatible with the pre-engine driver):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b \
        --reduced --batch 4 --prompt-len 32 --gen 16

Chaos mode (deterministic fault injection; the run must SURVIVE):

    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
        --max-slots 2 --kv-layout paged --page-size 8 \
        --chaos-nan-step 3 --chaos-deny-admissions 2

Speculative decoding (draft proposes, target verifies in one batched
forward; --check-exact pins greedy token-exactness vs the plain dense
reference engine):

    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
        --arch qwen1.5-32b --draft qwen3-0.6b --spec-k 4 --check-exact

Named workload scenarios (serving.workload.TRACES):

    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
        --workload bursty --arrival-rate 4

The engine (repro.serving) owns slot scheduling, per-slot prefill and
the shared jitted serve_step with a per-slot `pos` vector; this module
only builds a synthetic workload, constructs the execution Policy from
--backend/--autotune, optionally arms the serving FaultInjector, and
reports per-request latency plus aggregate throughput and goodput.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import tuning
from repro.configs import ARCH_NAMES, get_config
from repro.core import policy as policy_mod
from repro.core.policy import LEGACY_BACKEND_NAMES, Policy
from repro.launch import compile_cache
from repro.models import model as M
from repro.serving import DEFAULT_PREFILL_CHUNK, FaultInjector, \
    ServingEngine, TRACES, make_sampler, make_trace, prefix_heavy_trace, \
    synthetic_trace
from repro.serving.request import FINISHED


def build_workload(cfg, args, rng):
    """Synthetic trace (TraceItem list): a named scenario from the
    workload registry when --workload is set; otherwise prefix-heavy
    chat when --prefix-len is set, mixed-length Poisson when --requests
    is set, else the uniform degenerate batch. Deadlines, priorities
    and bursty arrivals apply throughout."""
    ft = dict(deadline=args.deadline or None,
              priority_levels=tuple(int(p) for p in
                                    args.priority_levels.split(",")),
              burst_size=args.burst_size)
    if args.workload:
        n = args.requests or args.batch
        kw = dict(gen=args.gen, arrival_rate=args.arrival_rate, **ft)
        if args.workload == "bursty":
            # compound-Poisson group sizes replace the fixed burst knob
            kw.pop("burst_size")
        return make_trace(args.workload, cfg, n, rng=rng, **kw)
    if args.prefix_len:
        n = args.requests or args.batch
        return prefix_heavy_trace(cfg, n, rng=rng,
                                  prefix_len=args.prefix_len,
                                  suffix_range=(args.suffix_min,
                                                args.suffix_max),
                                  gen=args.gen,
                                  arrival_rate=args.arrival_rate, **ft)
    if args.requests:
        len_range = (args.prompt_len_min, args.prompt_len_max)
        return synthetic_trace(cfg, args.requests, rng=rng,
                               len_range=len_range, gen=args.gen,
                               arrival_rate=args.arrival_rate, **ft)
    return synthetic_trace(cfg, args.batch, rng=rng,
                           len_range=(args.prompt_len, args.prompt_len),
                           gen=args.gen, arrival_rate=0.0, **ft)


def build_injector(args):
    """FaultInjector from the --chaos-* flags, or None when unarmed."""
    steps = lambda s: tuple(int(x) for x in s.split(",")) if s else ()
    nan_rows = ({int(args.chaos_nan_step): int(args.chaos_nan_slot)}
                if args.chaos_nan_step >= 0 else {})
    corrupt = ({int(args.chaos_corrupt_step): int(args.chaos_corrupt_slot)}
               if args.chaos_corrupt_step >= 0 else {})
    slow = {s: args.chaos_slow_seconds
            for s in steps(args.chaos_slow_steps)}
    kernel = steps(args.chaos_kernel_steps)
    deny = steps(args.chaos_deny_admissions)
    if not (nan_rows or corrupt or slow or kernel or deny):
        return None
    return FaultInjector(nan_rows=nan_rows, corrupt_pages=corrupt,
                         kernel_fail_steps=kernel, slow_steps=slow,
                         deny_admissions=deny)


def check_outputs(cfg, engine, requests):
    """Hard output contract (replaces the vacuous isfinite-on-int check):
    every emitted token is a real vocab id, the engine's aggregate token
    count matches the per-request streams, every request reached a
    terminal state, FINISHED requests generated their full quota, and
    the engine never fell back to xla unless a chaos injector was armed
    (a real kernel error propagates; only injected faults degrade)."""
    assert not engine.degraded or engine.injector is not None, \
        "engine degraded to the xla backend with no fault injector armed"
    for req in requests:
        toks = np.asarray(req.generated)
        if req.status == FINISHED:
            assert toks.size == req.max_new_tokens or (
                engine.eos_id is not None and toks[-1] == engine.eos_id), \
                (req.rid, toks.size, req.max_new_tokens)
        if toks.size:
            assert ((toks >= 0) & (toks < cfg.vocab)).all(), \
                (req.rid, toks.min(), toks.max(), cfg.vocab)
    n_emitted = sum(r.n_generated for r in requests)
    assert n_emitted == engine.tokens_emitted, \
        (n_emitted, engine.tokens_emitted)
    assert engine.scheduler.n_active == 0 and engine.scheduler.n_waiting == 0


def check_chaos(engine, report, requests):
    """Hard survival contract for chaos runs: the engine drained the
    trace with zero crashed steps, nonzero goodput, and terminal-status
    accounting that sums to the trace."""
    assert report["crashed_steps"] == 0, report
    assert report["goodput"] > 0.0, report
    assert report["useful_tokens"] > 0, report
    terminal = (report["n_finished"] + report["expired"]
                + report["cancelled"] + report["quarantined"])
    assert terminal == len(requests), (terminal, len(requests), report)
    inj = report["faults_injected"]
    # an armed injector whose script never fired (e.g. a fault aimed at
    # a slot that never went active) is a chaos run that tested nothing
    # — fail loudly so the script gets fixed, not trusted
    assert sum(inj.values()) > 0, f"no scripted fault fired: {inj}"
    print(f"chaos: survived {sum(inj.values())} injected fault(s) "
          f"({inj}); goodput {report['goodput']:.2f}, "
          f"quarantined {report['quarantined']}, "
          f"preempted {report['preempted']}, "
          f"degraded={report['degraded']}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    # mixed-length trace mode
    ap.add_argument("--requests", type=int, default=0,
                    help="number of requests in the synthetic trace "
                         "(0 = uniform single-batch mode)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0 = burst at t=0)")
    ap.add_argument("--max-slots", type=int, default=0,
                    help="cache slot pool size (default: --batch, or 4)")
    ap.add_argument("--prompt-len-min", type=int, default=8)
    ap.add_argument("--prompt-len-max", type=int, default=48)
    # uniform-batch mode (the degenerate case) + shared knobs
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens to generate per request")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sampler", choices=("greedy", "temperature"),
                    default="greedy")
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--backend", choices=LEGACY_BACKEND_NAMES, default="xla",
                    help="GEMM backend for every dense contraction; "
                         "constructs the engine's execution Policy "
                         "(tuned = pallas with autotuner-cached tiles)")
    ap.add_argument("--autotune", action="store_true",
                    help="tune uncached GEMM shapes at startup")
    # paged KV cache (serving.kv_pool) + prefix-heavy chat workload
    ap.add_argument("--kv-layout", choices=("dense", "paged"),
                    default="dense",
                    help="KV cache layout: per-slot rows, or a shared "
                         "page pool with prefix sharing + copy-on-write")
    ap.add_argument("--quant-kv", choices=("off", "int8"), default="off",
                    help="int8 KV pages (requires --kv-layout paged)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page in paged mode")
    ap.add_argument("--kv-pool-pages", type=int, default=0,
                    help="physical page pool size (0 = dense-equivalent "
                         "capacity: max_slots * pages_per_slot)")
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared system-prompt length: > 0 switches the "
                         "workload to the prefix-heavy chat trace")
    ap.add_argument("--suffix-min", type=int, default=2)
    ap.add_argument("--suffix-max", type=int, default=12)
    ap.add_argument("--workload", choices=sorted(TRACES), default="",
                    help="named scenario from the workload registry "
                         "(overrides the implicit trace selection)")
    # speculative decoding (serving.spec)
    ap.add_argument("--draft", choices=ARCH_NAMES, default="",
                    help="draft model arch: enables speculative decoding "
                         "(draft proposes --spec-k tokens per round, the "
                         "target verifies them in ONE batched forward)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculative round")
    ap.add_argument("--check-exact", action="store_true",
                    help="re-run the trace on a dense f32-KV reference "
                         "engine and assert identical token streams "
                         "(greedy sampling only)")
    # fault-tolerance knobs (workload-side)
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request deadline, seconds after arrival "
                         "(0 = no deadlines)")
    ap.add_argument("--priority-levels", type=str, default="0",
                    help="comma-separated priority levels sampled "
                         "uniformly per request (e.g. '0,1')")
    ap.add_argument("--burst-size", type=int, default=1,
                    help="requests per arrival burst (> 1 = bursty "
                         "arrivals at the same long-run rate)")
    # chaos harness (serving.faults.FaultInjector; all deterministic)
    ap.add_argument("--chaos-nan-step", type=int, default=-1,
                    help="decode step at which to NaN one slot's logits "
                         "row (-1 = off)")
    ap.add_argument("--chaos-nan-slot", type=int, default=0)
    ap.add_argument("--chaos-corrupt-step", type=int, default=-1,
                    help="decode step at which to NaN-poison one slot's "
                         "private KV page (-1 = off; paged mode)")
    ap.add_argument("--chaos-corrupt-slot", type=int, default=0)
    ap.add_argument("--chaos-kernel-steps", type=str, default="",
                    help="comma-separated decode steps raising a "
                         "simulated kernel fault (retry -> xla degrade)")
    ap.add_argument("--chaos-slow-steps", type=str, default="",
                    help="comma-separated decode steps slowed by "
                         "--chaos-slow-seconds (straggler flagging)")
    ap.add_argument("--chaos-slow-seconds", type=float, default=0.05)
    ap.add_argument("--chaos-deny-admissions", type=str, default="",
                    help="comma-separated admission ordinals forced to "
                         "see an exhausted KV pool (preemption path; "
                         "paged mode)")
    args = ap.parse_args(argv)
    if args.check_exact and args.sampler != "greedy":
        ap.error("--check-exact requires --sampler greedy")

    compile_cache.enable()
    cfg = get_config(args.arch, reduced=args.reduced)
    policy = Policy.from_backend(args.backend)
    policy = policy.replace(kv_layout=args.kv_layout, quant_kv=args.quant_kv)
    policy_mod.set_default_policy(policy)
    rng = np.random.default_rng(args.seed)
    work = build_workload(cfg, args, rng)
    injector = build_injector(args)

    max_slots = args.max_slots or (args.batch if not args.requests else 4)
    max_len = max(len(it.prompt) + it.gen for it in work)
    if policy.autotune == "cached" or args.autotune:
        # Warm the cache for the shapes the engine actually executes:
        # admission prefill runs at batch 1 over chunk-bucketed prompt
        # lengths plus one-token remainder steps (engine.prefill_chunk
        # floors each prompt), decode at max_slots rows x 1 token.
        chunk = DEFAULT_PREFILL_CHUNK
        buckets = sorted({(len(it.prompt) - len(it.prompt) % chunk)
                          or len(it.prompt) for it in work} | {1})
        wpol = policy if policy.autotune == "cached" else None
        rep = tuning.warm_start(cfg, 1, buckets, policy=wpol,
                                autotune=args.autotune)
        print(tuning.describe_warm_start(rep))
        # decode attends over the engine's cache depth, which rounds
        # max_len up to an attn_chunk multiple (engine.__init__)
        a = cfg.attn_chunk
        cache_len = max_len + (a - max_len % a if max_len > a
                               and max_len % a else 0)
        rep = tuning.warm_start(cfg, max_slots, 1, policy=wpol,
                                autotune=args.autotune,
                                decode_len=cache_len)
        print(tuning.describe_warm_start(rep))

    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    sampler = make_sampler(args.sampler, temperature=args.temperature,
                           top_k=args.top_k, seed=args.seed)
    draft = None
    if args.draft:
        dcfg = get_config(args.draft, reduced=args.reduced)
        draft = (dcfg, M.init_params(dcfg, jax.random.PRNGKey(args.seed + 1)))
    engine = ServingEngine(cfg, params, max_slots=max_slots,
                           max_len=max_len, sampler=sampler, policy=policy,
                           page_size=args.page_size,
                           kv_pool_pages=args.kv_pool_pages or None,
                           fault_injector=injector,
                           draft=draft, spec_k=args.spec_k)
    requests = [engine.submit(it.prompt, it.gen, arrival_time=it.arrival,
                              deadline=it.deadline, priority=it.priority,
                              enc_frames=it.enc_frames)
                for it in work]
    report = engine.run()

    for r in requests:
        lat = f"{r.latency*1e3:7.1f}ms" if r.latency is not None else "   --  "
        ttft = f"{r.ttft*1e3:7.1f}ms" if r.ttft is not None else "   --  "
        print(f"req {r.rid:3d} prompt={r.prompt_len:3d} "
              f"gen={r.n_generated:3d} ttft={ttft} latency={lat} "
              f"[{r.status}]" + (f" {r.error}" if r.error else ""))
    print(f"arch={cfg.name} slots={max_slots} requests={len(requests)} "
          f"prefill {report['prefill_tok_s']:.1f} tok/s, "
          f"decode {report['decode_tok_s']:.1f} tok/s "
          f"(occupancy {report['mean_occupancy']:.2f}/{max_slots}), "
          f"latency p50 {report['latency_p50_s']*1e3:.0f}ms "
          f"p95 {report['latency_p95_s']*1e3:.0f}ms, "
          f"ttft p50 {report['ttft_p50_s']*1e3:.0f}ms")
    print(f"fault tolerance: goodput {report['goodput']:.2f} "
          f"({report['useful_tokens']}/{engine.tokens_emitted} tokens), "
          f"expired {report['expired']}, cancelled {report['cancelled']}, "
          f"preempted {report['preempted']}, "
          f"quarantined {report['quarantined']}, "
          f"deadline-miss rate {report['deadline_miss_rate']:.2f}, "
          f"stragglers {report['straggler_steps']}")
    if "spec_acceptance_rate" in report:
        print(f"speculative: draft={args.draft} k={args.spec_k}, "
              f"{report['spec_rounds']} rounds, acceptance "
              f"{report['spec_acceptance_rate']:.2f} "
              f"({report['spec_accepted']}/{report['spec_proposed']}), "
              f"tokens/step {report['tokens_per_step']:.2f}, "
              f"draft time {report['draft_time_s']*1e3:.0f}ms")
    if "kv_pool" in report:
        kv = report["kv_pool"]
        print(f"kv pool: {kv['n_pages']} pages x {kv['page_size']} tok, "
              f"peak resident {kv['peak_resident']}, "
              f"peak sharing {kv['peak_sharing_ratio']:.2f}x, "
              f"{kv['shared_page_hits']} shared hits, "
              f"{kv['cow_copies']} CoW copies")
    check_outputs(cfg, engine, requests)
    if injector is not None:
        check_chaos(engine, report, requests)

    if args.check_exact:
        # Same trace, dense rows, full-precision KV, NO draft: the
        # paged / int8 / speculative engine must emit byte-identical
        # greedy token streams vs the plain reference.
        ref_pol = policy.replace(kv_layout="dense", quant_kv="off")
        ref = ServingEngine(
            cfg, params, max_slots=max_slots, max_len=max_len,
            sampler=make_sampler(args.sampler, seed=args.seed),
            policy=ref_pol)
        ref_reqs = [ref.submit(it.prompt, it.gen, arrival_time=it.arrival,
                               enc_frames=it.enc_frames)
                    for it in work]
        ref.run()
        # Under chaos, requests the injector terminated early carry
        # deliberately partial streams; every request that FINISHED must
        # still match the fault-free dense reference token-for-token.
        n_cmp = 0
        for a, b in zip(requests, ref_reqs):
            if injector is not None and a.status != FINISHED:
                continue
            assert a.generated == b.generated, \
                (a.rid, a.generated, b.generated)
            n_cmp += 1
        assert n_cmp > 0, "no finished requests to compare"
        if "kv_pool" in report and args.prefix_len:
            assert report["kv_pool"]["peak_sharing_ratio"] > 1.0, \
                report["kv_pool"]
        print(f"check-exact: {n_cmp} token streams match the "
              f"dense reference")

    if not args.requests:
        # degenerate mode keeps the pre-engine return contract:
        # (batch, gen) int32 token grid, submission order
        gen = np.stack([np.asarray(r.generated, np.int32)
                        for r in requests])
        print("generated ids[0,:16]:", gen[0, :16].tolist())
        return gen
    return report


if __name__ == "__main__":
    main()
