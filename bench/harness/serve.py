"""Runs a serving cell: the program's ServingEngine under open-loop
traffic, measured from the client's side.

Set-up makes the weights on the device from the seed in one jitted
call, builds the engine and warms exactly the prefill shapes and steps
that this cell's requests will use, then serves the mix's warm-up
stretch of arrivals, so that the window opens on a loaded engine. The
window submits each request when it falls due and drives
``engine.step()``; every token is stamped when the step that produced
it returns. After the window the program's state is freed and the
reference scores a seeded sample of the finished requests.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from harness import spec, traffic as T

WINDOW_SPAN = "bench.window"
TRACE_SPAN = "bench.traced"
# a traced run profiles this many seconds from the middle of its window
TRACE_SECONDS = 4.0


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Record:
    """What the window saw: per request due in it its due time, per
    request its token stamps inside it, per engine step ending in it
    what the step did, and the engine's counters when it opened."""

    def __init__(self):
        self.due: Dict[int, float] = {}
        self.prompt_len: Dict[int, int] = {}
        self.times: Dict[int, List[float]] = {}
        self.opened: Dict[str, Any] = {}
        self.opened_at = 0.0          # host clock when the window opened
        # per step: (t_end, [(prompt_len, bucket)] admitted,
        #            [context of each decoded token])
        self.steps: List[tuple] = []
        self.window_s = 0.0
        # the profiled part of a traced window, in window seconds
        self.traced_from: Optional[float] = None
        self.traced_to: Optional[float] = None


def build(cell: spec.Cell, seed: int):
    """Weights from the seed and the engine; returns (cfg, ref, params,
    engine). The engine block of the traffic may set the model's
    attention chunk, which the engine rounds max_len up to."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core.policy import Policy
    from repro.serving import ServingEngine
    from repro.serving.sampler import Sampler

    tr = cell.traffic
    eng = tr["engine"]
    cfg = spec.model_config(cell.config, tr["param_dtype"])
    if "attn_chunk" in eng:
        cfg = dataclasses.replace(cfg, attn_chunk=eng["attn_chunk"])
    ref = spec.reference(cell.config)
    dtype = jnp.dtype(tr["param_dtype"])
    key = jax.random.PRNGKey(T.jax_seed(seed))
    params = jax.jit(lambda k: ref.init_params(cell.config, k, dtype))(key)
    pol = Policy(backend=eng["backend"], interpret=False)
    engine = ServingEngine(cfg, params, max_slots=eng["max_slots"],
                           max_len=eng["max_len"], policy=pol,
                           prefill_chunk=eng["prefill_chunk"],
                           sampler=Sampler())
    return cfg, ref, params, engine


def warm(engine, cell: spec.Cell, requests: List[T.Request]) -> None:
    """Compile (or load from the cache) every program these requests
    will run: one prefill per bucket, the full-batch decode step, the
    slot copy, and the one-token step where a prompt leaves a
    remainder. Warm-up requests are drained before the window."""
    chunk = cell.traffic["engine"]["prefill_chunk"]
    lengths = [len(r.prompt) for r in requests]
    for lb in T.prefill_shapes(cell.traffic, lengths):
        engine.submit(np.zeros(lb, np.int32), 2)
    rem = [n for n in lengths if n >= chunk and n % chunk]
    if rem:
        engine.submit(np.zeros(rem[0], np.int32), 2)
    while engine.scheduler.has_work():
        engine.step()
    import jax
    jax.block_until_ready(engine.cache)


def counters(engine) -> Dict[str, Any]:
    return {"admissions": engine._admissions,
            "prefill_s": engine.prefill_time,
            "decode_steps": engine.decode_steps,
            "decode_slot_steps": engine.decode_slot_steps,
            "n_step_times": len(engine._step_times)}


def drive(engine, requests: List[T.Request], seconds: float,
          trace_dir: Optional[str] = None) -> Record:
    """Open loop, each request submitted when due, one engine step at a
    time, from the first warm-up arrival (due before 0) until the
    window closes at `seconds`. Only the window is recorded. With
    `trace_dir`, the profiler records TRACE_SECONDS from the middle of
    the window."""
    import jax
    from repro.serving.request import TERMINAL
    rec = Record()
    live: Dict[int, Any] = {}
    seen: Dict[int, int] = {}
    chunk = engine.prefill_chunk
    n, i = len(requests), 0
    lead = max(0.0, -requests[0].due_s) if requests else 0.0
    tr_lo = max(0.0, (seconds - TRACE_SECONDS) / 2) if trace_dir else None
    tracing = False
    window = span(WINDOW_SPAN)
    traced = None
    t0 = time.perf_counter() + lead          # the window opens at t0
    while True:
        now = time.perf_counter() - t0
        if now >= 0 and not rec.opened:
            rec.opened = counters(engine)
            rec.opened_at = t0
            window.__enter__()
        if trace_dir is not None:
            if not tracing and traced is None and now >= tr_lo:
                jax.profiler.start_trace(trace_dir, profiler_options=_opts())
                traced = span(TRACE_SPAN)
                traced.__enter__()
                tracing = True
                rec.traced_from = now
            elif tracing and now >= tr_lo + TRACE_SECONDS:
                traced.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
                rec.traced_to = now
        if now >= seconds:
            break
        while i < n and requests[i].due_s <= now:
            with span("bench.submit"):
                r = engine.submit(requests[i].prompt, requests[i].max_new)
            if requests[i].due_s >= 0:
                rec.due[r.rid] = requests[i].due_s
            rec.prompt_len[r.rid] = len(requests[i].prompt)
            rec.times[r.rid] = []
            live[r.rid] = r
            seen[r.rid] = 0
            i += 1
        if not engine.scheduler.has_work():
            nxt = requests[i].due_s - now if i < n else seconds - now
            with span("bench.idle"):
                time.sleep(max(0.0, min(nxt, 0.002)))
            continue
        with span("bench.engine_step"):
            engine.step()
        t = time.perf_counter() - t0
        admitted, decoded = [], []
        for rid, r in list(live.items()):
            k = len(r.generated)
            if k > seen[rid]:
                first = seen[rid]
                if first == 0:
                    p = rec.prompt_len[rid]
                    admitted.append((p, (p - p % chunk) or p))
                    first = 1
                decoded.extend(rec.prompt_len[rid] + j
                               for j in range(first, k))
                if t >= 0:
                    rec.times[rid].extend([t] * (k - seen[rid]))
                seen[rid] = k
            if r.status in TERMINAL:
                del live[rid]
        if t >= 0:
            rec.steps.append((t, admitted, decoded))
    window.__exit__(None, None, None)
    rec.window_s = time.perf_counter() - t0
    if tracing:                       # window shorter than the trace
        traced.__exit__(None, None, None)
        jax.profiler.stop_trace()
        rec.traced_to = rec.window_s
    return rec


def _opts():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


# ----------------------------------------------------------------------
# end-to-end metrics
# ----------------------------------------------------------------------

def ttft_s(rec: Record) -> List[float]:
    """Per request due in the window: due time to first token, or to
    the window's end for one that has none, so a stall cannot hide."""
    return [(rec.times[r][0] if rec.times[r] else rec.window_s) - due
            for r, due in rec.due.items()]


def token_gaps_s(rec: Record) -> List[float]:
    """Every gap between two consecutive tokens of one request, both
    emitted in the window."""
    out: List[float] = []
    for ts in rec.times.values():
        out.extend(np.diff(ts).tolist())
    return out


def tokens_in_window(rec: Record) -> int:
    return sum(len(ts) for ts in rec.times.values())


def model_flops(config, rec: Record) -> float:
    """Operations that the prompts admitted and the tokens decoded in
    the window require."""
    from harness import work as W
    total = 0.0
    for _, admitted, decoded in rec.steps:
        total += sum(W.forward_flops_prompt(config, p) for p, _ in admitted)
        total += sum(W.forward_flops_token(config, c) for c in decoded)
    return total


# ----------------------------------------------------------------------
# the check
# ----------------------------------------------------------------------

def sample(engine, rec: Record, seed: int, k: int) -> List[Any]:
    """A seeded sample of the requests finished in the window, the one
    with the most served tokens always in it."""
    from repro.serving.request import FINISHED
    done = [r for r in engine.requests
            if r.rid in rec.due and r.status == FINISHED]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = T.rng_for(seed, 3)
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False) \
        if rest else []
    return [longest] + [rest[j] for j in sorted(pick)]


def readings(cell: spec.Cell, ref, params, picked, mm_dtype=None) -> Dict:
    """Widest gap of a served token below the reference's best, over the
    sampled requests."""
    gaps, tokens = [], 0
    for r in picked:
        g = ref.served_gaps(cell.config, params, r.prompt,
                            list(r.generated), mm_dtype=mm_dtype)
        gaps.append(g["max_gap"])
        tokens += g["tokens"]
    return {"max_gap": max(gaps) if gaps else float("nan"),
            "tokens_checked": tokens, "requests_checked": len(picked)}


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool,
        devs, t_start: float, trace_dir: str, fault=None) -> Dict[str, Any]:
    """One run. `fault` breaks the built engine (the fault tests); the
    benchmark's own runs pass none."""
    from harness import device as D
    from repro.serving.request import FINISHED, WAITING, ACTIVE
    tr = cell.traffic
    cfg, ref, params, engine = build(cell, seed)
    if fault is not None:
        fault(engine)
    requests = T.serve_requests(tr, cfg.vocab, seed, seconds)
    warm(engine, cell, requests)
    with CompileCounter() as cc:
        rec = drive(engine, requests, seconds,
                    trace_dir if trace else None)
    c0, c1 = rec.opened, counters(engine)
    mem = D.memory_peak(devs)
    step_times = engine._step_times[c0["n_step_times"]:c1["n_step_times"]]
    win = {k: c1[k] - c0[k] for k in ("admissions", "prefill_s",
                                      "decode_steps", "decode_slot_steps")}
    ended = [r for r in engine.requests if r.rid in rec.due]
    failed = sum(1 for r in ended
                 if r.status not in (FINISHED, WAITING, ACTIVE))
    picked = sample(engine, rec, seed, tr["check"]["requests"])
    del engine
    gc.collect()
    read = readings(cell, ref, params, picked)
    return {"setup_s": rec.opened_at - t_start, "record": rec,
            "counters": win, "step_times": step_times, "memory_peak": mem,
            "attempted": len(rec.due), "failed": failed,
            "readings": read, "compiles_in_window": cc.count}


class CompileCounter(contextlib.AbstractContextManager):
    """Counts jit traces and backend compiles while it is open."""

    def __init__(self):
        self.count = 0
        self._open = False

    def _listen(self, event, duration, **kw):
        if self._open and ("jaxpr_trace_duration" in event
                           or "backend_compile_duration" in event):
            self.count += 1

    def __enter__(self):
        import jax
        if not getattr(CompileCounter, "_registered", False):
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._dispatch)
            CompileCounter._registered = True
        CompileCounter._current = self
        self._open = True
        return self

    def __exit__(self, *exc):
        self._open = False
        return False

    @staticmethod
    def _dispatch(event, duration, **kw):
        cur = getattr(CompileCounter, "_current", None)
        if cur is not None:
            cur._listen(event, duration, **kw)
