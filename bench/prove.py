"""Readings that set a cell's limits, taken on the chip at the cell's own
size in one process (the benchmark's own runs never run this):

    python3 bench/prove.py --workload <name> --seeds 12 --seconds 10 \
        [--controls 3] [--faults 3]
    python3 bench/prove.py --workload <name> --seconds 51 \
        --sweep 0.8,1.0,1.2 [--spread 3]

For each program seed: set-up as a run makes it, a window at the cell's
load (after the mix's warm-up stretch), and the check's readings. Then
the controls: the reference with every matrix product's operands in
float8 e4m3 in the program's place. Then the planted faults of
bench/faults.py. Every reading is printed as one JSON line with the
verdict of run.checks under the cell's committed limits.

With --sweep (serving) it instead offers each rate, after the warm-up
stretch, for --seconds, and prints how the backlog and the waits moved
over the window. The knee is the highest rate up to which no window's
backlog grew. With --spread N it then serves, at four fifths of the
knee, N seeds each in its own order, N seeds replaying one schedule,
and the first seed again, and prints the end-to-end metrics of each.
The sweep and the spread skip the check.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

from run import checks, compile_cache, limits  # noqa: E402


def emit(**kw):
    print(json.dumps(kw), flush=True)


def verdict(cell, readings):
    ok, chk = checks(readings, limits(cell.name))
    return {"readings": readings, "correct": ok, "checks": chk}


def renew(box, cell, seed, steps):
    """Replace the engine in `box` (a one-item list, so that no caller
    keeps the old engine's cache and weights alive) by a fresh one with
    the weights of `seed` and the compiled prefill and decode `steps`
    of the first engine (same shapes, same policy). Returns (ref,
    params)."""
    import jax
    import jax.numpy as jnp
    from repro.serving import ServingEngine
    from repro.serving.sampler import Sampler
    from harness import spec, traffic as T
    old = box.pop()
    cfg, pol = old.cfg, old.policy
    del old
    gc.collect()
    tr = cell.traffic
    ref = spec.reference(cell.config)
    params = jax.jit(lambda k: ref.init_params(
        cell.config, k, jnp.dtype(tr["param_dtype"])))(
            jax.random.PRNGKey(T.jax_seed(seed)))
    e = tr["engine"]
    eng = ServingEngine(cfg, params, max_slots=e["max_slots"],
                        max_len=e["max_len"], policy=pol,
                        prefill_chunk=e["prefill_chunk"], sampler=Sampler())
    eng._prefill, eng._step = steps
    box.append(eng)
    return ref, params


def first_engine(cell, seed, seconds):
    from harness import serve, traffic as T
    cfg, _, _, engine = serve.build(cell, seed)
    serve.warm(engine, cell, T.serve_requests(cell.traffic, cfg.vocab,
                                              seed, seconds))
    return [engine], (engine._prefill, engine._step)


def serve_seed(cell, box, steps, seed, seconds, fault=None,
               controls=False, check=True):
    """One window on a fresh engine; returns (record, readings)."""
    from harness import serve, traffic as T
    ref, params = renew(box, cell, seed, steps)
    engine = box[0]
    if fault is not None:
        fault(engine)
    reqs = T.serve_requests(cell.traffic, engine.cfg.vocab, seed, seconds)
    serve.warm(engine, cell, reqs)
    rec = serve.drive(engine, reqs, seconds)
    if not check:
        return rec, {}
    picked = serve.sample(engine, rec, seed, cell.traffic["check"]["requests"])
    del engine
    out = {"program": verdict(cell, serve.readings(cell, ref, params,
                                                   picked))}
    if controls:
        import jax.numpy as jnp
        out["fp8"] = verdict(cell, serve.readings(
            cell, ref, params, picked, mm_dtype=jnp.float8_e4m3fn))
    return rec, out


def e2e(rec):
    from harness import serve, traffic as T
    return {"due": len(rec.due),
            "ttft_p90_ms": 1000 * T.percentile(serve.ttft_s(rec), 90),
            "itl_p99_ms": 1000 * T.percentile(serve.token_gaps_s(rec), 99),
            "tokens_per_s": serve.tokens_in_window(rec) / rec.window_s}


def prove_serve(cell, args):
    import faults as F
    seed0 = args.first_seed
    box, steps = first_engine(cell, seed0, args.seconds)
    for i in range(args.seeds):
        seed = seed0 + i
        rec, out = serve_seed(cell, box, steps, seed, args.seconds,
                              controls=i < args.controls)
        emit(workload=cell.name, seed=seed, kind="program", **e2e(rec), **out)
    for name, fault in (("state_unchanged", F.serve_state_unchanged),
                        ("token_altered", F.serve_token_altered)):
        for i in range(args.faults):
            seed = seed0 + 1000 + i
            rec, out = serve_seed(cell, box, steps, seed, args.seconds,
                                  fault=fault)
            emit(workload=cell.name, seed=seed, kind=name, **out)


def backlog(rec, frac):
    """Requests due by `frac` of the window with no first token yet."""
    t = frac * rec.window_s
    return sum(1 for r, d in rec.due.items()
               if d <= t and not (rec.times[r] and rec.times[r][0] <= t))


def sweep(cell, args):
    """Offer each rate for --seconds after the warm-up stretch; the knee
    is the highest rate up to which the backlog did not grow over the
    window (its end at most two requests above its largest reading in
    the first half)."""
    seed0 = args.first_seed
    arrivals = dict(cell.traffic["arrivals"])
    box, steps, knee, held = None, None, None, True
    for rate in [float(r) for r in args.sweep.split(",")]:
        cell.traffic = dict(cell.traffic, arrivals=dict(arrivals,
                                                        rate_per_s=rate))
        if box is None:
            box, steps = first_engine(cell, seed0, args.seconds)
        rec, _ = serve_seed(cell, box, steps, seed0, args.seconds,
                            check=False)
        b = [backlog(rec, f) for f in (0.0, 0.25, 0.5, 0.75, 1.0)]
        grew = b[-1] > max(b[:3]) + 2
        held = held and not grew
        if held:
            knee = rate
        emit(workload=cell.name, rate=rate, backlog=b, grew=grew,
             **e2e(rec))
    emit(workload=cell.name, knee=knee)
    if not args.spread or knee is None:
        return
    rate = round(0.8 * knee, 2)
    cell.traffic = dict(cell.traffic, arrivals=dict(arrivals,
                                                    rate_per_s=rate))
    base = dict(cell.traffic)
    replay = base.pop("schedule_seed", seed0)
    runs = [(seed0 + i, None) for i in range(args.spread)]
    runs += [(seed0 + 100 + i, replay) for i in range(args.spread)]
    runs += [(seed0, None)]
    for seed, schedule in runs:
        cell.traffic = dict(base, **({"schedule_seed": schedule}
                                     if schedule is not None else {}))
        rec, _ = serve_seed(cell, box, steps, seed, args.seconds,
                            check=False)
        emit(workload=cell.name, rate=rate, seed=seed, schedule=schedule,
             **e2e(rec))


def prove_train(cell, args):
    import jax.numpy as jnp
    import faults as F
    from harness import train
    from repro.training import train_loop as TL
    seed0 = args.first_seed

    def one(seed, factory=None, controls=False):
        _, _, step_fn, state, init = train.build(cell, seed, factory)
        state, prog = train.first_steps(cell, seed, step_fn, state, init)
        state, win = train.window(cell, seed, step_fn, state, args.seconds)
        del state, step_fn
        gc.collect()
        ref = train.reference_readings(cell, seed, init)
        out = {"program": verdict(cell, train.compare(prog, ref)),
               "tokens_per_s": win["steps"] * cell.traffic["batch"]
               * cell.traffic["seq"] / win["window_s"]}
        if controls:
            ref8 = train.reference_readings(cell, seed, init,
                                            mm_dtype=jnp.float8_e4m3fn)
            out["fp8"] = verdict(cell, train.compare(ref8, ref))
        return out

    for i in range(args.seeds):
        emit(workload=cell.name, seed=seed0 + i, kind="program",
             **one(seed0 + i, controls=i < args.controls))
    for i in range(args.faults):
        emit(workload=cell.name, seed=seed0 + 1000 + i, kind="half_batch",
             **one(seed0 + 1000 + i, F.train_half_batch(TL.make_train_step)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_100_000_000)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--spread", type=int, default=0)
    args = ap.parse_args(argv)
    from harness import device as D, spec
    cell = spec.cell(args.workload)
    compile_cache()
    D.accelerators(cell.chips)
    t0 = time.perf_counter()
    if args.sweep:
        sweep(cell, args)
    elif cell.traffic["kind"] == "serve":
        prove_serve(cell, args)
    else:
        prove_train(cell, args)
    emit(workload=cell.name, done=True, seconds=time.perf_counter() - t0)


if __name__ == "__main__":
    main()
