"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json's ``workloads``) names a configuration file and
a traffic file; everything else is found by those names. Set-up (weights
from the seed, building and warming the system) is timed as setup_s;
the window then runs for --seconds; after it the program's outputs are
checked against the float32 reference. With --trace 0 the last line of
stdout carries the cell's end-to-end metrics, with --trace 1 its
per-layer metrics, read from the profiler's trace of part of the window
and from the program's counters. The numbers compared by the check are
printed with their limits as the last lines of stderr and under the
result's last key, ``checks``.

Exits non-zero, printing no result, where JAX finds no accelerator (or
fewer chips than the cell needs), where the device kind has no peaks in
bench/peaks.json, and where the program is not next to the benchmark.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"
sys.path.insert(0, str(BENCH))


def fail(msg: str, code: int = 2) -> None:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed directory inside the
    checkout, or where JAX_COMPILATION_CACHE_DIR says; every program is
    kept, so that only a cell's first run compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def limits(workload: str) -> dict:
    with open(BENCH / "limits" / f"{workload}.json") as f:
        return json.load(f)


def checks(readings: dict, lim: dict):
    """Each number compared beside its limit; correct when every one is
    finite and within it."""
    out, ok = {}, True
    for name, spec in lim["limits"].items():
        v = float(readings[name])
        good = math.isfinite(v) and v <= spec["limit"]
        ok &= good
        out[name] = {"value": v, "limit": spec["limit"]}
    return ok, out


def end_to_end(name: str, kind: str, res: dict, tr: dict) -> float:
    from harness import serve, traffic as T
    if name == "setup_s":
        return res["setup_s"]
    if kind == "serve":
        rec = res["record"]
        if name == "ttft_p90_ms":
            return 1000.0 * T.percentile(serve.ttft_s(rec), 90)
        if name == "itl_p99_ms":
            return 1000.0 * T.percentile(serve.token_gaps_s(rec), 99)
    if kind == "train" and name == "train_tokens_per_s":
        w = res["window"]
        return w["steps"] * tr["batch"] * tr["seq"] / w["window_s"]
    raise KeyError(f"no end-to-end metric {name!r} for a {kind} cell")


def layer_context(cell, kind: str, res: dict, peak: dict, red) -> dict:
    ctx = {"config": cell.config, "traffic": cell.traffic, "peak": peak,
           "trace": red}
    if kind == "serve":
        rec = res["record"]
        lo, hi = rec.traced_from, rec.traced_to
        ctx.update(record=rec, window_s=rec.window_s,
                   counters=res["counters"], step_times=res["step_times"],
                   traced_steps=[s for s in rec.steps if lo is not None
                                 and lo < s[0] <= hi])
    else:
        ctx.update(window=res["window"], window_s=res["window"]["window_s"])
    return ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the program (src/repro) is not next to the benchmark in "
             f"{ROOT}")
    sys.path.insert(0, str(ROOT / "src"))
    from harness import device as D, spec, trace as TR

    cell = spec.cell(args.workload)
    lim = limits(args.workload)
    compile_cache()
    try:
        devs = D.accelerators(cell.chips)
        peak = D.peaks(devs[0].device_kind)
    except (D.NoAccelerator, KeyError) as e:
        fail(str(e))
    kind = cell.traffic["kind"]
    trace_dir = str(OUT / "trace" / args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if kind == "serve":
        from harness import serve as runner
    elif kind == "train":
        from harness import train as runner
    else:
        fail(f"unknown traffic kind {kind!r}")
    res = runner.run(cell, args.seed, args.seconds, bool(args.trace), devs,
                     T_START, trace_dir)

    device = D.describe(devs, res["memory_peak"])
    out = {"attempted": res["attempted"], "failed": res["failed"]}
    if args.trace:
        red = TR.reduce(TR.load(trace_dir), runner.TRACE_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = layer_context(cell, kind, res, peak, red)
        metrics = {}
        for m in cell.per_layer:
            v = spec.load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = {"device_ops": [list(x) for x in red["device_ops"]],
                            "idle_gaps": [list(x) for x in red["idle_gaps"]]}
    else:
        metrics = {m["name"]: {"value": end_to_end(m["name"], kind, res,
                                                   cell.traffic),
                               "unit": m["unit"]} for m in cell.end_to_end}
    ok, chk = checks(res["readings"], lim)
    ok = ok and res["failed"] == 0
    print(f"compiles in window: {res['compiles_in_window']}; "
          f"failed: {res['failed']} of {res['attempted']}; correct: {ok}",
          file=sys.stderr)
    for name, c in chk.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    result = {"correct": ok, **out, "metrics": metrics, "device": device,
              "readings": res["readings"], "checks": chk}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
