"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--autotune]
    python benchmarks/run.py --autotune        # script form also works

Output contract: ``name,us_per_call,derived`` CSV lines, plus a
machine-readable ``BENCH_<git-rev>.json`` written at the end of every
run (benchmarks.common.write_bench_json) so the perf trajectory is
tracked across PRs — CI uploads it as an artifact.

--autotune runs the tile-autotuning sweep (repro.tuning) for the suites
that support it and persists winners to the tuning cache
($REPRO_TUNING_CACHE, default ~/.cache/repro/tuning.json); without
--only it restricts to those suites so cache population stays fast.
Subsequent runs report the `tuned` backend being served from the cache.
"""

from __future__ import annotations

if __package__ in (None, ""):  # `python benchmarks/run.py`
    import os
    import sys as _sys
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_ROOT, os.path.join(_ROOT, "src")):
        if _p not in _sys.path:
            _sys.path.insert(0, _p)

import argparse
import sys
import traceback

from repro.core import policy as policy_mod
from repro.core.policy import LEGACY_BACKEND_NAMES, Policy
from repro.launch import compile_cache

from benchmarks import (bench_add, bench_arch_step, bench_distributed_gemm,
                        bench_flash_attention, bench_fused_epilogue,
                        bench_matmul, bench_quant_matmul,
                        bench_roofline_table, bench_serving,
                        bench_shared_memory, bench_ssd, common)

SUITES = {
    "matmul": bench_matmul.run,               # Table 2 / Fig 7
    "shared_memory": bench_shared_memory.run,  # Fig 8
    "add": bench_add.run,                      # Fig 9
    "distributed_gemm": bench_distributed_gemm.run,  # S2050 section
    "arch_step": bench_arch_step.run,          # framework-level
    "roofline_table": bench_roofline_table.run,  # deliverable (g)
    "serving": bench_serving.run,              # continuous-batching engine
    "fused_epilogue": bench_fused_epilogue.run,  # fused-flush GEMM/SwiGLU
    "quant_matmul": bench_quant_matmul.run,    # int8-weight GEMM path
    "flash_attention": bench_flash_attention.run,  # fused fwd/bwd + decode
    "ssd": bench_ssd.run,                      # Mamba-2 SSD kernel suite
}

# Suites whose run() accepts autotune= and sweeps the tuner.
AUTOTUNABLE = frozenset({"matmul"})


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=sorted(SUITES), default=None)
    ap.add_argument("--backend", choices=LEGACY_BACKEND_NAMES, default="xla",
                    help="ambient execution Policy for the run; suites "
                         "that sweep backends still pin their own")
    ap.add_argument("--autotune", action="store_true",
                    help="sweep tile configs via repro.tuning and persist "
                         "winners to the tuning cache")
    args = ap.parse_args()
    compile_cache.enable()

    # One typed Policy for the whole run: recorded in the BENCH json
    # (write_bench_json) so a result is reproducible from its file.
    policy_mod.set_default_policy(Policy.from_backend(args.backend))

    print("name,us_per_call,derived")
    failures = []
    for name, fn in SUITES.items():
        if args.only and name != args.only:
            continue
        if args.autotune and not args.only and name not in AUTOTUNABLE:
            continue
        print(f"# --- {name} ---")
        try:
            if args.autotune and name in AUTOTUNABLE:
                fn(autotune=True)
            else:
                fn()
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if common.bench_results():
        # machine-readable perf trajectory: the untagged BENCH_<rev>.json
        # is reserved for full runs; partial runs (--only / --autotune's
        # suite restriction) get a tag so they never clobber it.
        tag = args.only or ("autotune" if args.autotune else None)
        print(f"# wrote {common.write_bench_json(tag=tag)}")
    if failures:
        print("# FAILED suites:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
