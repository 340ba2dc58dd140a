"""Smoke check that the system's main path runs on a TPU.

    python chip_smoke.py             # one chip: GEMM, serving, training
    python chip_smoke.py --chips 4   # four chips: sharded GEMM, 2x2 training

One chip: the paper's tiled GEMM (Listing 4) at 4096^3 in bf16 and f32
plus the naive kernel (Listing 3) as its negative control; a serving
engine on full-width qwen3-0.6b (random weights from --seed) checked
against the same trace on the xla backend; three training steps of
full-width qwen3-0.6b checked against an xla run. Four chips: the
multi-accelerator GEMM schedules (core.distributed.sharded_matmul)
with the tiled kernel on each chip against a one-chip jnp.dot, and
three (data 2 x model 2) training steps on the xla backend (XLA cannot
partition a Pallas kernel) against the same steps on one chip.

Every Pallas kernel runs compiled (never interpreted). Each phase prints
one line with its checks and its device, and raises on a failed check.
The last line of stdout is one JSON object naming the device. Times are
smoke timings of single calls, not benchmark numbers. The script exits
non-zero, printing no result, when JAX finds no TPU or when it is run
outside a checkout of the repository.
"""

from __future__ import annotations

import argparse
import functools
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
GEMM_N = 4096                  # the paper's largest Table 2 size
SHARDED_N = 8192               # multi-chip GEMM edge
GEN = 32                       # tokens generated per serving request
PROMPT_LENS = (1024, 256, 512, 300, 512, 1024, 256, 600)
# bf16 logits, relative to the row's max |logit|. At qwen3-0.6b's depth
# and vocabulary one bf16 forward sits up to ~2.5e-2 from an f32 one (a
# CPU run with the width cut to 256: xla 2.1e-2 prefill, 2.5e-2 first
# decode step); two bf16 implementations may differ by the sum.
LOGIT_TOL = 5e-2
LOSS_RTOL = 5e-3               # bf16 training loss, relative
# Losses after the first update, relative. AdamW's first steps move a
# weight by about lr whatever its gradient's size, so a weight whose
# gradient is rounding noise moves either way in two runs that sum in
# different orders; the losses then part by about 1% at lr 1e-3.
DRIFT_RTOL = 2e-2
GEMM_BOUNDS = {"bfloat16": 1e-2, "float32": 1e-3}   # rel. to max |ref|
# the sharded schedules add up per-chip partial products in bf16 (the
# ring adds four), each rounded: about four bf16 steps of the largest
# entry, against the one of a single GEMM
SHARDED_BOUND = 2e-2


def _tpu_devices(n: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {d.platform} "
                 f"({d.device_kind})")
    if len(devs) < n:
        sys.exit(f"chip_smoke: needs {n} TPU chips; JAX found {len(devs)}")
    return devs


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")


def _where(devs) -> str:
    return f"device={devs[0].device_kind}x{len(devs)}"


def _finish(line: str, checks: list[tuple[bool, str]], devs) -> None:
    """Print a phase's line with its verdict, then fail on any check
    that did not hold (the numbers are printed either way)."""
    failed = [what for ok, what in checks if not ok]
    verdict = (f"{len(checks)} checks passed" if not failed else
               f"FAILED {len(failed)} of {len(checks)} checks: "
               + "; ".join(failed))
    print(f"{line} -- {verdict} {_where(devs)}", flush=True)
    _check(not failed, "; ".join(failed))


def _rel_err(out, ref) -> float:
    import jax.numpy as jnp
    out = out.astype(jnp.float32)
    return float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))


def _seconds_per_call(fn, *args, iters: int = 3) -> float:
    import jax
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2]


def _kernels(lowered) -> list[str]:
    """Pallas kernel names in a lowered program (pallas_call name=)."""
    import re
    return sorted(set(re.findall(r'kernel_name = "(\w+)"',
                                 lowered.as_text())))


# ----------------------------------------------------------------------
# one chip
# ----------------------------------------------------------------------

def phase_gemm(devs, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.policy import Policy
    from repro.kernels import ops

    pallas = Policy(backend="pallas", interpret=False)
    naive = Policy(backend="naive", interpret=False)
    n = GEMM_N
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (n, n), jnp.float32)
    b = jax.random.normal(kb, (n, n), jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    for dtype, bound in GEMM_BOUNDS.items():
        x, y = a.astype(dtype), b.astype(dtype)
        ref = jnp.dot(x.astype(jnp.float32), y.astype(jnp.float32),
                      precision=hi)
        f = jax.jit(functools.partial(ops.matmul, policy=pallas))
        kern = _kernels(f.lower(x, y))
        err = _rel_err(f(x, y), ref)
        secs = _seconds_per_call(f, x, y)
        _finish(f"gemm tiled {dtype} {n}^3: rel_err={err:.3e} "
                f"(bound {bound:.0e}) smoke_s_per_call={secs:.6f} "
                f"kernels={kern}",
                [("matmul_tiled" in kern, "tiled kernel in the program"),
                 (err <= bound, f"rel err {err} > {bound}")], devs)

    # Listing 3, the paper's negative control: largest size that compiles
    f = jax.jit(functools.partial(ops.matmul, policy=naive))
    for m in (n, n // 2, n // 4):
        x, y = a[:m, :m], b[:m, :m]
        try:
            compiled = f.lower(x, y).compile()
        except Exception as e:   # noqa: BLE001 - reported, then smaller
            print(f"gemm naive float32 {m}^3: does not compile "
                  f"({type(e).__name__}: {str(e).splitlines()[0][:120]})",
                  flush=True)
            continue
        ref = jnp.dot(x, y, precision=hi)
        t0 = time.perf_counter()        # one call: it streams B m/8 times
        out = jax.block_until_ready(compiled(x, y))
        secs = time.perf_counter() - t0
        err = _rel_err(out, ref)
        bound = GEMM_BOUNDS["float32"]
        _finish(f"gemm naive float32 {m}^3: rel_err={err:.3e} (bound "
                f"{bound:.0e}) smoke_s_per_call={secs:.6f}",
                [(err <= bound, f"rel err {err} > {bound}")], devs)
        return
    raise AssertionError("naive GEMM compiled at no size")


class _Recorder:
    """Greedy sampler that keeps every logits row it samples from, and
    which request (and stream position) each row belonged to — found
    from the one request whose stream grew by the time of the next
    call."""

    def __init__(self):
        self.engine = None
        self.rows, self.snaps = [], []

    def _counts(self):
        return tuple(len(r.generated) for r in self.engine.requests)

    def __call__(self, row):
        self.snaps.append(self._counts())
        self.rows.append(np.array(row, np.float32))
        return int(np.argmax(row))

    def streams(self) -> list[list[np.ndarray]]:
        """Per request, its logits rows in stream order."""
        snaps = self.snaps + [self._counts()]
        out = [[] for _ in self.engine.requests]
        for i, row in enumerate(self.rows):
            grew = [r for r, (p, q) in enumerate(zip(snaps[i], snaps[i + 1]))
                    if p != q]
            _check(len(grew) == 1 and snaps[i][grew[0]] == len(out[grew[0]]),
                   "sampler calls map one-to-one onto stream positions")
            out[grew[0]].append(row)
        return out


def _serve(cfg, params, prompts, policy, max_len: int, chunk: int):
    from repro.serving import ServingEngine
    rec = _Recorder()
    eng = ServingEngine(cfg, params, max_slots=4, max_len=max_len,
                        sampler=rec, policy=policy, prefill_chunk=chunk)
    rec.engine = eng
    reqs = [eng.submit(p, GEN) for p in prompts]
    t0 = time.perf_counter()
    report = eng.run()
    return eng, reqs, report, rec.streams(), time.perf_counter() - t0


def _rel_margin(row) -> float:
    top2 = np.partition(row, -2)[-2:]
    return float((top2[1] - top2[0]) / np.max(np.abs(row)))


def phase_serve(devs, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.policy import Policy
    from repro.models import model as M
    from repro.training import train_loop as TL

    pallas = Policy(backend="pallas", interpret=False)
    cfg = get_config("qwen3-0.6b")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in PROMPT_LENS]
    # 1024 + 32 rounded up to a multiple of the decode kernel's 512 tile;
    # prompts bucket to multiples of 256 and the rest decode one token
    # at a time
    max_len, chunk = 1536, 256
    eng, reqs, report, streams, secs = _serve(cfg, params, prompts, pallas,
                                              max_len, chunk)
    ref_eng, ref_reqs, _, ref_streams, ref_secs = _serve(
        cfg, params, prompts, Policy(backend="xla"), max_len, chunk)
    checks = [(report["degraded"] is False, "pallas engine degraded"),
              (eng.kernel_faults == 0, f"{eng.kernel_faults} kernel faults"),
              (report["n_finished"] == len(prompts),
               f"{report['n_finished']} requests finished")]

    errs = ([], [])                 # per request: prefill, first decode
    n_tokens = 0
    for r, (got, want) in enumerate(zip(streams, ref_streams)):
        for pos in (0, 1):
            if pos == 1 and reqs[r].generated[0] != ref_reqs[r].generated[0]:
                continue          # decode step fed different tokens
            err = float(np.max(np.abs(got[pos] - want[pos]))
                        / np.max(np.abs(want[pos])))
            errs[pos].append(err)
            checks.append((err <= LOGIT_TOL,
                           f"request {r} position {pos} logits rel err {err}"))
        for pos in range(GEN):
            if _rel_margin(want[pos]) < LOGIT_TOL:
                break             # a near-tie: greedy may pick either
            checks.append((reqs[r].generated[pos] == ref_reqs[r].generated[pos],
                           f"request {r} token {pos} differs from xla's"))
            n_tokens += 1

    # the kernels each jitted step runs (the engine's own step factories)
    prefill = jax.jit(TL.make_prefill(cfg, policy=pallas),
                      donate_argnums=(2,))
    step = jax.jit(TL.make_serve_step(cfg, policy=pallas),
                   donate_argnums=(3,))
    tok = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    cache = lambda b: jax.eval_shape(lambda: M.init_cache(cfg, b, max_len))
    routes, kern_of = [], {}
    for n in sorted(set(PROMPT_LENS)):
        lb = n - n % chunk or n
        if lb not in kern_of:
            kern_of[lb] = _kernels(prefill.lower(
                params, {"tokens": tok(1, lb)}, cache(1)))
        attn = "flash_fwd" if "flash_fwd" in kern_of[lb] else "chunked"
        routes.append(f"{n}:prefill{lb}/{attn}"
                      + (f"+{n - lb}x1tok" if n > lb else ""))
    dec_lowered = step.lower(params, tok(4, 1), tok(4), cache(4))
    dec_kern = _kernels(dec_lowered)
    big = max(kern_of)
    pre_calls = prefill.lower(params, {"tokens": tok(1, big)}, cache(1)) \
        .compile().as_text().count("tpu_custom_call")
    dec_calls = dec_lowered.compile().as_text().count("tpu_custom_call")
    for name in ("matmul_tiled", "gated_matmul_tiled", "flash_fwd"):
        checks.append((name in kern_of[big], f"no {name} in the prefill"))
    for name in ("matmul_tiled", "gated_matmul_tiled", "flash_decode"):
        checks.append((name in dec_kern, f"no {name} in the decode step"))
    fmt = lambda v: "[" + " ".join(f"{e:.3e}" for e in v) + "]"
    _finish(f"serve qwen3-0.6b full width, 4 slots, {len(prompts)} requests "
            f"x {GEN} tokens: logits rel err (tol {LOGIT_TOL:.0e}) prefill "
            f"{fmt(errs[0])} first-decode {fmt(errs[1])}, {n_tokens}/"
            f"{len(prompts) * GEN} greedy tokens checked equal to xla, "
            f"degraded={report['degraded']} "
            f"kernel_faults={eng.kernel_faults}, tpu_custom_calls "
            f"prefill{big}={pre_calls} decode={dec_calls}, "
            f"routes [{' '.join(routes)}], smoke_s pallas={secs:.2f} "
            f"xla={ref_secs:.2f}", checks, devs)


def _train(extra: list[str], seed: int, batch: int):
    from repro.launch import train
    return train.main(["--arch", "qwen3-0.6b", "--steps", "3",
                       "--batch", str(batch), "--seq", "1024",
                       "--seed", str(seed), "--log-every", "1"] + extra)


def phase_train(devs, seed: int) -> None:
    import math
    # batch 4 x 1024 is the largest power of two that fits: a compile
    # for a v5e puts batch 8 at 17.2 GB of the 15.75 GB HBM
    batch = 4
    t0 = time.perf_counter()
    # keep only the losses: each run's state (weights and AdamW moments,
    # 7 GB) must leave the chip before the next run loads
    lp = _train(["--backend", "pallas", "--devices", "1"], seed, batch)[0]
    secs = time.perf_counter() - t0
    gc.collect()
    lx = _train(["--backend", "xla", "--devices", "1"], seed, batch)[0]
    rel = abs(lp[0] - lx[0]) / abs(lx[0])
    _finish(f"train qwen3-0.6b full width, batch {batch} x seq 1024, 3 "
            f"steps: pallas losses {[round(v, 5) for v in lp]} xla step-0 "
            f"{lx[0]:.5f} rel diff {rel:.2e} (tol {LOSS_RTOL:.0e}), "
            f"smoke_s 3 steps incl. compile={secs:.1f}",
            [(all(math.isfinite(v) for v in lp), f"non-finite loss {lp}"),
             (rel <= LOSS_RTOL, f"step-0 loss pallas {lp[0]} vs xla "
              f"{lx[0]}")], devs)


# ----------------------------------------------------------------------
# four chips
# ----------------------------------------------------------------------

def phase_sharded_gemm(devs, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.distributed import sharded_matmul
    from repro.core.policy import Policy
    from repro.launch.mesh import auto_axes

    pallas = Policy(backend="pallas", interpret=False)
    mesh = jax.make_mesh((4,), ("model",), auto_axes(1), devices=devs[:4])
    n = SHARDED_N
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (n, n), jnp.bfloat16)
    b = jax.random.normal(kb, (n, n), jnp.bfloat16)
    on0 = lambda t: jax.device_put(t, devs[0])
    ref = jnp.dot(on0(a), on0(b), preferred_element_type=jnp.float32)
    specs = {"ring": (P("model", None), P("model", None)),
             "column": (P("model", None), P(None, None)),
             "row": (P(None, "model"), P("model", None))}
    parts, checks = [], []
    for sched, (sa, sb) in specs.items():
        x = jax.device_put(a, NamedSharding(mesh, sa))
        y = jax.device_put(b, NamedSharding(mesh, sb))
        with pallas.scope():
            f = jax.jit(lambda u, w, s=sched: sharded_matmul(
                u, w, mesh, schedule=s))
            kern = _kernels(f.lower(x, y))
            out = f(x, y)
        spread = {s.device for s in out.addressable_shards}
        err = _rel_err(jax.device_put(out, devs[0]), ref)
        secs = _seconds_per_call(f, x, y)
        checks += [(len(spread) == 4 and len(x.sharding.device_set) == 4,
                    f"{sched}: operands and result not on 4 chips"),
                   ("matmul_tiled" in kern, f"{sched}: no tiled kernel"),
                   (err <= SHARDED_BOUND, f"{sched} rel err {err}")]
        parts.append(f"{sched} rel_err={err:.3e} smoke_s={secs:.6f}")
    _finish(f"sharded_matmul bf16 {n}^2 on 4 chips vs one-chip jnp.dot "
            f"(bound {SHARDED_BOUND:.0e}): {'; '.join(parts)}", checks, devs)


def phase_train_2x2(devs, seed: int) -> None:
    import jax
    batch, lr, steps = 4, 1e-3, 3
    # XLA partitions the step over the mesh; it cannot partition a
    # Pallas kernel, so the launcher takes the xla backend on a mesh
    extra = ["--backend", "xla", "--warmup", "1", "--lr", str(lr)]
    l1, s1 = _train(extra + ["--devices", "1"], seed, batch)
    p1 = jax.device_get(s1.params)   # on the host: chip 0's HBM is
    del s1                           # needed for the 2x2 run
    gc.collect()
    l4, s4 = _train(extra + ["--devices", "4", "--model-parallel", "2"],
                    seed, batch)
    spread = {d for leaf in jax.tree.leaves(s4.params)
              for d in leaf.sharding.device_set}
    wq = s4.params["layers"]["attn"]["wq"]["w"]
    sharded = (len(spread) == 4 and len(wq.sharding.device_set) == 4
               and not wq.sharding.is_fully_replicated)
    rel = [abs(a - b) / abs(b) for a, b in zip(l4, l1)]
    diffs = jax.tree.map(
        lambda p, q: float(np.max(np.abs(p.astype(np.float32)
                                         - q.astype(np.float32)))),
        jax.device_get(s4.params), p1)
    worst = max(jax.tree.leaves(diffs))
    # an AdamW step moves a weight by about lr, so two runs whose
    # gradients differ only in rounding stay within 2 * lr per step
    bound = 2 * lr * steps
    _finish(f"train qwen3-0.6b xla backend (data 2 x model 2) vs one "
            f"chip, batch {batch} x seq 1024, {steps} steps: losses "
            f"{[round(v, 5) for v in l4]} vs {[round(v, 5) for v in l1]} "
            f"rel diff step 0 {rel[0]:.2e} (tol {LOSS_RTOL:.0e}) later "
            f"{max(rel[1:]):.2e} (tol {DRIFT_RTOL:.0e}), params max "
            f"|diff| {worst:.3e} (bound {bound:.0e}), wq sharding "
            f"{wq.sharding.spec}",
            [(sharded, "parameters not sharded over 4 chips"),
             (rel[0] <= LOSS_RTOL, f"step-0 loss {l4[0]} vs one chip "
              f"{l1[0]}"),
             (max(rel[1:]) <= DRIFT_RTOL, f"losses {l4} vs one chip {l1}"),
             (worst <= bound, f"params max diff {worst} > {bound}")], devs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = _tpu_devices(args.chips)
    src = ROOT / "src"
    sys.path.insert(0, str(src))
    try:
        from repro.core import hw
        from repro.launch import compile_cache
    except ImportError:
        hw = None
    if hw is None or not pathlib.Path(hw.__file__).is_relative_to(src):
        sys.exit("chip_smoke: run it from a checkout of the repository "
                 f"(no src/repro next to {ROOT})")
    compile_cache.enable()
    hw.chip_for(devs[0].device_kind)      # peak table entry, or raise
    phases = ((phase_sharded_gemm, phase_train_2x2) if args.chips == 4
              else (phase_gemm, phase_serve, phase_train))
    for phase in phases:
        phase(devs, args.seed)
        gc.collect()                      # free the phase's device arrays
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
