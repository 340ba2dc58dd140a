"""Runs a training cell: the program's jitted train step, built as
the repo's training launcher builds it (``launch/train.build``: the
step of ``training.train_loop.make_train_step`` under the cell's
execution policy, jitted with the state donated, AdamW with a cosine
schedule and global-norm clipping).

Set-up makes the float32 weights on the device from the seed, builds
that one step and its state, and drives it through the first
`check_steps` steps on rows that all differ, through the same call and
feed as the window. What the check compares is read from those steps:
each loss, the first gradient as the optimizer holds it after step 1
(AdamW's first moment over (1 - b1)), and each leaf's change after the
last. The window then continues the same state. Steps are dispatched
without a host sync each; losses are read back in order, keeping at
most `in_flight` steps ahead of the host.
"""

from __future__ import annotations

import collections
import gc
import math
import time
from typing import Any, Dict, List

import numpy as np

from harness import spec, traffic as T
from harness.serve import CompileCounter, span, _opts

WINDOW_SPAN = "bench.window"
TRACE_SPAN = "bench.traced"
TRACE_STEPS = 6


def optimizer(tr):
    from repro.optim.adamw import AdamW, cosine_schedule
    o = tr["optimizer"]
    return AdamW(lr=cosine_schedule(o["lr"], o["warmup"], o["total_steps"]),
                 b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], clip_norm=o["clip_norm"])


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in t])([x for _, x in flat])
    return {jax.tree_util.keystr(p): float(v)
            for (p, _), v in zip(flat, norms)}


def diff_norms(a, b) -> Dict[str, float]:
    import jax
    return leaf_norms(jax.tree.map(lambda x, y: x.astype("float32")
                                   - y.astype("float32"), a, b))


def build(cell: spec.Cell, seed: int, step_factory=None):
    """The step and its state; returns (cfg, ref, step_fn, state, init)."""
    import jax
    import jax.numpy as jnp
    from repro.core import policy as policy_mod
    from repro.core.policy import Policy
    from repro.training import train_loop as TL
    tr = cell.traffic
    cfg = spec.model_config(cell.config, tr["param_dtype"])
    ref = spec.reference(cell.config)
    policy = Policy(backend=tr["backend"], interpret=False)
    policy_mod.set_default_policy(policy)
    opt = optimizer(tr)
    dtype = jnp.dtype(tr["param_dtype"])
    key = jax.random.PRNGKey(T.jax_seed(seed))
    init = jax.jit(lambda k: ref.init_params(cell.config, k, dtype))
    params = init(key)
    state = TL.TrainState(params=params, opt=opt.init(params), ef=None)
    make = step_factory or TL.make_train_step
    step_fn = jax.jit(make(cfg, opt), donate_argnums=(0,))
    return cfg, ref, step_fn, state, (init, key)


def put(cell, seed: int, k: int):
    import jax
    tr = cell.traffic
    b = T.train_batch(seed, k, tr["batch"], tr["seq"],
                      cell.config["program"]["vocab"])
    return {"tokens": jax.device_put(b["tokens"]),
            "labels": jax.device_put(b["labels"])}


def first_steps(cell, seed, step_fn, state, init):
    """Steps 1..check_steps through the window's own call and feed;
    returns (state, program readings)."""
    tr = cell.traffic
    losses, g1 = [], None
    for k in range(1, tr["check_steps"] + 1):
        with span("bench.batch_put"):
            batch = put(cell, seed, k)
        with span("bench.train_step"):
            state, met = step_fn(state, batch)
        with span("bench.loss_read"):
            losses.append(float(met["loss"]))
        if k == 1:
            b1 = tr["optimizer"]["b1"]
            g1 = {p: v / (1 - b1) for p, v in leaf_norms(state.opt.m).items()}
    p0 = init[0](init[1])
    change = diff_norms(state.params, p0)
    del p0
    return state, {"losses": losses, "grad": g1, "change": change}


def window(cell, seed, step_fn, state, seconds: float, trace_dir=None):
    """Steps check_steps+1, ... until the window's time is up. With
    `trace_dir` the profiler records TRACE_STEPS steps, from the third,
    with the queue drained on both sides so that they are whole."""
    import jax
    tr = cell.traffic
    k = tr["check_steps"] + 1
    pending = collections.deque()
    losses: List[float] = []

    def drain():
        with span("bench.loss_read"):
            losses.extend(float(x) for x in pending)
        pending.clear()

    n, traced, tracing = 0, 0, None
    t0 = time.perf_counter()
    with span(WINDOW_SPAN):
        while time.perf_counter() - t0 < seconds:
            if trace_dir is not None and n == 2 and traced == 0:
                drain()
                jax.profiler.start_trace(trace_dir, profiler_options=_opts())
                tracing = span(TRACE_SPAN)
                tracing.__enter__()
            with span("bench.batch_put"):
                batch = put(cell, seed, k)
            with span("bench.train_step"):
                state, met = step_fn(state, batch)
            pending.append(met["loss"])
            if len(pending) > tr["in_flight"]:
                with span("bench.loss_read"):
                    losses.append(float(pending.popleft()))
            k += 1
            n += 1
            if tracing is not None:
                traced += 1
                if traced == TRACE_STEPS:
                    drain()
                    tracing.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing = None
        drain()
        if tracing is not None:              # window ended mid-trace
            tracing.__exit__(None, None, None)
            jax.profiler.stop_trace()
    elapsed = time.perf_counter() - t0
    return state, {"steps": n, "losses": losses, "window_s": elapsed,
                   "traced_steps": traced}


# ----------------------------------------------------------------------
# the reference's readings and the comparison
# ----------------------------------------------------------------------

def adamw_ref(o, params, grads, m, v, step: int):
    """AdamW as the published algorithm, with global-norm clipping and
    the cosine-with-warmup schedule, in float32."""
    import jax
    import jax.numpy as jnp
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / (gnorm + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: o["b1"] * a + (1 - o["b1"]) * g, m, grads)
    v = jax.tree.map(lambda a, g: o["b2"] * a + (1 - o["b2"]) * g * g,
                     v, grads)
    warm, total = o["warmup"], o["total_steps"]
    if step < warm:
        lr = o["lr"] * step / max(warm, 1)
    else:
        frac = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
        lr = o["lr"] * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * frac)))
    bc1, bc2 = 1 - o["b1"] ** step, 1 - o["b2"] ** step

    def upd(p, a, b):
        d = (a / bc1) / (jnp.sqrt(b / bc2) + o["eps"])
        if p.ndim >= 2:
            d = d + o["weight_decay"] * p
        return p - lr * d
    return jax.tree.map(upd, params, m, v), m, v, grads


def reference_readings(cell, seed: int, init,
                       mm_dtype=None) -> Dict[str, Any]:
    """The reference's own first steps from the same weights and rows:
    losses, the first (clipped) gradient's leaf norms, and each leaf's
    change after check_steps steps."""
    import jax
    import jax.numpy as jnp
    tr = cell.traffic
    ref = spec.reference(cell.config)
    o = tr["optimizer"]
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)

    @jax.jit
    def grad_step(params, tokens, labels):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p: ref.loss(cell.config, p, tokens, labels,
                                   mm_dtype))(params)

    params = f32(init[0](init[1]))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, g1 = [], None
    for k in range(1, tr["check_steps"] + 1):
        b = T.train_batch(seed, k, tr["batch"], tr["seq"],
                          cell.config["program"]["vocab"])
        lval, grads = grad_step(params, jnp.asarray(b["tokens"]),
                                jnp.asarray(b["labels"]))
        # params, grads and moments are donated: four float32 copies of
        # the model are all that one chip holds beside the pass
        params, m, v, clipped = jax.jit(
            lambda p, g, m_, v_, s=k: adamw_ref(o, p, g, m_, v_, s),
            donate_argnums=(0, 1, 2, 3))(params, grads, m, v)
        losses.append(float(lval))
        if k == 1:
            g1 = leaf_norms(clipped)
        del grads, clipped
    del m, v
    gc.collect()
    change = diff_norms(params, f32(init[0](init[1])))
    return {"losses": losses, "grad": g1, "change": change}


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The three numbers compared: the largest relative gap of a step's
    loss; the worst leaf's gap between the two first-gradient norms; the
    worst leaf's gap between the two changes after the first steps.
    A leaf's gap is measured against the reference's norm of that leaf
    or of the median leaf, whichever is larger. Leaves whose reference
    gradient is under a thousandth of the median leaf's move under Adam
    by round-off alone and are left out of the change."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad"]
    med = float(np.median(list(g_ref.values())))
    grad_gap = max(abs(prog["grad"][k] - g) / max(g, med)
                   for k, g in g_ref.items())
    moving = [k for k, g in g_ref.items() if g >= 1e-3 * med]
    c_ref = ref["change"]
    cmed = float(np.median([c_ref[k] for k in moving]))
    change_gap = max(abs(prog["change"][k] - c_ref[k]) / max(c_ref[k], cmed)
                     for k in moving)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "leaves_left_out": len(g_ref) - len(moving)}


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, devs,
        t_start: float, trace_dir: str, step_factory=None) -> Dict[str, Any]:
    """One run. `step_factory` replaces the program's step factory (the
    fault tests); the benchmark's own runs pass none."""
    from harness import device as D
    cfg, ref, step_fn, state, init = build(cell, seed, step_factory)
    state, prog = first_steps(cell, seed, step_fn, state, init)
    setup_s = time.perf_counter() - t_start
    with CompileCounter() as cc:
        state, win = window(cell, seed, step_fn, state, seconds,
                            trace_dir if trace else None)
    mem = D.memory_peak(devs)
    del state, step_fn
    gc.collect()
    refr = reference_readings(cell, seed, init)
    read = compare(prog, refr)
    bad = sum(1 for x in prog["losses"] + win["losses"]
              if not math.isfinite(x))
    return {"setup_s": setup_s, "window": win, "memory_peak": mem,
            "attempted": win["steps"], "failed": bad, "readings": read,
            "program": prog, "reference": refr,
            "compiles_in_window": cc.count}
