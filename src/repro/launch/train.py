"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 200 --batch 8 --seq 128 [--ckpt-dir ckpts] \
        [--fail-at 50] [--compress] [--accum 2] [--model-parallel 1]

On this CPU container it trains the reduced configs for real (the
end-to-end example); on a TPU fleet the same driver runs the full
configs — the mesh, sharding rules, checkpointing, supervisor and data
pipeline are identical code paths.
"""

from __future__ import annotations

import argparse
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import tuning
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import ARCH_NAMES, get_config
from repro.core import policy as policy_mod
from repro.core.policy import LEGACY_BACKEND_NAMES, Policy
from repro.data.pipeline import SyntheticLM
from repro.distributed import sharding as shard_rules
from repro.distributed.context import mesh_context
from repro.distributed.fault_tolerance import (FailureInjector, Supervisor)
from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib
from repro.optim.adamw import AdamW, cosine_schedule
from repro.training import train_loop as TL


def build(args):
    cfg = get_config(args.arch, reduced=args.reduced)
    policy = Policy.from_backend(args.backend)
    policy_mod.set_default_policy(policy)
    if policy.autotune == "cached" or args.autotune:
        # Warm the autotuner cache before init/jit so tuned tiles are
        # baked into the compiled train step (both fwd and the VJP
        # GEMMs route through the same chokepoint), keyed by the
        # policy the runtime lookup will resolve to.
        rep = tuning.warm_start(
            cfg, args.batch, args.seq,
            policy=policy if policy.autotune == "cached" else None,
            autotune=args.autotune, backward=True)
        print(tuning.describe_warm_start(rep))
    mesh = mesh_lib.make_host_mesh(args.model_parallel,
                                   devices=args.devices or None)
    if (mesh.size > 1 and policy.backend != "xla"
            and not policy.resolved_interpret):
        # XLA cannot partition a compiled Mosaic kernel across a mesh;
        # each kernel call would need a shard_map (a ROADMAP item)
        raise SystemExit(
            f"--backend {args.backend} runs on one device only; train on "
            f"{mesh.size} devices with --backend xla")
    opt = AdamW(lr=cosine_schedule(args.lr, args.warmup, args.steps),
                clip_norm=1.0)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq, batch=args.batch,
                       seed=args.seed)
    with mesh_context(mesh):
        state = TL.init_state(cfg, opt, jax.random.PRNGKey(args.seed),
                              compress=args.compress)
    state = place_state(state, mesh)
    step_fn = jax.jit(TL.make_train_step(cfg, opt, accum=args.accum,
                                         compress=args.compress),
                      donate_argnums=(0,))
    return cfg, mesh, state, step_fn, data


def place_state(state, mesh):
    """Shard the parameters (and every optimizer / error-feedback tree
    shaped like them) over `mesh` by the rules in distributed.sharding;
    scalars such as the step counter are replicated."""
    psh = shard_rules.shardings_for(
        mesh, shard_rules.param_specs(state.params, mesh))
    like_params = lambda t: jax.device_put(t, psh)
    replicated = NamedSharding(mesh, P())
    return state._replace(
        params=like_params(state.params),
        opt=state.opt._replace(step=jax.device_put(state.opt.step,
                                                   replicated),
                               m=like_params(state.opt.m),
                               v=like_params(state.opt.v)),
        ef=None if state.ef is None
        else state.ef._replace(error=like_params(state.ef.error)))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b",
                    help=f"one of {ARCH_NAMES} or a registered custom config")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="train on the first N devices (0 = all)")
    ap.add_argument("--backend", choices=LEGACY_BACKEND_NAMES, default="xla",
                    help="GEMM backend for every dense contraction; "
                         "constructs the run's execution Policy "
                         "(tuned = pallas with autotuner-cached tiles)")
    ap.add_argument("--autotune", action="store_true",
                    help="tune uncached GEMM shapes at startup")
    ap.add_argument("--compress", action="store_true",
                    help="int8 error-feedback gradient compression")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, nargs="*", default=(),
                    help="inject simulated failures at these steps")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    compile_cache.enable()
    cfg, mesh, state, step_fn, data = build(args)
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M "
          f"devices={len(jax.devices())} mesh={dict(mesh.shape)}")

    losses = []

    def on_metrics(step, metrics, dt):
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0:
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms")

    batch_sharding = NamedSharding(mesh, P("data"))

    def run_step(state, step):
        batch = jax.device_put(data.batch_at(step), batch_sharding)
        with mesh_context(mesh):
            return step_fn(state, batch)

    if args.ckpt_dir:
        ckpt = Checkpointer(args.ckpt_dir, keep=3)
        sup = Supervisor(ckpt, checkpoint_every=args.ckpt_every)
        injector = FailureInjector(tuple(args.fail_at)) if args.fail_at else None
        start = ckpt.latest_step() or 0
        if start:
            state = ckpt.restore(start, state)
            print(f"resumed from checkpoint step {start}")
        t0 = time.time()
        state, step = sup.run_resilient(
            state, run_step, args.steps, start_step=start,
            injector=injector, on_metrics=on_metrics)
        print(f"done at step {step} in {time.time()-t0:.1f}s "
              f"(restarts={sup.restarts}, "
              f"stragglers={len(sup.straggler.flagged)})")
    else:
        t0 = time.time()
        for step in range(args.steps):
            t1 = time.perf_counter()
            state, metrics = run_step(state, step)
            on_metrics(step, metrics, time.perf_counter() - t1)
        print(f"done {args.steps} steps in {time.time()-t0:.1f}s")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses, state


if __name__ == "__main__":
    main()
