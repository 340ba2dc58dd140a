"""Continuous-batching serving engine over a fixed pool of cache slots.

Request lifecycle (one slot = one batch row of the jitted step):

        submit            slot free & arrived          len == max_new
    req ------> WAITING ----------------------> ACTIVE --------------> FINISHED
                          admit = prefill(1xL)         evict: pos[slot] = -1,
                          + copy into slot row         slot back in free pool

Every decode step runs ONE jitted serve_step over ALL slots with a
per-slot position vector `pos: (S,) int32` — heterogeneous requests
(different prompt lengths, admitted at different times) share the same
compiled program. Inactive slots carry pos = -1: the model masks their
cache writes and their logits are discarded, so idle rows cost FLOPs
but never correctness (the fixed batch shape is what keeps one XLA
executable serving the whole trace).

Admission prefills the prompt at batch size 1 into a fresh single-slot
cache, then copies that cache into the slot's row of the pooled cache.
Prompt lengths are bucketed down to a multiple of `prefill_chunk` for
the jitted prefill (bounding compile count under mixed-length traffic);
the 0..chunk-1 remainder tokens run through the same serve_step at
batch 1 (jitted as `admit_token_step`, so a profile tells the two
apart), so the admitted state is exactly what a full-length prefill
would have produced — tests/test_serving.py asserts token-exactness.

Spans (repro.core.spans, recorded only when turned on): every step()
is a `repro.engine.step`, split into `.schedule`, `.admit` (`.prefill`,
`.token_steps`, `.slot_copy`, `.first_token`) and `.decode`
(`.dispatch`, `.logits_read`, `.sample`); `repro.engine.queue` is a
request's wait from submission (or its later arrival_time) to its first
admission. prefill_time and the decode step times are read from the
same stamps as the admit and decode spans.

Family notes: attention caches copy per-slot KV rows; ssm/hybrid copy
recurrent state rows (their "position" is implicit in the state, the
pos vector only drives the attention members and bookkeeping); an
interleaved hybrid (Granite 4.0-H) copies both, each leaf along its own
slot axis. The admit and slot-copy spans carry the slot's `state_bytes`
(recurrent state) and `kv_bytes` (its KV row), the decode span the
`state_bytes` of every slot's state, which each step reads and writes. MoE is
served but not token-exact vs. an isolated run by construction: expert
capacity is contended by whichever tokens share the decode batch.

Paged mode (policy.kv_layout="paged"): the per-slot cache rows are
replaced by a fixed pool of KV pages plus a per-slot page table
(models.init_paged_cache + serving.kv_pool). Admission still prefills
into a dense batch-1 sub-cache, but the copy-out lands page by page
through the `_write_page` chokepoint — and pages whose content-hash
matches an already-resident prompt page are *shared* instead of
written. Decode writes go through `pool.prepare_write` first, which
turns a write into a shared page into a copy-on-write. Admission is
additionally gated on the pool guaranteeing the request's full write
range, so a decode step can never run out of pages mid-stream.
policy.quant_kv="int8" stores pages as int8 + per-(position, head)
scales, quantized at page write; the decode kernel dequantizes on its
f32 accumulator.

Fault tolerance (docs/ARCHITECTURE.md §Fault tolerance):

  * Deadlines + cancellation — waiters whose `deadline` passed are
    dropped (EXPIRED) before they ever burn a slot; `cancel(rid)`
    releases a waiting or mid-decode request immediately, refcount-safe
    against prefix-shared and mid-CoW KV pages.
  * Preemption — when the FCFS head cannot be admitted because the page
    pool is exhausted, the lowest-priority / youngest active slot is
    preempted instead of stalling the head: its private pages return to
    the pool (shared prefix pages survive via refcounts), the victim is
    requeued and later *resumed* by re-prefilling prompt + generated so
    far (token-identical continuation under greedy sampling). A
    per-request retry budget plus exponential resume backoff bound the
    churn.
  * Numeric guards — after every decode step a sentinel scans each
    active row's logits; a non-finite row quarantines ONLY that slot
    (terminal QUARANTINED status + diagnostic) while the rest of the
    batch keeps decoding. Injected kernel faults (SimulatedKernelFault
    from an armed chaos injector) are retried, and once they repeat
    the engine's policy degrades to the `xla` registry backend with a
    once-per-process warning. A real kernel error is never absorbed:
    it propagates out of the step, so a backend that cannot compile or
    run on the device fails the run instead of hiding behind xla.
  * Chaos harness — a `serving.faults.FaultInjector` drives all of the
    above at scripted step counts for deterministic tests and the
    `--chaos-*` serve CLI flags.
"""

from __future__ import annotations

import math
import time
import warnings
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as _pol
from repro.core import precision as _prec
from repro.core import spans
from repro.distributed.fault_tolerance import StragglerDetector
from repro.models import model as M
from repro.serving.faults import FaultInjector, SimulatedKernelFault
from repro.serving.kv_pool import KVPagePool, KVPoolExhausted
from repro.serving.request import (ACTIVE, CANCELLED, FINISHED, QUARANTINED,
                                   TERMINAL, WAITING, Request, percentile)
from repro.serving.sampler import Sampler
from repro.serving.scheduler import SlotScheduler
from repro.serving.spec import SpecDecoder
from repro.training import train_loop as TL

#: Default tokens per KV page in paged mode. 16 rows keeps a page's K
#: block a single sublane-aligned tile at head_dim 64-128 while keeping
#: internal fragmentation (half a page per request on average) small.
DEFAULT_PAGE_SIZE = 16

# Admission prefill buckets prompt lengths down to a multiple of this
# (remainder tokens run through one-token steps) to bound compile count.
DEFAULT_PREFILL_CHUNK = 8

# Degrading a faulting kernel backend to xla warns once per process.
_DEGRADE_WARNED = False


def _slot_axis(big_shape, small_shape, name: str = "cache leaf"):
    """Axis along which a cache leaf indexes slots: the axis where the
    max_slots-sized cache differs from the 1-slot cache. None = the leaf
    has no slot axis distinguishable (max_slots == 1: replace whole)."""
    diffs = [i for i, (a, b) in enumerate(zip(big_shape, small_shape))
             if a != b]
    if not diffs:
        return None
    if len(diffs) != 1:
        raise ValueError(
            f"cannot locate the slot axis of {name}: pooled shape "
            f"{tuple(big_shape)} differs from the 1-slot shape "
            f"{tuple(small_shape)} on axes {diffs}; per-slot admission "
            f"copies need exactly one differing (slot) axis")
    return diffs[0]


def _slot_bytes(small) -> Dict[str, int]:
    """Bytes of one slot's cache by kind: `kv_bytes` (its K/V leaves)
    and `state_bytes` (every other leaf: recurrent state), from the
    shapes of a 1-slot cache."""
    out = {"state_bytes": 0, "kv_bytes": 0}
    for path, leaf in jax.tree_util.tree_flatten_with_path(small)[0]:
        kind = "kv_bytes" if getattr(path[-1], "key", None) in ("k", "v") \
            else "state_bytes"
        out[kind] += leaf.size * leaf.dtype.itemsize
    return out


class ServingEngine:
    def __init__(self, cfg, params, *, max_slots: int, max_len: int,
                 sampler: Optional[Sampler] = None,
                 prefill_chunk: int = DEFAULT_PREFILL_CHUNK,
                 eos_id: Optional[int] = None, policy=None,
                 page_size: int = DEFAULT_PAGE_SIZE,
                 kv_pool_pages: Optional[int] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 preempt_retry_budget: int = 2,
                 preempt_backoff: float = 0.02,
                 kernel_fault_threshold: int = 2,
                 max_step_retries: int = 2,
                 draft=None, spec_k: int = 4,
                 draft_policy=None,
                 draft_sampler: Optional[Sampler] = None):
        self.cfg = cfg
        # Execution policy for every jitted step this engine compiles —
        # captured once at construction (explicit arg > ambient default)
        # so a later ambient change can never retrace a live engine
        # under different kernels. The ONE exception is the engine's own
        # fault handler, which may degrade backend -> "xla" after
        # repeated kernel faults (see _degrade_to_xla).
        self.policy = _pol.resolve(policy)
        paged = self.policy.kv_layout == "paged"
        if paged and cfg.family not in ("dense", "moe", "vlm"):
            raise ValueError(
                f"kv_layout='paged' supports attention-cache families "
                f"(dense/moe/vlm), not {cfg.family!r}")
        if self.policy.quant_kv != "off" and not paged:
            raise ValueError(
                "quant_kv applies to KV pages; it requires "
                "kv_layout='paged' (dense caches stay full precision)")
        # quant="int8" policies quantize the dense weights ONCE here —
        # every jitted step then streams int8 weight tiles (the 2-4x
        # weight-traffic cut is the whole point of serving quantized);
        # embeddings and routers stay full precision (model.QUANT_EXCLUDE).
        if self.policy.quant == "int8":
            params = M.quantize_params(params)
        self.params = params
        self.max_slots = max_slots
        # chunked_attention requires kv lengths beyond attn_chunk to be
        # chunk multiples; max_len is trace-dependent, so round it up.
        # Paged mode additionally needs a whole number of pages so the
        # admission page copies never straddle the sub-cache end.
        a = cfg.attn_chunk
        if paged:
            m = math.lcm(a, page_size) if max_len > a else page_size
            if max_len % m:
                max_len += m - max_len % m
        elif max_len > a and max_len % a:
            max_len += a - max_len % a
        self.max_len = max_len
        self.prefill_chunk = max(1, prefill_chunk)
        self.eos_id = eos_id
        self.sampler = sampler or Sampler()
        self.scheduler = SlotScheduler(max_slots)
        self.injector = fault_injector
        self.preempt_retry_budget = preempt_retry_budget
        self.preempt_backoff = preempt_backoff
        self.kernel_fault_threshold = kernel_fault_threshold
        self.max_step_retries = max_step_retries
        self.straggler = StragglerDetector()

        self.page_size = page_size if paged else None
        self.pool: Optional[KVPagePool] = None
        if paged:
            pages_per_slot = max_len // page_size
            # Default pool = the dense layout's token capacity; prefix
            # sharing and early-exit requests then turn unused rows into
            # admission headroom instead of stranded slot tail.
            n_pages = (max_slots * pages_per_slot if kv_pool_pages is None
                       else kv_pool_pages)
            self.pool = KVPagePool(n_pages, page_size, max_slots,
                                   pages_per_slot)
            self.cache = M.init_paged_cache(
                cfg, n_pages, page_size, max_slots, pages_per_slot,
                quant_kv=self.policy.quant_kv)
            self._table_version = self.pool.version
            self._write_pg = jax.jit(self._write_page, donate_argnums=(0,))
            self._copy_pg = jax.jit(self._copy_page, donate_argnums=(0,))
        else:
            self.cache = M.init_cache(cfg, max_slots, max_len)
            flat, self._treedef = jax.tree_util.tree_flatten_with_path(
                self.cache)
            small = M.init_cache(cfg, 1, max_len)
            self._slot_axes = [
                _slot_axis(b.shape, s.shape,
                           name=jax.tree_util.keystr(path))
                for (path, b), s in zip(flat, jax.tree.leaves(small))]
            self._write = jax.jit(self._write_slot, donate_argnums=(0,))
        self._slot_bytes = _slot_bytes(jax.eval_shape(
            lambda: M.init_cache(cfg, 1, self.max_len)))

        # -- speculative decoding (serving.spec) ------------------------
        # draft=(draft_cfg, draft_params) turns every decode step into a
        # draft round (spec_k cheap draft steps) plus ONE batched target
        # verification over all k+1 positions (model.verify_step); the
        # leftover/residual acceptance rule keeps the emitted stream
        # distribution-identical — token-exact under greedy sampling.
        self.spec: Optional[SpecDecoder] = None
        self.spec_k = spec_k
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        if draft is not None:
            if cfg.family not in ("dense", "moe", "vlm"):
                raise ValueError(
                    f"speculative decoding needs a verify-capable target "
                    f"(dense/moe/vlm), not {cfg.family!r}")
            if fault_injector is not None:
                raise ValueError(
                    "speculative decoding and the chaos injector are "
                    "mutually exclusive: the injector's step hooks assume "
                    "one token per slot per step")
            draft_cfg, draft_params = draft
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab} != target vocab "
                    f"{cfg.vocab}: acceptance compares distributions "
                    f"over the same token space")
            # The draft runs under its own policy (default: the target's,
            # forced dense-KV — positional rollback needs no pages); a
            # different quant/backend keeps its tuning cache separate via
            # the policy fingerprint.
            dpol = _pol.resolve(draft_policy) if draft_policy is not None \
                else self.policy.replace(kv_layout="dense", quant_kv="off")
            self.spec = SpecDecoder(
                draft_cfg, draft_params, max_slots=max_slots,
                max_len=self.max_len, spec_k=spec_k, policy=dpol,
                sampler=draft_sampler, prefill_chunk=self.prefill_chunk)

        self._build_steps()

        # per-slot device-mirrored state (pos < 0 = inactive slot)
        self._tokens = np.zeros((max_slots, 1), np.int32)
        self._pos = np.full((max_slots,), -1, np.int32)

        self.requests: List[Request] = []
        self._next_rid = 0
        self._t0_ns: Optional[int] = None
        # aggregate counters; prefill_time and _step_times come from the
        # stamps of the repro.engine.admit and .decode spans
        self.prefill_tokens = 0
        self.prefill_time = 0.0
        self.decode_steps = 0
        self.decode_slot_steps = 0     # sum of active slots over steps
        self.tokens_emitted = 0
        self.peak_occupancy = 0
        self._step_times: List[float] = []
        # fault-tolerance counters
        self.expired = 0
        self.cancelled = 0
        self.preempted = 0             # preemption EVENTS (req may repeat)
        self.quarantined = 0
        self.kernel_faults = 0
        self.crashed_steps = 0         # steps that exhausted their retries
        self.degraded = False
        self._admissions = 0           # successful admissions (ordinal)

    def _build_steps(self) -> None:
        """(Re)compile the jitted prefill/serve steps under the current
        policy — called at construction and again by _degrade_to_xla."""
        self._prefill = jax.jit(TL.make_prefill(self.cfg,
                                                policy=self.policy),
                                donate_argnums=(2,))
        serve_step = TL.make_serve_step(self.cfg, policy=self.policy)
        self._step = jax.jit(serve_step, donate_argnums=(3,))

        # the same step at batch 1 for admission's remainder tokens, under
        # a name of its own in the profiler's trace (jit_admit_token_step)
        def admit_token_step(params, token, pos, cache):
            return serve_step(params, token, pos, cache)
        self._admit_step = jax.jit(admit_token_step, donate_argnums=(3,))
        if self.spec is not None:
            self._vstep = jax.jit(TL.make_verify_step(self.cfg,
                                                      policy=self.policy),
                                  donate_argnums=(4,))

    # -- cache slot copy ----------------------------------------------
    def _write_slot(self, cache, sub, slot):
        leaves = jax.tree.leaves(cache)
        subs = jax.tree.leaves(sub)
        out = []
        for leaf, s, ax in zip(leaves, subs, self._slot_axes):
            if ax is None:
                out.append(s.astype(leaf.dtype))
                continue
            start = [0] * leaf.ndim
            start[ax] = slot
            out.append(jax.lax.dynamic_update_slice(
                leaf, s.astype(leaf.dtype), tuple(start)))
        return jax.tree.unflatten(self._treedef, out)

    # -- page pool copies (paged layout) -------------------------------
    def _write_page(self, cache, sub, phys, start):
        """Copy `page_size` prefilled rows starting at `start` out of the
        dense batch-1 sub-cache into physical page `phys` of every
        layer's pool — THE admission-copy chokepoint for the paged
        layout (quantizing here when the policy asks for int8 pages)."""
        ps = self.page_size
        pages = dict(cache["pages"])
        z = jnp.int32(0)         # uniform index dtype (x64-safe)
        phys = jnp.int32(phys)
        for name in ("k", "v"):
            rows = jax.lax.dynamic_slice_in_dim(
                sub[name][:, 0], start, ps, axis=1
            ).transpose(0, 2, 1, 3)                      # (L, Hkv, ps, Dh)
            if "ks" in pages:
                q, s = _prec.quantize_kv(rows)           # s: (L, Hkv, ps)
                pages[name] = jax.lax.dynamic_update_slice(
                    pages[name], q[:, None], (z, phys, z, z, z))
                pages[name + "s"] = jax.lax.dynamic_update_slice(
                    pages[name + "s"], s[:, None], (z, phys, z, z))
            else:
                pages[name] = jax.lax.dynamic_update_slice(
                    pages[name], rows[:, None].astype(pages[name].dtype),
                    (z, phys, z, z, z))
        return {"pages": pages, "table": cache["table"]}

    def _copy_page(self, cache, src, dst):
        """Device copy page src -> dst in every layer's pool (CoW)."""
        pages = {}
        z = jnp.int32(0)         # uniform index dtype (x64-safe)
        src, dst = jnp.int32(src), jnp.int32(dst)
        for name, leaf in cache["pages"].items():
            page = jax.lax.dynamic_slice(
                leaf, (z, src) + (z,) * (leaf.ndim - 2),
                (leaf.shape[0], 1) + leaf.shape[2:])
            pages[name] = jax.lax.dynamic_update_slice(
                leaf, page, (z, dst) + (z,) * (leaf.ndim - 2))
        return {"pages": pages, "table": cache["table"]}

    def _sync_table(self) -> None:
        """Mirror the host page table to the device cache when the pool
        has mutated it since the last jitted step."""
        if self.pool.version != self._table_version:
            self.cache = {"pages": self.cache["pages"],
                          "table": jnp.asarray(self.pool.table)}
            self._table_version = self.pool.version

    # -- submission ----------------------------------------------------
    def submit(self, prompt, max_new_tokens: int, *, arrival_time: float = 0.0,
               deadline: Optional[float] = None, priority: int = 0,
               enc_frames=None) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"request (prompt {prompt.size} + gen {max_new_tokens}) "
                f"exceeds the engine's max_len {self.max_len}")
        if self.cfg.family == "encdec" and enc_frames is None:
            raise ValueError("encdec requests need enc_frames")
        if self.pool is not None:
            # Infeasible-even-on-an-empty-pool requests are refused here,
            # cleanly, before they can wedge the FCFS queue; transient
            # fullness just defers admission (see step()).
            need = -(-(prompt.size + max_new_tokens) // self.page_size)
            if need > self.pool.n_pages:
                raise KVPoolExhausted(
                    f"request needs {need} KV pages (prompt {prompt.size} "
                    f"+ gen {max_new_tokens} @ page_size {self.page_size}) "
                    f"but the pool only has {self.pool.n_pages}")
        req = Request(rid=self._next_rid, prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      arrival_time=arrival_time, deadline=deadline,
                      priority=priority, enc_frames=enc_frames)
        self._next_rid += 1
        req.t_submitted = self._now()
        self.requests.append(req)
        self.scheduler.submit(req)
        return req

    # -- clock ---------------------------------------------------------
    def _now(self) -> float:
        return self._clock(time.perf_counter_ns())

    def _clock(self, ns: int) -> float:
        """Engine-clock seconds of a time.perf_counter_ns() stamp; the
        clock starts at the engine's first use of it."""
        if self._t0_ns is None:
            self._t0_ns = ns
        return (ns - self._t0_ns) / 1e9

    # -- admission (prefill path) ---------------------------------------
    def _copy_prefill(self, slot: int, sub, plan=None) -> None:
        """Admission-copy chokepoint for BOTH layouts. Dense copies the
        whole slot row (`_write_slot`); paged copies each freshly
        allocated prompt page through `_write_page` — pages the pool
        matched to an already-resident prefix are shared, not
        rewritten, which is where prefix admission gets cheap."""
        if self.pool is None:
            self.cache = self._write(self.cache, sub, slot)
            return
        for j, phys in plan.private:
            self.cache = self._write_pg(
                self.cache, sub, jnp.int32(phys),
                jnp.int32(j * self.page_size))

    def _admit(self, req: Request) -> None:
        """Prefill `req` into a free slot. A resumed (previously
        preempted) request re-prefills its FULL context — prompt plus
        everything generated before eviction — so decode continues
        exactly where it stopped (recompute-on-resume)."""
        slot = self.scheduler.admit(req)
        ctx = req.context_tokens()
        plan = None
        if self.pool is not None:
            plan = self.pool.admit_slot(slot, ctx, req.remaining_tokens)
        self._admissions += 1

        L = len(ctx)
        chunk = self.prefill_chunk
        lb = L - (L % chunk) or L      # bucket down; short prompts exact
        with spans.timed("repro.engine.admit", rid=req.rid, prompt_len=L,
                         bucket=lb, token_steps=L - lb,
                         **self._slot_bytes) as adm:
            with spans.span("repro.engine.admit.prefill"):
                batch: Dict[str, Any] = {
                    "tokens": jnp.asarray(ctx[None, :lb])}
                if self.cfg.family == "encdec":
                    batch["enc_frames"] = jnp.asarray(req.enc_frames[None])
                sub = M.init_cache(self.cfg, 1, self.max_len)
                logits, sub = self._prefill(self.params, batch, sub)
            with spans.span("repro.engine.admit.token_steps"):
                for i in range(lb, L):         # remainder: one-token steps
                    logits, sub = self._admit_step(
                        self.params, jnp.asarray(ctx[None, None, i]),
                        jnp.int32(i), sub)
            with spans.span("repro.engine.admit.slot_copy",
                            **self._slot_bytes):
                self._copy_prefill(slot, sub, plan)
            with spans.span("repro.engine.admit.first_token"):
                row = np.asarray(logits)[0, -1, :self.cfg.vocab]
                # same sentinel as decode: a poisoned prefill quarantines
                # this request only, never the engine
                tok = self.sampler(row) if np.isfinite(row).all() else None
        self.prefill_time += adm.seconds
        self.prefill_tokens += L
        if req.t_admitted is None:
            req.t_admitted = self._clock(adm.start_ns)
            # the wait before a first admission; a resume's wait after
            # its preemption is not a queue wait of the request's own
            spans.record("repro.engine.queue",
                         self._t0_ns + round(req.t_due * 1e9), adm.start_ns,
                         rid=req.rid)
        now = self._clock(adm.end_ns)
        if tok is None:
            req.error = "non-finite logits at admission prefill"
            self.quarantined += 1
            self._release(req, slot, QUARANTINED, now)
            return
        if req.t_first_token is None:
            req.t_first_token = now
        req.generated.append(tok)
        self.tokens_emitted += 1
        if self._done(req, tok):
            self._finish(req, slot, now)
        else:
            self._pos[slot] = L
            self._tokens[slot, 0] = tok
            if self.spec is not None:
                # Fill the draft cache with the same context (rows
                # 0..L-1); the first draft round then feeds the pending
                # token at L. Resumes pass the fuller context through
                # here too (recompute-on-resume covers both caches).
                self.spec.admit(slot, ctx)

    def _done(self, req: Request, tok: int) -> bool:
        return (req.n_generated >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id))

    # -- release / cancellation / preemption ----------------------------
    def _release(self, req: Request, slot: int, status: str,
                 now: float) -> None:
        """Free a slot into a terminal request state, returning its KV
        pages to the pool (refcount-safe: shared prefix pages and pages
        mid-CoW just drop one reference; survivors keep their bytes)."""
        self.scheduler.release(slot, status)
        if self.pool is not None:
            self.pool.release_slot(slot)
        self._pos[slot] = -1
        self._tokens[slot, 0] = 0
        req.t_finished = now

    def _finish(self, req: Request, slot: int, now: float) -> None:
        self._release(req, slot, FINISHED, now)

    def cancel(self, rid: int) -> bool:
        """Cancel a request by id: a waiter leaves the queue, an active
        request gives up its slot and KV pages immediately. Returns
        False when the request is already terminal."""
        req = next((r for r in self.requests if r.rid == rid), None)
        if req is None:
            raise ValueError(f"unknown request id {rid}")
        if req.status in TERMINAL:
            return False
        now = self._now()
        if req.status == WAITING:
            self.scheduler.remove_waiting(req)
            req.status = CANCELLED
            req.t_finished = now
        elif req.status == ACTIVE:
            self._release(req, req.slot, CANCELLED, now)
        self.cancelled += 1
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Evict the request in `slot` back to the waiting queue,
        reclaiming its pages. Resume backoff doubles per eviction so a
        repeatedly-starved victim cannot thrash the admission loop."""
        req = self.scheduler.active[slot]
        backoff = self.preempt_backoff * (2 ** req.preemptions)
        req.preemptions += 1
        self.preempted += 1
        self.scheduler.preempt(slot, resume_at=self._now() + backoff)
        if self.pool is not None:
            self.pool.release_slot(slot)
        self._pos[slot] = -1
        self._tokens[slot, 0] = 0

    def _preempt_for(self, head: Request) -> bool:
        """Pick and evict a victim so `head` can be admitted: the
        lowest-priority, then youngest (latest-admitted) active request
        that still has preemption-retry budget and is STRICTLY
        outranked by the head. Equal-priority contention defers FCFS
        instead (no churn; the pinned deferral semantics of a smooth
        trace are unchanged). Returns False when no victim exists."""
        cands = [(r.priority, -(r.t_admitted or 0.0), slot)
                 for slot, r in self.scheduler.active.items()
                 if r.preemptions < self.preempt_retry_budget
                 and r.priority < head.priority]
        if not cands:
            return False
        cands.sort()
        self._preempt_slot(cands[0][2])
        return True

    # -- numeric / kernel fault handling --------------------------------
    def _degrade_to_xla(self, err: BaseException) -> None:
        global _DEGRADE_WARNED
        self.policy = self.policy.replace(backend="xla")
        self._build_steps()
        self.degraded = True
        if not _DEGRADE_WARNED:
            _DEGRADE_WARNED = True
            warnings.warn(
                f"serving engine degraded to the 'xla' registry backend "
                f"after {self.kernel_faults} kernel fault(s) (last: "
                f"{err!r}); latency may regress but the trace continues",
                RuntimeWarning, stacklevel=2)

    def _run_step(self, step_idx: int):
        """One guarded jitted decode step: injected kernel faults are
        retried, and once they repeat past `kernel_fault_threshold` the
        engine rebuilds its steps on the xla backend instead of
        crashing. A step that exhausts its retries counts as crashed and
        re-raises; any other error propagates at once."""
        tokens = jnp.asarray(self._tokens)
        pos = jnp.asarray(self._pos)
        attempts = 0
        while True:
            try:
                if self.injector is not None:
                    self.injector.before_kernel(step_idx)
                return self._step(self.params, tokens, pos, self.cache)
            except SimulatedKernelFault as e:
                attempts += 1
                self.kernel_faults += 1
                if attempts > self.max_step_retries:
                    self.crashed_steps += 1
                    raise
                if (self.kernel_faults >= self.kernel_fault_threshold
                        and not self.degraded
                        and self.policy.backend != "xla"):
                    self._degrade_to_xla(e)

    def _poison_slot_cache(self, slot: int) -> None:
        """Chaos-harness hook: NaN a cache region PRIVATE to `slot` so
        the fault surfaces through real attention math. Paged mode
        poisons the slot's current write page (made private by
        prepare_write just before this runs — a shared page is never
        touched, pinning the sharer-survives contract); dense mode
        poisons the slot's row of every float cache leaf."""
        if self.pool is not None:
            j = int(self._pos[slot]) // self.page_size
            phys = int(self.pool.table[slot, j])
            pages = dict(self.cache["pages"])
            for name in ("k", "v"):
                # int8 pages cannot hold a NaN; poison the scales
                target = name + "s" if name + "s" in pages else name
                pages[target] = pages[target].at[:, phys].set(jnp.nan)
            self.cache = {"pages": pages, "table": self.cache["table"]}
            return
        leaves = jax.tree.leaves(self.cache)
        out = []
        for leaf, ax in zip(leaves, self._slot_axes):
            if ax is None or not jnp.issubdtype(leaf.dtype, jnp.floating):
                out.append(leaf)
                continue
            idx = [slice(None)] * leaf.ndim
            idx[ax] = slot
            out.append(leaf.at[tuple(idx)].set(jnp.nan))
        self.cache = jax.tree.unflatten(self._treedef, out)

    # -- decode --------------------------------------------------------
    def _decode_once(self) -> None:
        active = self.scheduler.active
        if not active:
            raise ValueError("decode step with no active slots")
        step_idx = self.decode_steps
        with spans.timed("repro.engine.decode.dispatch") as disp:
            if self.pool is not None:
                # Make every slot's write position privately owned
                # BEFORE the jitted step scatters into it: a write into a
                # shared page becomes a device page copy (CoW), a write
                # past the mapped prefix allocates from the reservation
                # made at admission (so this can never fail mid-stream).
                for slot in active:
                    w = self.pool.prepare_write(slot, int(self._pos[slot]))
                    if w is not None and w.kind == "cow":
                        self.cache = self._copy_pg(
                            self.cache, jnp.int32(w.src), jnp.int32(w.dst))
                self._sync_table()
            if self.injector is not None:
                for slot in self.injector.corrupt_slots(step_idx,
                                                        tuple(active)):
                    self._poison_slot_cache(slot)
            logits, self.cache = self._run_step(step_idx)
        with spans.timed("repro.engine.decode.logits_read") as read:
            rows = np.asarray(logits)[:, -1, :self.cfg.vocab]  # sync point
        with spans.span("repro.engine.decode.sample"):
            self._count_step(step_idx, disp.start_ns, read.end_ns,
                             len(active))
            if self.injector is not None:
                rows = self.injector.poison_rows(step_idx, rows,
                                                 tuple(active))
            now = self._clock(read.end_ns)
            for slot in sorted(active):
                req = active[slot]
                if not np.isfinite(rows[slot]).all():
                    # quarantine ONLY the poisoned slot; co-scheduled rows
                    # are untouched (their logits never mix across slots)
                    req.error = f"non-finite logits at decode step {step_idx}"
                    self.quarantined += 1
                    self._release(req, slot, QUARANTINED, now)
                    continue
                tok = self.sampler(rows[slot])
                req.generated.append(tok)
                self.tokens_emitted += 1
                if self._done(req, tok):
                    self._finish(req, slot, now)
                else:
                    self._pos[slot] += 1
                    self._tokens[slot, 0] = tok

    def _count_step(self, step_idx: int, start_ns: int, end_ns: int,
                    n_active: int) -> None:
        """A decode step's counters: its time from dispatch to the host
        holding every slot's logits, and its occupancy."""
        dt = (end_ns - start_ns) / 1e9
        self._step_times.append(dt)
        self.straggler.observe(step_idx, dt)
        self.decode_steps += 1
        self.decode_slot_steps += n_active
        self.peak_occupancy = max(self.peak_occupancy, n_active)

    # -- speculative decode (draft round + ONE batched verification) ----
    def _spec_decode_once(self) -> None:
        """One speculative round: spec_k draft steps propose tokens for
        every active slot, then the TARGET model scores all k+1
        positions (pending + drafts) in ONE prefill-shaped verify_step —
        batched verification is the whole subsystem's point; the per-
        round target cost is one multi-token forward, never k decode
        steps. Acceptance (sampler.speculative_accept) emits 1..k+1
        tokens per slot; the target/draft caches need no rollback work
        because rollback is positional (see serving/spec.py docstring):
        rows past each slot's new pending position are stale but masked,
        and the next round overwrites them before they could be read."""
        active = self.scheduler.active
        if not active:
            raise ValueError("decode step with no active slots")
        step_idx = self.decode_steps
        k = self.spec_k
        k_vec = np.zeros(self.max_slots, np.int32)
        for slot, req in active.items():
            # a slot about to hit its budget proposes fewer drafts —
            # tokens past max_new would be drafted only to be dropped
            k_vec[slot] = min(k, req.remaining_tokens - 1)
        with spans.timed("repro.engine.decode.dispatch") as disp:
            drafts, qprobs = self.spec.draft_round(self._tokens, self._pos,
                                                   k_vec)
            vtokens = np.zeros((self.max_slots, k + 1), np.int32)
            vtokens[:, 0] = self._tokens[:, 0]
            vtokens[:, 1:] = drafts
            n_tok = np.where(self._pos >= 0, k_vec + 1, 0).astype(np.int32)
            if self.pool is not None:
                # every position the verify scatter may write must be
                # privately owned first; the admission reservation covers
                # the full range (max write pos + k_vec stays short of the
                # reserved last page), so this never fails mid-stream.
                ps = self.page_size
                for slot in active:
                    p0 = int(self._pos[slot])
                    for j in range(p0 // ps,
                                   (p0 + int(n_tok[slot]) - 1) // ps + 1):
                        w = self.pool.prepare_write(slot, j * ps)
                        if w is not None and w.kind == "cow":
                            self.cache = self._copy_pg(
                                self.cache, jnp.int32(w.src),
                                jnp.int32(w.dst))
                self._sync_table()
            logits, self.cache = self._vstep(
                self.params, jnp.asarray(vtokens), jnp.asarray(self._pos),
                jnp.asarray(n_tok), self.cache)
        with spans.timed("repro.engine.decode.logits_read") as read:
            rows = np.asarray(logits)[:, :, :self.cfg.vocab]  # sync point
        with spans.span("repro.engine.decode.sample"):
            self._count_step(step_idx, disp.start_ns, read.end_ns,
                             len(active))
            self.spec_rounds += 1
            now = self._clock(read.end_ns)
            for slot in sorted(active):
                req = active[slot]
                nt = int(n_tok[slot])
                if not np.isfinite(rows[slot, :nt]).all():
                    req.error = f"non-finite logits at decode step {step_idx}"
                    self.quarantined += 1
                    self._release(req, slot, QUARANTINED, now)
                    continue
                kk = nt - 1
                emitted, n_acc = self.sampler.speculative_accept(
                    rows[slot, :nt], drafts[slot, :kk],
                    None if qprobs is None else qprobs[slot, :kk])
                req.draft_proposed += kk
                req.draft_accepted += n_acc
                self.spec_proposed += kk
                self.spec_accepted += n_acc
                n_cons = 0
                finished = False
                for tok in emitted:
                    req.generated.append(tok)
                    self.tokens_emitted += 1
                    n_cons += 1
                    if self._done(req, tok):   # eos truncates mid-round
                        finished = True
                        break
                if finished:
                    self._finish(req, slot, now)
                else:
                    self._pos[slot] += n_cons
                    self._tokens[slot, 0] = emitted[n_cons - 1]

    # -- driving -------------------------------------------------------
    def step(self) -> bool:
        """Drop expired waiters, admit ready requests (preempting for a
        pool-starved FCFS head when a victim exists), then run one
        decode step if any slot is active. An admission stalls every
        decoding slot, so while one decodes a step admits at most one
        request and no token gap spans two admissions; with none
        decoding it admits every ready request. Returns False when all
        work is drained."""
        with spans.span("repro.engine.step"):
            decoding = self.scheduler.n_active > 0
            while True:
                with spans.span("repro.engine.schedule"):
                    req = self._next_admission()
                if req is None:
                    break
                self._admit(req)
                if decoding:
                    break
            if self.scheduler.n_active:
                with spans.span("repro.engine.decode", step=self.decode_steps,
                                active=self.scheduler.n_active,
                                state_bytes=self.max_slots
                                * self._slot_bytes["state_bytes"]):
                    if self.spec is not None:
                        self._spec_decode_once()
                    else:
                        self._decode_once()
            return self.scheduler.has_work()

    def _next_admission(self) -> Optional[Request]:
        """Drop expired waiters and return the request to admit now, or
        None; a pool-starved FCFS head preempts a victim where one
        exists, and otherwise waits for pages to free."""
        now = self._now()
        for req in self.scheduler.drop_expired(now):
            req.t_finished = now
            self.expired += 1
        req = self.scheduler.next_admission(now)
        if req is None or self.pool is None:
            return req
        denied = (self.injector is not None
                  and self.injector.deny_admission(self._admissions))
        ok = not denied and self.pool.can_admit(req.context_tokens(),
                                                req.remaining_tokens)
        while not ok and self._preempt_for(req):
            ok = self.pool.can_admit(req.context_tokens(),
                                     req.remaining_tokens)
        return req if ok else None

    def run(self, *, idle_sleep: float = 1e-3) -> Dict[str, Any]:
        """Drive to completion; returns the stats report."""
        while self.scheduler.has_work():
            if not self.step():
                break
            if not self.scheduler.n_active:
                nxt = self.scheduler.next_arrival_time()
                if nxt is not None:
                    time.sleep(max(idle_sleep, min(nxt - self._now(), 0.05)))
        return self.report()

    # -- stats ----------------------------------------------------------
    def report(self) -> Dict[str, Any]:
        done = [r for r in self.requests if r.status == FINISHED]
        lat = [r.latency for r in done]
        ttft = [r.ttft for r in done]
        n_emitted = sum(r.n_generated for r in self.requests)
        assert n_emitted == self.tokens_emitted, \
            (n_emitted, self.tokens_emitted)
        waits = [r.t_admitted - r.t_due for r in self.requests
                 if r.t_admitted is not None]
        # goodput: only tokens of requests that FINISHED (and met their
        # deadline, if they had one) were worth emitting; everything a
        # cancelled / expired / quarantined / late request decoded is
        # wasted work. (Preemption waste is re-PREFILL compute and so
        # shows up in prefill_tokens, not here — no token is emitted
        # twice.)
        useful = sum(r.n_generated for r in done
                     if r.missed_deadline is not True)
        deadlined = [r for r in self.requests
                     if r.deadline is not None and r.status in TERMINAL]
        missed = [r for r in deadlined if r.missed_deadline]
        decode_tokens = self.tokens_emitted - len(
            [r for r in self.requests if r.t_first_token is not None])
        out = {
            "n_requests": len(self.requests),
            "n_finished": len(done),
            "prefill_tokens": self.prefill_tokens,
            "prefill_tok_s": self.prefill_tokens / max(self.prefill_time,
                                                       1e-9),
            "decode_tokens": decode_tokens,
            "decode_steps": self.decode_steps,
            "decode_tok_s": (self.decode_slot_steps
                             / max(sum(self._step_times), 1e-9)),
            "mean_occupancy": (self.decode_slot_steps
                               / max(self.decode_steps, 1)),
            "latency_p50_s": percentile(lat, 50),
            "latency_p95_s": percentile(lat, 95),
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p95_s": percentile(ttft, 95),
            "peak_occupancy": self.peak_occupancy,
            "decode_step_p50_s": percentile(self._step_times, 50),
            "decode_step_p99_s": percentile(self._step_times, 99),
            "admission_wait_p50_s": percentile(waits, 50),
            "admission_wait_p99_s": percentile(waits, 99),
            # fault tolerance
            "expired": self.expired,
            "cancelled": self.cancelled,
            "preempted": self.preempted,
            "quarantined": self.quarantined,
            "kernel_faults": self.kernel_faults,
            "crashed_steps": self.crashed_steps,
            "degraded": self.degraded,
            "straggler_steps": len(self.straggler.flagged),
            "useful_tokens": useful,
            "goodput": useful / max(self.tokens_emitted, 1),
            "deadline_miss_rate": (len(missed) / len(deadlined)
                                   if deadlined else float("nan")),
            # tokens emitted per slot-step: exactly 1.0 for plain
            # decode (minus quarantines), > 1.0 when speculation pays
            "tokens_per_step": decode_tokens / max(self.decode_slot_steps,
                                                   1),
        }
        if self.spec is not None:
            out["spec_rounds"] = self.spec_rounds
            out["spec_proposed"] = self.spec_proposed
            out["spec_accepted"] = self.spec_accepted
            out["spec_acceptance_rate"] = (self.spec_accepted
                                           / max(self.spec_proposed, 1))
            out["draft_time_s"] = self.spec.draft_time
            out["draft_prefill_time_s"] = self.spec.prefill_time
        if self.injector is not None:
            out["faults_injected"] = self.injector.report()
        if self.pool is not None:
            out["kv_pool"] = self.pool.report()
        return out
