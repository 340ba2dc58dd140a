"""Layer stacks for every assigned architecture family.

All stacks scan over layers (bounded HLO size => tractable 512-device
compiles) with a configurable remat policy, and all dense compute inside
every block routes through core.gemm — the paper's kernel under load.

Families:
  decoder   — dense / MoE / VLM decoder-only transformer
  ssm       — Mamba-2 stack (norm + mamba residual)
  hybrid    — Zamba2: Mamba-2 backbone + ONE weight-shared attention
              block invoked every `attn_every` layers with per-invocation
              LoRA deltas and concat([hidden, embed0]) input; or, with
              `layer_types` (Granite 4.0-H), Mamba-2 and attention layers
              interleaved, each with its own weights and an MLP
  encdec    — Whisper: bidirectional encoder (stub conv frontend
              upstream) + causal decoder with cross-attention
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S


# ----------------------------------------------------------------------
# remat policy
# ----------------------------------------------------------------------

def _maybe_remat(fn, cfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)        # "full": save nothing


def _norm_init(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "ln":
        return L.layernorm_init(d, dtype=jnp.dtype(cfg.param_dtype))
    return L.rmsnorm_init(d, dtype=jnp.dtype(cfg.param_dtype))


def _norm_apply(cfg, p, x):
    if cfg.norm == "ln":
        return L.layernorm_apply(p, x)
    return L.rmsnorm_apply(p, x, eps=cfg.norm_eps)


# ----------------------------------------------------------------------
# decoder-only transformer block (dense / MoE)
# ----------------------------------------------------------------------

def block_init(key, cfg, *, cross: bool = False):
    ks = jax.random.split(key, 4)
    p = {
        "attn_norm": _norm_init(cfg),
        "attn": A.attn_init(ks[0], cfg),
        "mlp_norm": _norm_init(cfg),
    }
    if cfg.moe is not None:
        p["moe"] = M.moe_init(ks[1], cfg)
    else:
        p["mlp"] = F.mlp_init(ks[1], cfg)
    if cross:
        p["cross_norm"] = _norm_init(cfg)
        p["cross_attn"] = A.attn_init(ks[2], cfg, cross=True)
    return p


def block_apply(p, x, cfg, *, positions=None, causal=True, cache=None,
                cache_pos=None, enc_out=None, cross_cache=None,
                kv_table=None, n_valid=None):
    """Returns (x, new_cache, aux)."""
    h, new_cache = A.attn_apply(
        p["attn"], _norm_apply(cfg, p["attn_norm"], x), cfg,
        positions=positions, causal=causal, cache=cache, cache_pos=cache_pos,
        kv_table=kv_table, n_valid=n_valid)
    x = x + h
    if enc_out is not None or cross_cache is not None:
        if cross_cache is not None:
            kv = (cross_cache["k"], cross_cache["v"])
        else:
            kv = A.project_cross_kv(p["cross_attn"], enc_out, cfg)
        hc, _ = A.attn_apply(
            p["cross_attn"], _norm_apply(cfg, p["cross_norm"], x), cfg,
            enc_kv=kv)
        x = x + hc
    x = constrain(x, "dp", None, None)
    aux = {}
    if cfg.moe is not None:
        h, aux = M.moe_apply(p["moe"], _norm_apply(cfg, p["mlp_norm"], x), cfg)
        out = x + h
    else:
        # the skip connection rides the down-projection's fused flush on
        # Pallas backends (residual epilogue); identical composition on xla
        out = F.mlp_apply(p["mlp"], _norm_apply(cfg, p["mlp_norm"], x), cfg,
                          residual=x)
    return constrain(out, "dp", None, None), new_cache, aux


# ----------------------------------------------------------------------
# stacked decoder (scan over layers)
# ----------------------------------------------------------------------

def stack_init(key, cfg, *, n_layers=None, cross=False):
    n = n_layers or cfg.n_layers
    keys = jax.random.split(key, n)
    if cfg.scan_layers:
        return jax.vmap(lambda k: block_init(k, cfg, cross=cross))(keys)
    return [block_init(k, cfg, cross=cross) for k in keys]


def stack_apply(params, x, cfg, *, positions=None, causal=True,
                caches=None, cache_pos=None, enc_out=None,
                cross_caches=None, kv_table=None, n_valid=None):
    """caches / cross_caches carry a leading layer dim when scanning.

    kv_table (paged decode) is closed over rather than scanned: one
    logical page is the same physical index in every layer's pool, so
    the table has no layer dim to carry as an xs. n_valid (per-row valid
    token count, speculative verify) is likewise layer-less and closed
    over.

    Returns (x, new_caches, aux_sum).
    """
    def body(carry, layer_in):
        xc, aux_sum = carry
        lp, cache, ccache = layer_in
        xo, new_cache, aux = block_apply(
            lp, xc, cfg, positions=positions, causal=causal, cache=cache,
            cache_pos=cache_pos, enc_out=enc_out, cross_cache=ccache,
            kv_table=kv_table, n_valid=n_valid)
        aux_sum = {k: aux_sum.get(k, 0.0) + v for k, v in aux.items()} \
            if aux else aux_sum
        return (xo, aux_sum), new_cache

    aux0 = {}
    if cfg.moe is not None:
        zero = jnp.zeros((), jnp.float32)
        aux0 = {"moe_lb_loss": zero, "moe_z_loss": zero,
                "moe_dropped_frac": zero}

    if cfg.scan_layers:
        body_r = _maybe_remat(body, cfg)
        (x, aux), new_caches = jax.lax.scan(
            body_r, (x, aux0), (params, caches, cross_caches))
    else:
        new_list = []
        carry = (x, aux0)
        n = len(params)
        for i in range(n):
            carry, nc = body(carry, (
                params[i],
                None if caches is None else jax.tree.map(lambda c: c[i], caches),
                None if cross_caches is None else jax.tree.map(
                    lambda c: c[i], cross_caches)))
            new_list.append(nc)
        x, aux = carry
        new_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_list)
                      if new_list and new_list[0] is not None else None)
    if cfg.moe is not None and aux:
        aux = dict(aux)
        aux["moe_dropped_frac"] = aux["moe_dropped_frac"] / cfg.n_layers
    return x, new_caches, aux


# ----------------------------------------------------------------------
# Mamba-2 stack
# ----------------------------------------------------------------------

def ssm_stack_init(key, cfg):
    keys = jax.random.split(key, cfg.n_layers)

    def one(k):
        return {"norm": _norm_init(cfg), "mamba": S.mamba_init(k, cfg)}
    if cfg.scan_layers:
        return jax.vmap(one)(keys)
    return [one(k) for k in keys]


def ssm_stack_apply(params, x, cfg, *, states=None, decode=False):
    """states: stacked mamba states (leading L dim). decode => 1 token."""
    collect = states is not None and not decode

    def body(xc, layer_in):
        lp, st = layer_in
        xin = _norm_apply(cfg, lp["norm"], xc)
        if decode:
            h, new_st = S.mamba_decode(lp["mamba"], xin, cfg, st)
        else:
            h, new_st = S.mamba_apply(lp["mamba"], xin, cfg,
                                      return_state=collect)
        return xc + h, new_st

    body_r = _maybe_remat(body, cfg) if not decode else body
    x, new_states = jax.lax.scan(body_r, x, (params, states))
    return x, new_states


# ----------------------------------------------------------------------
# Zamba2 hybrid stack
# ----------------------------------------------------------------------

def hybrid_init(key, cfg):
    assert cfg.attn_every > 0
    n_seg = cfg.n_layers // cfg.attn_every
    ks = jax.random.split(key, 4)
    # mamba layers stacked as (n_seg, per_seg, ...)
    keys = jax.random.split(ks[0], cfg.n_layers)

    def one(k):
        return {"norm": _norm_init(cfg), "mamba": S.mamba_init(k, cfg)}
    mamba = jax.vmap(one)(keys)
    mamba = jax.tree.map(
        lambda a: a.reshape((n_seg, cfg.attn_every) + a.shape[1:]), mamba)

    shared_cfg = dataclasses.replace(cfg, moe=None)
    shared = {
        "in_proj": L.dense_init(ks[1], 2 * cfg.d_model, cfg.d_model,
                                dtype=jnp.dtype(cfg.param_dtype)),
        "block": block_init(ks[2], shared_cfg),
    }
    p = {"mamba": mamba, "shared": shared}
    r = cfg.shared_attn_lora_rank
    if r:
        dh = cfg.resolved_head_dim
        ka, kb = jax.random.split(ks[3])
        p["lora_a"] = (jax.random.normal(
            ka, (n_seg, cfg.d_model, r), jnp.float32) * cfg.d_model ** -0.5
        ).astype(jnp.dtype(cfg.param_dtype))
        p["lora_b"] = jnp.zeros((n_seg, r, cfg.n_heads * dh),
                                jnp.dtype(cfg.param_dtype))
    return p


def hybrid_apply(params, x, cfg, *, emb0, attn_caches=None, cache_pos=None,
                 mamba_states=None, decode=False):
    """emb0: the initial embedding, concat-fed to every shared-block call.

    attn_caches: stacked (n_seg, B, Tmax, Hkv, Dh) KV caches.
    Returns (x, new_attn_caches, new_mamba_states).
    """
    n_seg = cfg.n_layers // cfg.attn_every
    shared_cfg = dataclasses.replace(cfg, moe=None)
    collect = mamba_states is not None and not decode

    def seg_body(carry, seg_in):
        xc = carry
        seg_params, seg_states, attn_cache, lora = seg_in

        def layer_body(xi, layer_in):
            lp, st = layer_in
            xin = _norm_apply(cfg, lp["norm"], xi)
            if decode:
                h, new_st = S.mamba_decode(lp["mamba"], xin, cfg, st)
            else:
                h, new_st = S.mamba_apply(lp["mamba"], xin, cfg,
                                          return_state=collect)
            return xi + h, new_st

        lb = _maybe_remat(layer_body, cfg) if not decode else layer_body
        xc, new_seg_states = jax.lax.scan(lb, xc, (seg_params, seg_states))

        # shared attention block on concat(hidden, first-embedding)
        xin = L.dense_apply(params["shared"]["in_proj"],
                            jnp.concatenate([xc, emb0], axis=-1))
        bp = params["shared"]["block"]
        if lora is not None:
            la, lbm = lora
            delta = jnp.einsum("btd,dr,rh->bth",
                               _norm_apply(cfg, bp["attn_norm"], xin),
                               la.astype(xin.dtype), lbm.astype(xin.dtype))
        else:
            delta = None
        xo, new_cache, _ = block_apply(
            bp, xin, shared_cfg, cache=attn_cache, cache_pos=cache_pos)
        if delta is not None:
            xo = xo + delta
        return xc + xo, (new_seg_states, new_cache)

    lora_xs = None
    if cfg.shared_attn_lora_rank:
        lora_xs = (params["lora_a"], params["lora_b"])
    seg_in = (params["mamba"], mamba_states, attn_caches, lora_xs)
    x, (new_states, new_caches) = jax.lax.scan(seg_body, x, seg_in)
    return x, new_caches, new_states


# ----------------------------------------------------------------------
# Interleaved hybrid stack (Granite 4.0-H)
# ----------------------------------------------------------------------
#
# Layer i mixes with Mamba-2 or attention as cfg.layer_types[i] says, and
# every layer follows its mixer with a SwiGLU MLP; both branches are
# scaled by cfg.residual_multiplier before their add:
#
#     h += r * mixer_i(norm(h));    h += r * mlp_i(norm(h))
#
# Each kind's weights (and cache) are stacked over its own layers in
# depth order. The stack scans over whole periods of layer_types; inside
# a period each run of same-kind layers is a scan of its own, so the
# compiled program holds one body per run, not one per layer. A layer
# indexes its weights out of the stacks, and its cache too, which it
# writes back in place: the stacked cache rides the loops' carry, so a
# step holds no second copy of it.

def _runs(kinds):
    """(kind, first layer, length) of each run of equal kinds."""
    out = []
    for j, k in enumerate(kinds):
        if out and out[-1][0] == k:
            out[-1][2] += 1
        else:
            out.append([k, j, 1])
    return [tuple(r) for r in out]


def interleaved_init(key, cfg):
    """{"mamba": {norm, mamba}, "attn": {norm, attn}, "mlp": {norm,
    mlp}}, each stacked over the layers that have it."""
    km, ka, kf = jax.random.split(key, 3)

    def stacked(fn, k, count):
        return jax.vmap(fn)(jax.random.split(k, count))

    return {
        "mamba": stacked(lambda k: {"norm": _norm_init(cfg),
                                    "mamba": S.mamba_init(k, cfg)},
                         km, cfg.layer_types.count("mamba")),
        "attn": stacked(lambda k: {"norm": _norm_init(cfg),
                                   "attn": A.attn_init(k, cfg)},
                        ka, cfg.layer_types.count("attention")),
        "mlp": stacked(lambda k: {"norm": _norm_init(cfg),
                                  "mlp": F.mlp_init(k, cfg)},
                       kf, cfg.n_layers),
    }


def _branch(cfg, x, h):
    if cfg.residual_multiplier != 1.0:
        h = h * cfg.residual_multiplier
    return x + h


def interleaved_apply(params, x, cfg, *, caches=None, cache_pos=None,
                      decode=False):
    """caches: {"mamba": stacked states (n_mamba, B, ...), "attn":
    {"k", "v": (n_attn, B, Tmax, Hkv, Dh)}}, or None (training).
    Returns (x, new_caches)."""
    per = cfg.layer_period
    kinds = cfg.layer_types[:per]
    key = {"mamba": "mamba", "attention": "attn"}
    count = {k: kinds.count(k) for k in key}
    collect = caches is not None and not decode

    def at(tree, i):
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False),
            tree)

    def layer(kind, xc, lp, fp, st):
        with jax.named_scope(f"repro.{key[kind]}"):
            u = _norm_apply(cfg, lp["norm"], xc)
            if kind == "attention":
                h, st = A.attn_apply(lp["attn"], u, cfg, cache=st,
                                     cache_pos=cache_pos)
            elif decode:
                h, st = S.mamba_decode(lp["mamba"], u, cfg, st)
            else:
                h, st = S.mamba_apply(lp["mamba"], u, cfg,
                                      return_state=collect)
        xc = _branch(cfg, xc, h)
        xc = _branch(cfg, xc, F.mlp_apply(
            fp["mlp"], _norm_apply(cfg, fp["norm"], xc), cfg))
        return constrain(xc, "dp", None, None), st

    def run(kind, first_kind, first_layer):
        k = key[kind]

        def body(carry, r):
            xc, cache = carry
            i = first_kind + r
            st = None if cache is None else at(cache[k], i)
            xc, st = layer(kind, xc, at(params[k], i),
                           at(params["mlp"], first_layer + r), st)
            if cache is not None:
                cache = {**cache, k: jax.tree.map(
                    lambda c, s: jax.lax.dynamic_update_index_in_dim(
                        c, s.astype(c.dtype), i, 0), cache[k], st)}
            return (xc, cache), None
        return body if decode else _maybe_remat(body, cfg)

    def period(carry, p):
        done = {k: 0 for k in key}
        for kind, j0, n in _runs(kinds):
            body = run(kind, p * count[kind] + done[kind], p * per + j0)
            carry, _ = jax.lax.scan(body, carry, jnp.arange(n))
            done[kind] += n
        return carry, None

    (x, caches), _ = jax.lax.scan(period, (x, caches),
                                  jnp.arange(cfg.n_layers // per))
    return x, caches
