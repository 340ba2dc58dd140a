"""Top-level model API, uniform across all 10 assigned architectures.

    params = init_params(cfg, key)
    loss, metrics = loss_fn(cfg, params, batch)            # train
    cache = init_cache(cfg, batch_size, max_len)
    logits, cache = prefill(cfg, params, batch, cache)     # inference
    logits, cache = decode_step(cfg, params, token, pos, cache)

Batch keys by family:
  decoder/moe : tokens, labels
  vlm         : tokens, patch_embeds (aligned, zeros at text pos),
                positions (B,S,3 M-RoPE), labels
  ssm/hybrid  : tokens, labels
  encdec      : enc_frames (stub conv-frontend output), tokens, labels
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import precision as _prec
from repro.distributed.context import constrain
from repro.models import attention as A
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T


# ----------------------------------------------------------------------
# init
# ----------------------------------------------------------------------

def init_params(cfg, key) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    dtype = jnp.dtype(cfg.param_dtype)
    p: Dict[str, Any] = {
        "embed": L.embed_init(ks[0], cfg.padded_vocab, cfg.d_model,
                              dtype=dtype),
        "final_norm": T._norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = L.dense_init(ks[1], cfg.d_model, cfg.padded_vocab,
                                    dtype=dtype)
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        p["layers"] = T.stack_init(ks[2], cfg)
    elif fam == "ssm":
        p["layers"] = T.ssm_stack_init(ks[2], cfg)
    elif fam == "hybrid" and cfg.layer_types:
        p["layers"] = T.interleaved_init(ks[2], cfg)
    elif fam == "hybrid":
        p["hybrid"] = T.hybrid_init(ks[2], cfg)
    elif fam == "encdec":
        enc_cfg = dataclasses.replace(cfg, n_layers=cfg.n_enc_layers)
        p["enc_layers"] = T.stack_init(ks[3], enc_cfg)
        p["enc_final_norm"] = T._norm_init(cfg)
        p["dec_layers"] = T.stack_init(ks[4], cfg, cross=True)
    else:
        raise ValueError(fam)
    return p


#: Param subtrees never quantized: embeddings are gathered, not
#: matmul'd (and tied lm_heads attend through them), and the MoE router
#: is a negligible-byte f32 GEMM whose argmax decides expert routing —
#: a quantization-grid flip there reroutes whole tokens.
QUANT_EXCLUDE = ("embed", "router")


def quantize_params(params, *, spec=None, exclude=QUANT_EXCLUDE):
    """Walk a param tree and quantize every dense-layer weight dict
    ({"w": 2D/3D float, "b"?} from layers.dense_init — scanned stacks
    carry a leading layer dim) to int8 via layers.dense_quantize.
    dense_apply/gated_apply then route those layers through
    core.gemm.dense_q; the serving engine calls this once at
    construction when its pinned policy has quant="int8". MoE expert
    banks (raw 3D arrays, not dicts) and the `exclude` subtrees pass
    through unchanged."""
    spec = spec or _prec.QuantSpec()

    def rec(node, name):
        if isinstance(node, dict):
            w = node.get("w")
            if (w is not None and getattr(w, "ndim", 0) in (2, 3)
                    and name not in exclude
                    and jnp.issubdtype(w.dtype, jnp.floating)):
                return L.dense_quantize(node, spec)
            return {k: rec(v, k) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(rec(v, name) for v in node)
        return node

    return rec(params, "")


# ----------------------------------------------------------------------
# forward (full-sequence) per family
# ----------------------------------------------------------------------

def _embed_inputs(cfg, params, batch):
    dtype = jnp.dtype(cfg.dtype)
    x = L.embed_apply(params["embed"], batch["tokens"], dtype=dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    if cfg.family == "vlm" and "patch_embeds" in batch:
        # Vision stub: precomputed patch embeddings arrive aligned with
        # the token grid (zeros at text positions) and are added in.
        x = x + batch["patch_embeds"].astype(dtype)
    return constrain(x, "dp", None, None)


def _logits(cfg, params, x):
    x = T._norm_apply(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = L.embed_attend(params["embed"], x)
    else:
        logits = L.dense_apply(params["lm_head"], x, out_dtype=jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    if cfg.padded_vocab != cfg.vocab:
        # Megatron-style vocab padding: mask pad classes out of softmax.
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return constrain(logits, "dp", None, "tp")


def _run_encoder(cfg, params, frames):
    enc_cfg = dataclasses.replace(cfg, n_layers=cfg.n_enc_layers)
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoid_positions(x.shape[1], cfg.d_model)[None].astype(x.dtype)
    x, _, _ = T.stack_apply(params["enc_layers"], x, enc_cfg, causal=False)
    return T._norm_apply(cfg, params["enc_final_norm"], x)


def forward(cfg, params, batch) -> tuple[jnp.ndarray, dict]:
    """Full-sequence logits (training / evaluation). Returns (logits, aux)."""
    fam = cfg.family
    aux: dict = {}
    if fam in ("dense", "moe", "vlm"):
        x = _embed_inputs(cfg, params, batch)
        x, _, aux = T.stack_apply(params["layers"], x, cfg,
                                  positions=batch.get("positions"))
    elif fam == "ssm":
        x = _embed_inputs(cfg, params, batch)
        x, _ = T.ssm_stack_apply(params["layers"], x, cfg)
    elif fam == "hybrid" and cfg.layer_types:
        x = _embed_inputs(cfg, params, batch)
        x, _ = T.interleaved_apply(params["layers"], x, cfg)
    elif fam == "hybrid":
        x = _embed_inputs(cfg, params, batch)
        x, _, _ = T.hybrid_apply(params["hybrid"], x, cfg, emb0=x)
    elif fam == "encdec":
        enc_out = _run_encoder(cfg, params, batch["enc_frames"])
        x = _embed_inputs(cfg, params, batch)
        x = x + L.sinusoid_positions(x.shape[1], cfg.d_model)[None].astype(x.dtype)
        x, _, aux = T.stack_apply(params["dec_layers"], x, cfg,
                                  enc_out=enc_out)
    else:
        raise ValueError(fam)
    return _logits(cfg, params, x), aux


def loss_fn(cfg, params, batch):
    logits, aux = forward(cfg, params, batch)
    labels = batch["labels"]
    valid = labels >= 0
    labels = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(valid), 1)
    loss = jnp.sum(nll * valid) / denom
    metrics = {"ce_loss": loss, "tokens": denom}
    for k, v in aux.items():
        metrics[k] = v
        if k.endswith("_loss"):
            loss = loss + v
    metrics["loss"] = loss
    return loss, metrics


# ----------------------------------------------------------------------
# KV / state caches
# ----------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, enc_len: int = 0):
    dtype = jnp.dtype(cfg.dtype)
    dh = cfg.resolved_head_dim
    fam = cfg.family

    def kv(layers, length, heads):
        shape = (layers, batch, length, heads, dh)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    if fam in ("dense", "moe", "vlm"):
        return kv(cfg.n_layers, max_len, cfg.n_kv_heads)
    if fam == "ssm":
        st = S.mamba_init_state(cfg, batch, dtype=dtype)
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), st)
    if fam == "hybrid" and cfg.layer_types:
        st = S.mamba_init_state(cfg, batch, dtype=dtype)
        n_mamba = cfg.layer_types.count("mamba")
        return {"mamba": jax.tree.map(
                    lambda a: jnp.broadcast_to(
                        a, (n_mamba,) + a.shape).copy(), st),
                "attn": kv(cfg.layer_types.count("attention"), max_len,
                           cfg.n_kv_heads)}
    if fam == "hybrid":
        n_seg = cfg.n_layers // cfg.attn_every
        st = S.mamba_init_state(cfg, batch, dtype=dtype)
        mamba = jax.tree.map(
            lambda a: jnp.broadcast_to(
                a, (n_seg, cfg.attn_every) + a.shape).copy(), st)
        return {"mamba": mamba, "attn": kv(n_seg, max_len, cfg.n_kv_heads)}
    if fam == "encdec":
        return {"self": kv(cfg.n_layers, max_len, cfg.n_kv_heads),
                "cross": kv(cfg.n_layers, enc_len or cfg.enc_ctx,
                            cfg.n_kv_heads)}
    raise ValueError(fam)


def init_paged_cache(cfg, n_pages: int, page_size: int, max_slots: int,
                     pages_per_slot: int, *, quant_kv: str = "off"):
    """Page-pool KV cache for continuous-batching decode (see
    serving.kv_pool for the host-side bookkeeping). Layout:

        {"pages": {"k", "v": (L, n_pages, Hkv, page_size, Dh)
                   [, "ks", "vs": (L, n_pages, Hkv, page_size) f32]},
         "table": (max_slots, pages_per_slot) int32, -1 = unmapped}

    quant_kv="int8" stores int8 pages plus per-(position, head) f32
    scale planes; the decode kernel dequantizes on its f32 accumulator.
    Attention-cache families only — ssm/hybrid state is recurrent, not
    token-addressed, so pages don't apply (and encdec's cross cache is
    read-only whole-sequence)."""
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"paged KV cache supports dense/moe/vlm, not {cfg.family!r}")
    dh = cfg.resolved_head_dim
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page_size, dh)
    if quant_kv == "int8":
        pages = {"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "ks": jnp.zeros(shape[:-1], jnp.float32),
                 "vs": jnp.zeros(shape[:-1], jnp.float32)}
    elif quant_kv == "off":
        dtype = jnp.dtype(cfg.dtype)
        pages = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    else:
        raise ValueError(f"unknown quant_kv {quant_kv!r}")
    return {"pages": pages,
            "table": jnp.full((max_slots, pages_per_slot), -1, jnp.int32)}


# ----------------------------------------------------------------------
# prefill / decode
# ----------------------------------------------------------------------

def prefill(cfg, params, batch, cache, pos: int = 0):
    """Run the prompt through the model, filling `cache`. Returns
    (last-position logits, cache)."""
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        x = _embed_inputs(cfg, params, batch)
        x, cache, _ = T.stack_apply(params["layers"], x, cfg,
                                    positions=batch.get("positions"),
                                    caches=cache, cache_pos=pos)
    elif fam == "ssm":
        x = _embed_inputs(cfg, params, batch)
        x, cache = T.ssm_stack_apply(params["layers"], x, cfg, states=cache)
    elif fam == "hybrid" and cfg.layer_types:
        x = _embed_inputs(cfg, params, batch)
        x, cache = T.interleaved_apply(params["layers"], x, cfg,
                                       caches=cache, cache_pos=pos)
    elif fam == "hybrid":
        x = _embed_inputs(cfg, params, batch)
        x, attn_c, mamba_c = T.hybrid_apply(
            params["hybrid"], x, cfg, emb0=x,
            attn_caches=cache["attn"], cache_pos=pos,
            mamba_states=cache["mamba"])
        cache = {"mamba": mamba_c, "attn": attn_c}
    elif fam == "encdec":
        enc_out = _run_encoder(cfg, params, batch["enc_frames"])
        cross = jax.vmap(
            lambda lp: A.project_cross_kv(lp["cross_attn"], enc_out, cfg)
        )(params["dec_layers"])
        cross = {"k": cross[0], "v": cross[1]}
        x = _embed_inputs(cfg, params, batch)
        x = x + L.sinusoid_positions(
            x.shape[1], cfg.d_model, pos)[None].astype(x.dtype)
        x, self_c, _ = T.stack_apply(
            params["dec_layers"], x, cfg, caches=cache["self"],
            cache_pos=pos, cross_caches=cross)
        cache = {"self": self_c, "cross": cross}
    else:
        raise ValueError(fam)
    return _logits(cfg, params, x[:, -1:]), cache


def decode_step(cfg, params, token, pos, cache):
    """One-token step. token: (B, 1) int32; pos: scalar int32, or a (B,)
    per-slot position vector (continuous batching: each batch row is an
    independent request at its own depth; pos < 0 marks an inactive slot
    whose cache is left untouched and whose logits are garbage)."""
    fam = cfg.family
    batch = {"tokens": token}
    if fam in ("dense", "moe", "vlm"):
        if fam == "vlm":
            # text token in decode: t = h = w = pos (M-RoPE degenerate)
            b = token.shape[0]
            batch["positions"] = jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32).reshape((-1, 1, 1)), (b, 1, 3)) \
                if cfg.mrope_sections else None
        x = _embed_inputs(cfg, params, batch)
        if isinstance(cache, dict) and "pages" in cache:
            # Paged cache (init_paged_cache): scan the page pools as
            # layer xs, close over the layer-less table.
            x, pages, _ = T.stack_apply(params["layers"], x, cfg,
                                        positions=batch.get("positions"),
                                        caches=cache["pages"], cache_pos=pos,
                                        kv_table=cache["table"])
            cache = {"pages": pages, "table": cache["table"]}
        else:
            x, cache, _ = T.stack_apply(params["layers"], x, cfg,
                                        positions=batch.get("positions"),
                                        caches=cache, cache_pos=pos)
    elif fam == "ssm":
        x = _embed_inputs(cfg, params, batch)
        x, cache = T.ssm_stack_apply(params["layers"], x, cfg,
                                     states=cache, decode=True)
    elif fam == "hybrid" and cfg.layer_types:
        x = _embed_inputs(cfg, params, batch)
        x, cache = T.interleaved_apply(params["layers"], x, cfg,
                                       caches=cache, cache_pos=pos,
                                       decode=True)
    elif fam == "hybrid":
        x = _embed_inputs(cfg, params, batch)
        x, attn_c, mamba_c = T.hybrid_apply(
            params["hybrid"], x, cfg, emb0=x,
            attn_caches=cache["attn"], cache_pos=pos,
            mamba_states=cache["mamba"], decode=True)
        cache = {"mamba": mamba_c, "attn": attn_c}
    elif fam == "encdec":
        x = _embed_inputs(cfg, params, batch)
        # offset the sinusoid by pos dynamically (scalar or per-slot)
        pe = _sinusoid_at(cfg.d_model, pos).reshape((-1, 1, cfg.d_model))
        x = x + pe.astype(x.dtype)
        x, self_c, _ = T.stack_apply(
            params["dec_layers"], x, cfg, caches=cache["self"],
            cache_pos=pos, cross_caches=cache["cross"])
        cache = {"self": self_c, "cross": cache["cross"]}
    else:
        raise ValueError(fam)
    return _logits(cfg, params, x), cache


def verify_step(cfg, params, tokens, pos, n_tok, cache):
    """Speculative-verification step: one batched multi-token forward.

    tokens: (B, T) int32 — per slot, the pending token followed by the
    draft proposals; pos: (B,) per-slot write position of tokens[:, 0]
    (pos < 0 = inactive slot); n_tok: (B,) count of valid rows per slot
    (rows past n_tok neither write KV nor attend — slots nearing their
    generation budget propose fewer than T-1 drafts).

    Returns (logits (B, T, V), cache). logits[:, j] is the target
    distribution for stream position pos + j + 1, so row j-1 scores
    draft token j and row n_tok-1 supplies the bonus token. This is a
    prefill-shaped call (all T positions in one GEMM pass over the
    tuned kernel stack), NOT T decode steps — the whole point of
    speculative decoding under the paper's batching thesis.
    """
    fam = cfg.family
    if fam not in ("dense", "moe", "vlm"):
        raise ValueError(
            f"speculative verification supports dense/moe/vlm, not {fam!r}")
    pos = jnp.asarray(pos, jnp.int32)
    n_tok = jnp.asarray(n_tok, jnp.int32)
    batch = {"tokens": tokens}
    if fam == "vlm" and cfg.mrope_sections:
        b, t = tokens.shape
        wpos = pos[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
        batch["positions"] = jnp.broadcast_to(wpos[..., None], (b, t, 3))
    x = _embed_inputs(cfg, params, batch)
    if isinstance(cache, dict) and "pages" in cache:
        x, pages, _ = T.stack_apply(params["layers"], x, cfg,
                                    positions=batch.get("positions"),
                                    caches=cache["pages"], cache_pos=pos,
                                    kv_table=cache["table"], n_valid=n_tok)
        cache = {"pages": pages, "table": cache["table"]}
    else:
        x, cache, _ = T.stack_apply(params["layers"], x, cfg,
                                    positions=batch.get("positions"),
                                    caches=cache, cache_pos=pos,
                                    n_valid=n_tok)
    return _logits(cfg, params, x), cache


def _sinusoid_at(d: int, pos) -> jnp.ndarray:
    """Sinusoid row(s) at `pos` (scalar -> (d,), vector (B,) -> (B, d))."""
    div = jnp.exp(-jnp.log(10_000.0) * jnp.arange(0, d, 2) / d)
    p = jnp.asarray(pos, jnp.float32)
    ang = p[..., None] * div
    pe = jnp.zeros(p.shape + (d,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(ang))
    pe = pe.at[..., 1::2].set(jnp.cos(ang))
    return pe


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
