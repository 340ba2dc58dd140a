"""Plain float32 reference of a dense decoder with grouped-query
attention and per-head query/key RMSNorm (the Qwen3 block).

    h   = x + Wo · attn(rope(qnorm(x Wq)), rope(knorm(x Wk)), x Wv)
    out = h + Wdown · (silu(h' Wgate) * h' Wup),   x' = rmsnorm(x)

Written from the published description (Qwen3 technical report and its
config.json): pre-norm RMSNorm, RoPE with rotate-half over the whole
head, query head i reads key/value head i // (H / Hkv), causal softmax
scaled by head_dim^-0.5, SwiGLU MLP, final RMSNorm and a head tied to
the embedding. It imports nothing of the program. Every matrix product
runs under ``jax.default_matmul_precision("highest")``. Departures from
the published model: none in the mathematics; weights are random from
the benchmark's seed, laid out (and the embedding padded) as the
program stores them so that one set of arrays feeds both.

``mm_dtype`` rounds every matrix product's operands to a lower
precision (the control); None keeps float32.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _dims(config):
    p = config["program"]
    dh = p["head_dim"] or p["d_model"] // p["n_heads"]
    return p, dh


def _padded(toks: np.ndarray, multiple: int = 256) -> np.ndarray:
    """Tokens padded at the end to a multiple of `multiple` (the model is
    causal, so padding changes no earlier position), so that sequences
    of nearby lengths share one compiled program."""
    return np.pad(toks, (0, -len(toks) % multiple))


def padded_vocab(config) -> int:
    p = config["program"]
    m = p.get("vocab_pad_to", 1)
    return -(-p["vocab"] // m) * m


def init_params(config, key, dtype):
    """Seeded random weights in the program's layout (stacked layers)."""
    p, dh = _dims(config)
    d, f, nl = p["d_model"], p["d_ff"], p["n_layers"]
    hq, hkv = p["n_heads"] * dh, p["n_kv_heads"] * dh
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    ones = lambda *s: jnp.ones(s, dtype)
    out_scale = 1.0 / math.sqrt(2 * nl)
    params = {
        "embed": {"w": normal((padded_vocab(config), d), d ** -0.5)},
        "final_norm": {"scale": ones(d)},
        "layers": {
            "attn_norm": {"scale": ones(nl, d)},
            "attn": {
                "wq": {"w": normal((nl, d, hq), d ** -0.5)},
                "wk": {"w": normal((nl, d, hkv), d ** -0.5)},
                "wv": {"w": normal((nl, d, hkv), d ** -0.5)},
                "wo": {"w": normal((nl, hq, d), hq ** -0.5 * out_scale)},
                "q_norm": {"scale": ones(nl, dh)},
                "k_norm": {"scale": ones(nl, dh)},
            },
            "mlp_norm": {"scale": ones(nl, d)},
            "mlp": {
                "w_gate": {"w": normal((nl, d, f), d ** -0.5)},
                "w_up": {"w": normal((nl, d, f), d ** -0.5)},
                "w_down": {"w": normal((nl, f, d), f ** -0.5 * out_scale)},
            },
        },
    }
    if not p.get("qk_norm"):
        del params["layers"]["attn"]["q_norm"], params["layers"]["attn"]["k_norm"]
    if not p.get("tie_embeddings"):
        params["lm_head"] = {"w": normal((d, padded_vocab(config)),
                                         d ** -0.5)}
    return params


# ----------------------------------------------------------------------
# the forward pass
# ----------------------------------------------------------------------

def _mm(a, b, mm_dtype):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mm_dtype is not None:
        a = a.astype(mm_dtype).astype(jnp.float32)
        b = b.astype(mm_dtype).astype(jnp.float32)
    return jnp.matmul(a, b)


def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x: (B, T, H, dh); rotate-half RoPE at integer positions pos (T,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None].astype(jnp.float32) * inv[None]          # (T, dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def layer(config, lp, x, mm_dtype=None, eps=1e-6):
    """One decoder layer on x: (B, T, d) float32."""
    p, dh = _dims(config)
    b, t, d = x.shape
    hq, hkv = p["n_heads"], p["n_kv_heads"]
    a = lp["attn"]
    u = _rms(x, lp["attn_norm"]["scale"], eps)
    q = _mm(u, a["wq"]["w"], mm_dtype).reshape(b, t, hq, dh)
    k = _mm(u, a["wk"]["w"], mm_dtype).reshape(b, t, hkv, dh)
    v = _mm(u, a["wv"]["w"], mm_dtype).reshape(b, t, hkv, dh)
    if "q_norm" in a:
        q = _rms(q, a["q_norm"]["scale"], eps)
        k = _rms(k, a["k_norm"]["scale"], eps)
    pos = jnp.arange(t)
    q = _rope(q, pos, p["rope_theta"])
    k = _rope(k, pos, p["rope_theta"])
    rep = hq // hkv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = _mm(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1), mm_dtype) \
        * dh ** -0.5                                            # (B,H,T,T)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    o = _mm(pr, v.transpose(0, 2, 1, 3), mm_dtype)              # (B,H,T,dh)
    o = o.transpose(0, 2, 1, 3).reshape(b, t, hq * dh)
    h = x + _mm(o, a["wo"]["w"], mm_dtype)
    m = lp["mlp"]
    u = _rms(h, lp["mlp_norm"]["scale"], eps)
    g = _mm(u, m["w_gate"]["w"], mm_dtype)
    up = _mm(u, m["w_up"]["w"], mm_dtype)
    return h + _mm(jax.nn.silu(g) * up, m["w_down"]["w"], mm_dtype)


def head_weight(config, params):
    """(d, vocab) float32 head, the padded rows left out."""
    v = config["program"]["vocab"]
    if config["program"].get("tie_embeddings"):
        return params["embed"]["w"][:v].T
    return params["lm_head"]["w"][:, :v]


def logits(config, params, h, mm_dtype=None, eps=1e-6):
    hn = _rms(h, params["final_norm"]["scale"], eps)
    return _mm(hn, head_weight(config, params), mm_dtype)


def embed(config, params, tokens):
    return params["embed"]["w"][tokens].astype(jnp.float32)


def _layer_at(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


# ----------------------------------------------------------------------
# serving check: the gap of each served token below the reference's best
# ----------------------------------------------------------------------

def served_gaps(config, params, prompt: np.ndarray, served: List[int],
                mm_dtype=None, rows: int = 512) -> Dict[str, float]:
    """Run the reference once over prompt + served tokens and return the
    widest gap by which a served token's logit lies below the
    reference's best at its position, and the count of tokens read.
    With `mm_dtype` it instead reads, at each position, the gap of the
    token the lower precision puts first (the control)."""
    toks = _padded(np.concatenate([np.asarray(prompt, np.int32),
                                   np.asarray(served[:-1], np.int32)]))
    n_p = len(prompt)
    with jax.default_matmul_precision("highest"):
        h = _hidden(config, params, jnp.asarray(toks)[None], None)
        hc = _hidden(config, params, jnp.asarray(toks)[None], mm_dtype) \
            if mm_dtype is not None else None
        target = jnp.asarray(served, jnp.int32)
        worst = 0.0
        for s in range(0, len(served), rows):
            sl = slice(n_p - 1 + s, n_p - 1 + min(s + rows, len(served)))
            ref = _logits_jit(config, params, h[0, sl], None)
            if hc is None:
                pick = target[s:s + rows]
            else:
                pick = jnp.argmax(_logits_jit(config, params, hc[0, sl],
                                              mm_dtype), -1)
            worst = max(worst, float(_gap(ref, pick)))
    return {"max_gap": worst, "tokens": len(served)}


@jax.jit
def _gap(ref, pick):
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.max(best - got)


def _hidden(config, params, tokens, mm_dtype):
    x = embed(config, params, tokens)
    for i in range(config["program"]["n_layers"]):
        x = _layer_jit(config, _layer_at(params, i), x, mm_dtype)
    return x


def _freeze(config):
    return _Frozen(config)


class _Frozen:
    """A hashable view of a configuration, for jit's static arguments."""

    def __init__(self, config):
        self.config = config
        self._key = repr(sorted(config["program"].items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


_layer_c = jax.jit(lambda fc, lp, x, mm: layer(fc.config, lp, x, mm),
                   static_argnums=(0, 3))
_logits_c = jax.jit(lambda fc, params, h, mm: logits(fc.config, params, h, mm),
                    static_argnums=(0, 3))


def _layer_jit(config, lp, x, mm_dtype):
    return _layer_c(_freeze(config), lp, x, mm_dtype)


def _logits_jit(config, params, h, mm_dtype):
    return _logits_c(_freeze(config), params, h, mm_dtype)


# ----------------------------------------------------------------------
# training check: loss and gradients of the mean next-token loss
# ----------------------------------------------------------------------

def loss(config, params, tokens, labels, mm_dtype=None):
    """Mean cross-entropy over all positions; layers rematerialized and
    the head taken a row at a time so that the float32 pass fits."""
    x = embed(config, params, tokens)

    def body(x, lp):
        return jax.checkpoint(
            lambda x, lp: layer(config, lp, x, mm_dtype))(x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])

    def row_nll(args):
        h, lab = args
        lg = logits(config, params, h, mm_dtype)
        return jnp.sum(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, lab[:, None], -1)[:, 0])

    nll = jax.lax.map(jax.checkpoint(row_nll), (x, labels))
    return jnp.sum(nll) / labels.size
