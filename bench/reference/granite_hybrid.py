"""Plain float32 reference of an interleaved Mamba-2 / attention language
model (Granite 4.0-H, model_type granitemoehybrid).

Written from the published config.json and the granitemoehybrid
modelling code:

    h = m_emb * E[t]
    for layer i, of type layer_types[i]:
        u  = rmsnorm(h)
        mamba:      z, xBC, dt = u W_in
                    xBC = silu(causal_depthwise_conv(xBC) + b)
                    dt = softplus(dt + dt_bias),  A = -exp(A_log)
                    s_t = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T
                                            (per head, one group)
                    y_t = s_t C_t + D x_t
                    m = W_out · rmsnorm(y * silu(z))   (gated norm over the
                                                        whole inner width)
        attention:  m = W_o · softmax(m_att · q k^T + causal) v
                    (grouped-query heads, RoPE only where
                    position_embedding_type is "rope")
        h = h + m_res * m
        h = h + m_res * W_down · (silu(h' W_gate) * h' W_up),  h' = rmsnorm(h)
    logits = rmsnorm(h) E^T / logits_scaling

The multipliers, the epsilon, the layer types and the position
embedding are read from the configuration's published keys; the widths
from its ``program`` block, whose layout the weights follow. It imports
nothing of the program, and every matrix product runs under
``jax.default_matmul_precision("highest")``. The residual stream is
float32. The recurrence is the plain sequential scan, one token at a
time. Departures from the published model: weights are random from the
benchmark's seed, laid out as the program stores them (in_proj split
into z/x and B/C/dt parts, the single depthwise conv over xBC split
into its x and B/C channels, each kind's layers stacked in depth
order), the embedding drawn at EMBED_SCALE of d^-0.5 (below). Each
layer is a jitted call of its own, and attention is taken a block of
queries at a time, so that 40 layers over 4.6k tokens fit.

``mm_dtype`` rounds every matrix product's operands to a lower
precision (the control); None keeps float32.
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

# queries per block of the attention reference
_Q_BLOCK = 256
# The embedding's draw, as a share of d^-0.5. Drawn at d^-0.5 and
# multiplied by the published x12, the input token's embedding outweighs
# every mixer in the residual stream and the tied head ranks that token
# first at every position, in any precision; a 24th leaves the mixers
# most of the stream, so that what a served token depends on (the state,
# the cache, the precision) shows in its logit.
EMBED_SCALE = 1 / 24


def _dims(config):
    p = config["program"]
    s = p["ssm"]
    d = p["d_model"]
    di = s["expand"] * d
    return p, s, d, di, di // s["head_dim"], s["n_groups"] * s["d_state"]


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
    """Seeded random weights in the program's layout: each kind's layers
    stacked ("mamba", "attn"), and an MLP for every layer ("mlp"); the
    embedding at EMBED_SCALE of d^-0.5. A, D and dt_bias are float32 as
    the program keeps them; dt follows the published init (log-uniform
    in [1e-3, 1e-1]), A_log the range [1, 16]."""
    p, s, d, di, h, gn = _dims(config)
    types = config["layer_types"]
    nl, nm, na = len(types), types.count("mamba"), types.count("attention")
    dh, f, w = p["head_dim"], p["d_ff"], s["conv_width"]
    hq, hkv = p["n_heads"] * dh, p["n_kv_heads"] * dh
    out = 1.0 / math.sqrt(2 * nl)
    ks = iter(jax.random.split(key, 24))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    ones = lambda *sh: jnp.ones(sh, dtype)
    dt = jnp.exp(jax.random.uniform(next(ks), (nm, h), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    a = jax.random.uniform(next(ks), (nm, h), jnp.float32, 1.0, 16.0)
    return {
        "embed": {"w": normal((padded_vocab(config), d),
                               EMBED_SCALE * d ** -0.5)},
        "final_norm": {"scale": ones(d)},
        "layers": {
            "mamba": {
                "norm": {"scale": ones(nm, d)},
                "mamba": {
                    "in_proj": {"w": normal((nm, d, 2 * di), d ** -0.5)},
                    "in_proj_bc": {"w": normal((nm, d, 2 * gn + h),
                                               d ** -0.5)},
                    "conv_w": normal((nm, w, di), w ** -0.5),
                    "conv_b": normal((nm, di), 0.1),
                    "conv_bc_w": normal((nm, w, 2 * gn), w ** -0.5),
                    "conv_bc_b": normal((nm, 2 * gn), 0.1),
                    "A_log": jnp.log(a),
                    "D": jnp.ones((nm, h), jnp.float32),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm": {"scale": ones(nm, di)},
                    "out_proj": {"w": normal((nm, di, d),
                                             di ** -0.5 * out)},
                },
            },
            "attn": {
                "norm": {"scale": ones(na, d)},
                "attn": {
                    "wq": {"w": normal((na, d, hq), d ** -0.5)},
                    "wk": {"w": normal((na, d, hkv), d ** -0.5)},
                    "wv": {"w": normal((na, d, hkv), d ** -0.5)},
                    "wo": {"w": normal((na, hq, d), hq ** -0.5 * out)},
                },
            },
            "mlp": {
                "norm": {"scale": ones(nl, d)},
                "mlp": {
                    "w_gate": {"w": normal((nl, d, f), d ** -0.5)},
                    "w_up": {"w": normal((nl, d, f), d ** -0.5)},
                    "w_down": {"w": normal((nl, f, d), f ** -0.5 * out)},
                },
            },
        },
    }


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


def _rms(config, x, w):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + config["rms_norm_eps"]) \
        * w.astype(jnp.float32)


def _gated_norm(config, y, z, w):
    """The mixer's output norm: rmsnorm(y * silu(z)) over the whole inner
    width (one group)."""
    return _rms(config, y * jax.nn.silu(z), w)


def _conv(x, w, b):
    """Causal depthwise conv over time: x (T, C), w (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    out = sum(xp[i:i + x.shape[0]] * w[i].astype(jnp.float32)
              for i in range(width))
    return out + b.astype(jnp.float32)


def _residual(config, x, m):
    return x + config["residual_multiplier"] * m


def mamba(config, lp, x, mm_dtype=None):
    """One Mamba-2 layer and its residual add on x: (T, d) float32."""
    p, s, d, di, h, gn = _dims(config)
    m = lp["mamba"]
    u = _rms(config, x, lp["norm"]["scale"])
    zx = _mm(u, m["in_proj"]["w"], mm_dtype)
    bcdt = _mm(u, m["in_proj_bc"]["w"], mm_dtype)
    z, xs = zx[:, :di], zx[:, di:]
    bc, dt = bcdt[:, :2 * gn], bcdt[:, 2 * gn:]
    xs = jax.nn.silu(_conv(xs, m["conv_w"], m["conv_b"]))
    bc = jax.nn.silu(_conv(bc, m["conv_bc_w"], m["conv_bc_b"]))
    bm, cm = bc[:, :gn], bc[:, gn:]                       # (T, N), 1 group
    dt = jax.nn.softplus(dt + m["dt_bias"])               # (T, H)
    a = -jnp.exp(m["A_log"])                              # (H,)
    xh = xs.reshape(x.shape[0], h, s["head_dim"])         # (T, H, P)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y_t = jnp.einsum("hpn,n->hp", state, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t

    s0 = jnp.zeros((h, s["head_dim"], s["d_state"]), jnp.float32)
    _, y = jax.lax.scan(step, s0, (xh, bm, cm, dt))
    y = (y + m["D"][None, :, None] * xh).reshape(x.shape[0], di)
    y = _gated_norm(config, y, z, m["norm"]["scale"])
    return _residual(config, x, _mm(y, m["out_proj"]["w"], mm_dtype))


def _rope(x, theta):
    """x: (T, H, dh); rotate-half RoPE at positions 0..T-1."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(config, lp, x, mm_dtype=None):
    """One attention layer and its residual add on x: (T, d) float32,
    T a multiple of the query block."""
    p = config["program"]
    t, dh = x.shape[0], p["head_dim"]
    hq, hkv = p["n_heads"], p["n_kv_heads"]
    a = lp["attn"]
    u = _rms(config, x, lp["norm"]["scale"])
    q = _mm(u, a["wq"]["w"], mm_dtype).reshape(t, hq, dh)
    k = _mm(u, a["wk"]["w"], mm_dtype).reshape(t, hkv, dh)
    v = _mm(u, a["wv"]["w"], mm_dtype).reshape(t, hkv, dh)
    if config.get("position_embedding_type") == "rope":
        q = _rope(q, config["rope_theta"])
        k = _rope(k, config["rope_theta"])
    k = jnp.repeat(k, hq // hkv, axis=1).transpose(1, 2, 0)   # (H, dh, T)
    v = jnp.repeat(v, hq // hkv, axis=1).transpose(1, 0, 2)   # (H, T, dh)
    scale = config.get("attention_multiplier", dh ** -0.5)
    nb = t // _Q_BLOCK

    def block(args):
        qb, i = args                                          # (Qb, H, dh)
        s = _mm(qb.transpose(1, 0, 2), k, mm_dtype) * scale   # (H, Qb, T)
        rows = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)
        s = jnp.where(jnp.arange(t)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        return _mm(jax.nn.softmax(s, -1), v, mm_dtype)        # (H, Qb, dh)

    o = jax.lax.map(block, (q.reshape(nb, _Q_BLOCK, hq, dh),
                            jnp.arange(nb)))                  # (nb,H,Qb,dh)
    o = o.transpose(0, 2, 1, 3).reshape(t, hq * dh)
    return _residual(config, x, _mm(o, a["wo"]["w"], mm_dtype))


def mlp(config, lp, x, mm_dtype=None):
    """The SwiGLU MLP that follows every mixer, and its residual add."""
    m = lp["mlp"]
    u = _rms(config, x, lp["norm"]["scale"])
    g = _mm(u, m["w_gate"]["w"], mm_dtype)
    up = _mm(u, m["w_up"]["w"], mm_dtype)
    return _residual(config, x, _mm(jax.nn.silu(g) * up,
                                    m["w_down"]["w"], mm_dtype))


def head_weight(config, params):
    """(d, vocab) float32 tied head, the padded rows left out."""
    return params["embed"]["w"][:config["program"]["vocab"]].T


def logits(config, params, h, mm_dtype=None):
    return _mm(_rms(config, h, params["final_norm"]["scale"]),
               head_weight(config, params), mm_dtype) \
        / config["logits_scaling"]


class _Frozen:
    """A hashable view of a configuration, for jit's static arguments."""

    def __init__(self, config):
        self.config = config
        self._key = repr(sorted((k, repr(v)) for k, v in config.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


def _jit(fn):
    return jax.jit(lambda fc, lp, x, mm: fn(fc.config, lp, x, mm),
                   static_argnums=(0, 3))


_LAYER = {"mamba": _jit(lambda c, lp, x, mm: mamba(c, lp, x, mm)),
          "attention": _jit(lambda c, lp, x, mm: attention(c, lp, x, mm))}
_MLP = _jit(lambda c, lp, x, mm: mlp(c, lp, x, mm))
_logits_c = jax.jit(lambda fc, params, h, mm: logits(fc.config, params, h,
                                                     mm),
                    static_argnums=(0, 3))
_KEY = {"mamba": "mamba", "attention": "attn"}


@jax.jit
def _gap(ref, pick):
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.max(best - got)


def _hidden(config, params, tokens, mm_dtype):
    """The residual stream after the last layer: (T, d) float32 for
    tokens (T,), T a multiple of the query block."""
    fc = _Frozen(config)
    x = params["embed"]["w"][tokens].astype(jnp.float32) \
        * config["embedding_multiplier"]
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(config["layer_types"]):
        j = seen[kind]
        seen[kind] += 1
        lp = jax.tree.map(lambda a: a[j], params["layers"][_KEY[kind]])
        x = _LAYER[kind](fc, lp, x, mm_dtype)
        x = _MLP(fc, jax.tree.map(lambda a: a[i], params["layers"]["mlp"]),
                 x, mm_dtype)
    return x


def served_gaps(config, params, prompt: np.ndarray, served: List[int],
                mm_dtype=None, rows: int = 512) -> Dict[str, float]:
    """Run the reference once over prompt + served tokens and return the
    widest gap by which a served token's logit lies below the
    reference's best at its position, and the count of tokens read.
    With `mm_dtype` it instead reads, at each position, the gap of the
    token the lower precision puts first (the control)."""
    toks = jnp.asarray(_padded(np.concatenate(
        [np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)]),
        _Q_BLOCK))
    n_p = len(prompt)
    fc = _Frozen(config)
    with jax.default_matmul_precision("highest"):
        h = _hidden(config, params, toks, None)
        hc = _hidden(config, params, toks, mm_dtype) \
            if mm_dtype is not None else None
        target = jnp.asarray(served, jnp.int32)
        worst = 0.0
        for s in range(0, len(served), rows):
            sl = slice(n_p - 1 + s, n_p - 1 + min(s + rows, len(served)))
            ref = _logits_c(fc, params, h[sl], None)
            pick = target[s:s + rows] if hc is None else jnp.argmax(
                _logits_c(fc, params, hc[sl], mm_dtype), -1)
            worst = max(worst, float(_gap(ref, pick)))
    return {"max_gap": worst, "tokens": len(served)}
