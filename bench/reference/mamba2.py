"""Plain float32 reference of a Mamba-2 language model, with the SSD
mixer computed as its sequential scan.

Per layer (Dao & Gu, arXiv:2405.21060, and mamba_ssm's Mamba2 layer):

    u            = rmsnorm(x)
    z, xBC, dt   = u W_in                 (W_in split as the program stores it)
    xBC          = silu(causal_depthwise_conv(xBC) + b)
    dt           = softplus(dt + dt_bias),  A = -exp(A_log)
    s_t          = exp(dt_t A) s_{t-1} + dt_t x_t B_t^T   (per head, ngroups 1)
    y_t          = s_t C_t + D x_t
    x           += W_out · rmsnorm(y * silu(z))

then a final RMSNorm and a head tied to the embedding. It imports
nothing of the program, and every matrix product runs under
``jax.default_matmul_precision("highest")``. The residual stream is
float32 throughout, as the published model's residual_in_fp32 asks, and
RMSNorm epsilon is the configuration's norm_epsilon (1e-5, as
published). Departures from the published model: weights are random
from the benchmark's seed, laid out as the program stores them (in_proj
split into z/x and B/C/dt parts, the conv split likewise).
"""

from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

def _dims(config):
    p = config["program"]
    s = p["ssm"]
    d = p["d_model"]
    di = s["expand"] * d
    h = di // s["head_dim"]
    gn = s["n_groups"] * s["d_state"]
    return p, s, d, di, h, gn


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
    """Seeded random weights in the program's layout (stacked layers).
    A, D and dt_bias are float32 as the program keeps them; dt follows
    the published init (log-uniform in [1e-3, 1e-1]), A_log the
    published range [1, 16]."""
    p, s, d, di, h, gn = _dims(config)
    nl, w = p["n_layers"], s["conv_width"]
    ks = iter(jax.random.split(key, 16))

    def normal(shape, scale):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * scale).astype(dtype)

    dt = jnp.exp(jax.random.uniform(next(ks), (nl, h), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    a = jax.random.uniform(next(ks), (nl, h), jnp.float32, 1.0, 16.0)
    params = {
        "embed": {"w": normal((padded_vocab(config), d), d ** -0.5)},
        "final_norm": {"scale": jnp.ones((d,), dtype)},
        "layers": {
            "norm": {"scale": jnp.ones((nl, d), dtype)},
            "mamba": {
                "in_proj": {"w": normal((nl, d, 2 * di), d ** -0.5)},
                "in_proj_bc": {"w": normal((nl, d, 2 * gn + h), d ** -0.5)},
                "conv_w": normal((nl, w, di), w ** -0.5),
                "conv_b": normal((nl, di), 0.1),
                "conv_bc_w": normal((nl, w, 2 * gn), w ** -0.5),
                "conv_bc_b": normal((nl, 2 * gn), 0.1),
                "A_log": jnp.log(a),
                "D": jnp.ones((nl, h), jnp.float32),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "norm": {"scale": jnp.ones((nl, di), dtype)},
                "out_proj": {"w": normal((nl, di, d),
                                         di ** -0.5 / math.sqrt(2 * nl))},
            },
        },
    }
    if not p.get("tie_embeddings"):
        params["lm_head"] = {"w": normal((d, padded_vocab(config)),
                                         d ** -0.5)}
    return params


def _mm(a, b, mm_dtype):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mm_dtype is not None:
        a = a.astype(mm_dtype).astype(jnp.float32)
        b = b.astype(mm_dtype).astype(jnp.float32)
    return jnp.matmul(a, b)


def _rms(config, x, w):
    x = x.astype(jnp.float32)
    eps = config["norm_epsilon"]
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _conv(x, w, b):
    """Causal depthwise conv over time: x (T, C), w (W, C)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((width - 1, 0), (0, 0)))
    out = sum(xp[i:i + x.shape[0]] * w[i].astype(jnp.float32)
              for i in range(width))
    return out + b.astype(jnp.float32)


def layer(config, lp, x, mm_dtype=None):
    """One Mamba-2 layer on x: (T, d) float32, one sequence."""
    p, s, d, di, h, gn = _dims(config)
    t = x.shape[0]
    m = lp["mamba"]
    u = _rms(config, x, lp["norm"]["scale"])
    zx = _mm(u, m["in_proj"]["w"], mm_dtype)
    bcdt = _mm(u, m["in_proj_bc"]["w"], mm_dtype)
    z, xs = zx[:, :di], zx[:, di:]
    bc, dt = bcdt[:, :2 * gn], bcdt[:, 2 * gn:]
    xs = jax.nn.silu(_conv(xs, m["conv_w"], m["conv_b"]))
    bc = jax.nn.silu(_conv(bc, m["conv_bc_w"], m["conv_bc_b"]))
    bm, cm = bc[:, :gn], bc[:, gn:]                       # (T, N), ngroups 1
    dt = jax.nn.softplus(dt + m["dt_bias"])               # (T, H)
    a = -jnp.exp(m["A_log"])                              # (H,)
    xh = xs.reshape(t, h, s["head_dim"])                  # (T, H, P)

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        y_t = jnp.einsum("hpn,n->hp", state, c_t,
                         precision=jax.lax.Precision.HIGHEST)
        return state, y_t

    s0 = jnp.zeros((h, s["head_dim"], s["d_state"]), jnp.float32)
    _, y = jax.lax.scan(step, s0, (xh, bm, cm, dt))
    y = y + m["D"][None, :, None] * xh
    y = y.reshape(t, di)
    y = _rms(config, y * jax.nn.silu(z), m["norm"]["scale"])
    return x + _mm(y, m["out_proj"]["w"], mm_dtype)


def head_weight(config, params):
    v = config["program"]["vocab"]
    if config["program"].get("tie_embeddings"):
        return params["embed"]["w"][:v].T
    return params["lm_head"]["w"][:, :v]


def logits(config, params, h, mm_dtype=None):
    return _mm(_rms(config, h, params["final_norm"]["scale"]),
               head_weight(config, params), mm_dtype)


class _Frozen:
    """A hashable view of a configuration, for jit's static arguments."""

    def __init__(self, config):
        self.config = config
        self._key = repr(sorted((k, repr(v))
                                for k, v in config["program"].items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._key == other._key


_layer_c = jax.jit(lambda fc, lp, x, mm: layer(fc.config, lp, x, mm),
                   static_argnums=(0, 3))
_logits_c = jax.jit(lambda fc, params, h, mm: logits(fc.config, params, h, mm),
                    static_argnums=(0, 3))


@jax.jit
def _gap(ref, pick):
    best = jnp.max(ref, -1)
    got = jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
    return jnp.max(best - got)


def _hidden(config, params, tokens, mm_dtype):
    fc = _Frozen(config)
    x = params["embed"]["w"][tokens].astype(jnp.float32)
    for i in range(config["program"]["n_layers"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        x = _layer_c(fc, lp, x, mm_dtype)
    return x


def served_gaps(config, params, prompt: np.ndarray, served: List[int],
                mm_dtype=None, rows: int = 512) -> Dict[str, float]:
    """As dense_gqa.served_gaps: the widest gap of a served token below
    the reference's best, layer by layer over prompt + served tokens."""
    toks = jnp.asarray(_padded(np.concatenate(
        [np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])))
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
