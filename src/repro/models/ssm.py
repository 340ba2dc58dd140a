"""Mamba-2 (SSD, state-space duality) blocks.

The SSD dual form is itself a *blocked matmul algorithm*: the sequence
is chunked, intra-chunk terms are dense (decay-masked) matmuls and
inter-chunk terms are a rank-N state recurrence — i.e. the paper's
tiling idea applied along time. This makes mamba2-2.7b the assigned
architecture that most directly exercises the contribution (DESIGN §6).

Shapes follow the Mamba-2 paper: d_inner = expand*d_model, H heads of
size P=head_dim, G state groups of size N=d_state, short causal
depthwise conv of width W over the (x, B, C) channels.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ssd as _core_ssd
from repro.distributed.context import constrain
from repro.kernels.ssd import _segsum, ssd_chunked   # noqa: F401  (compat)
from repro.models import layers as L


# ----------------------------------------------------------------------
# Mamba-2 block
# ----------------------------------------------------------------------

def _dims(cfg, d_model=None):
    sc = cfg.ssm
    d = d_model or cfg.d_model
    d_inner = sc.expand * d
    h = d_inner // sc.head_dim
    conv_dim = d_inner + 2 * sc.n_groups * sc.d_state
    return d, d_inner, h, conv_dim


def mamba_init(key, cfg, *, d_model=None):
    """Two projections, not one: z/x (wide, TP-sharded over "model") and
    B/C/dt (narrow, replicated). A single fused in_proj shards its
    output dim over "model", which strands the 2GN B/C channels on one
    shard and forces a per-layer broadcast — measured as the dominant
    collective on mamba2 prefill (EXPERIMENTS §Perf it4)."""
    sc = cfg.ssm
    d, d_inner, h, conv_dim = _dims(cfg, d_model)
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    gn2 = 2 * sc.n_groups * sc.d_state
    dt = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32) *
                 (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return {
        "in_proj": L.dense_init(ks[0], d, 2 * d_inner, dtype=dtype),
        "in_proj_bc": L.dense_init(ks[4], d, gn2 + h, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (sc.conv_width, d_inner),
                                     jnp.float32)
                   * (sc.conv_width ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((d_inner,), dtype),
        "conv_bc_w": (jax.random.normal(ks[3], (sc.conv_width, gn2),
                                        jnp.float32)
                      * (sc.conv_width ** -0.5)).astype(dtype),
        "conv_bc_b": jnp.zeros((gn2,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
        "D": jnp.ones((h,), jnp.float32),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(jnp.float32),
        "norm": L.rmsnorm_init(d_inner, dtype=dtype),
        "out_proj": L.dense_init(ks[5], d_inner, d, dtype=dtype,
                                 scale=d_inner ** -0.5
                                 / (2 * cfg.n_layers) ** 0.5),
    }


def _project(p, x, cfg, d_model=None):
    """-> (z, x_pre_conv, bc_pre_conv, dt_raw)."""
    sc = cfg.ssm
    _, d_inner, h, _ = _dims(cfg, d_model)
    zx = L.dense_apply(p["in_proj"], x)
    bcdt = L.dense_apply(p["in_proj_bc"], x)
    z = zx[..., :d_inner]
    xs = zx[..., d_inner:]
    bc = bcdt[..., :-h]
    dt = bcdt[..., -h:]
    return z, xs, bc, dt


def _causal_conv(xbc, w, b):
    """Depthwise causal conv over (B, L, C) with weight (W, C)."""
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i:i + xbc.shape[1], :] * w[i][None, None, :]
        for i in range(width))
    return out + b[None, None, :]


def mamba_apply(p, x, cfg, *, d_model=None, return_state: bool = False):
    """Full-sequence (train / prefill) pass. x: (B, L, D)."""
    sc = cfg.ssm
    _, d_inner, h, conv_dim = _dims(cfg, d_model)
    bsz, l, _ = x.shape
    gn = sc.n_groups * sc.d_state

    z, xs_pre, bc_pre, dt_raw = _project(p, x, cfg, d_model)
    z = constrain(z, "dp", None, "tp")
    xs_pre = constrain(xs_pre, "dp", None, "tp")
    bc_pre = constrain(bc_pre, "dp", None, None)     # replicated (tiny)
    xsc = jax.nn.silu(_causal_conv(xs_pre, p["conv_w"].astype(x.dtype),
                                   p["conv_b"].astype(x.dtype)))
    bcc = jax.nn.silu(_causal_conv(bc_pre, p["conv_bc_w"].astype(x.dtype),
                                   p["conv_bc_b"].astype(x.dtype)))
    xs = xsc.reshape(bsz, l, h, sc.head_dim)
    xs = constrain(xs, "dp", None, "tp", None)
    b_ = bcc[..., :gn].reshape(bsz, l, sc.n_groups, sc.d_state)
    c_ = bcc[..., gn:].reshape(bsz, l, sc.n_groups, sc.d_state)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"][None, None, :])        # (B,L,H)
    a_neg = -jnp.exp(p["A_log"])[None, None, :] * dt           # (B,L,H)

    # pad L to a chunk multiple; dt=0 makes pad steps exact identities
    # for the recurrence (decay exp(0)=1, zero state contribution).
    chunk = min(sc.chunk, l)
    pad = (-l) % chunk
    xdt = xs.astype(jnp.float32) * dt[..., None]
    bf = b_.astype(jnp.float32)
    cf = c_.astype(jnp.float32)
    if pad:
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        xdt = jnp.pad(xdt, pad4)
        bf = jnp.pad(bf, pad4)
        cf = jnp.pad(cf, pad4)
        a_neg = jnp.pad(a_neg, ((0, 0), (0, pad), (0, 0)))

    # tagged for the roofline analyzer: the chunk-interior tensors
    # (decay masks, CB scores) are VMEM-resident in a fused SSD kernel
    # (the Mamba-2 paper's own kernel design; our Pallas analogue is the
    # §Perf substitution model).
    with jax.named_scope("ssdsite"):
        y, s_final = _core_ssd.ssd(xdt, a_neg, bf, cf, chunk)
    y = y[:, :l]
    y = y + p["D"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, l, d_inner).astype(x.dtype)
    y = constrain(y, "dp", None, "tp")
    y = L.rmsnorm_apply(p["norm"], y * jax.nn.silu(z), eps=cfg.norm_eps)
    out = constrain(L.dense_apply(p["out_proj"], y), "dp", None, None)
    if not return_state:
        return out, None
    # conv caches: last (W-1) *pre-conv* channel values. Prompts shorter
    # than W-1 left-pad the *projected* tail with zeros — matching the
    # zero conv buffers of mamba_init_state, which is exactly what the
    # running conv would hold after only `l` tokens. (Padding x before
    # projection would be wrong: a biased dense of zeros is not zero.)
    w1 = sc.conv_width - 1
    tail = x[:, -w1:]
    _, xs_tail, bc_tail, _ = _project(p, tail, cfg, d_model)
    if tail.shape[1] < w1:
        padn = w1 - tail.shape[1]
        pad3 = ((0, 0), (padn, 0), (0, 0))
        xs_tail = jnp.pad(xs_tail, pad3)
        bc_tail = jnp.pad(bc_tail, pad3)
    return out, {"ssd": s_final, "conv": xs_tail, "conv_bc": bc_tail}


def mamba_init_state(cfg, bsz, *, d_model=None, dtype=jnp.float32):
    sc = cfg.ssm
    _, d_inner, h, conv_dim = _dims(cfg, d_model)
    gn2 = 2 * sc.n_groups * sc.d_state
    return {
        "ssd": jnp.zeros((bsz, h, sc.head_dim, sc.d_state), jnp.float32),
        "conv": jnp.zeros((bsz, sc.conv_width - 1, d_inner), dtype),
        "conv_bc": jnp.zeros((bsz, sc.conv_width - 1, gn2), dtype),
    }


def mamba_decode(p, x_t, cfg, state, *, d_model=None):
    """Single-token step. x_t: (B, 1, D); state keys: ssd/conv/conv_bc."""
    sc = cfg.ssm
    _, d_inner, h, conv_dim = _dims(cfg, d_model)
    bsz = x_t.shape[0]
    gn = sc.n_groups * sc.d_state

    z, xs_new, bc_new, dt_raw = _project(p, x_t, cfg, d_model)

    def conv_step(buf, new, w, bias):
        cat = jnp.concatenate([buf, new.astype(buf.dtype)], axis=1)
        out = jnp.einsum("bwc,wc->bc", cat.astype(x_t.dtype),
                         w.astype(x_t.dtype))
        return jax.nn.silu(out + bias.astype(x_t.dtype)), cat[:, 1:]

    xbc, new_conv = conv_step(state["conv"], xs_new, p["conv_w"],
                              p["conv_b"])
    bcc, new_conv_bc = conv_step(state["conv_bc"], bc_new, p["conv_bc_w"],
                                 p["conv_bc_b"])

    xs = xbc.reshape(bsz, h, sc.head_dim)
    b_ = bcc[:, :gn].reshape(bsz, sc.n_groups, sc.d_state)
    c_ = bcc[:, gn:].reshape(bsz, sc.n_groups, sc.d_state)
    rep = h // sc.n_groups
    b_h = jnp.repeat(b_, rep, axis=1).astype(jnp.float32)      # (B,H,N)
    c_h = jnp.repeat(c_, rep, axis=1).astype(jnp.float32)

    dt = jax.nn.softplus(dt_raw[:, 0].astype(jnp.float32)
                         + p["dt_bias"][None, :])              # (B,H)
    da = jnp.exp(-jnp.exp(p["A_log"])[None, :] * dt)           # (B,H)
    xf = xs.astype(jnp.float32) * dt[..., None]                # (B,H,P)

    s = state["ssd"] * da[..., None, None] + jnp.einsum(
        "bhn,bhp->bhpn", b_h, xf)
    y = jnp.einsum("bhn,bhpn->bhp", c_h, s)
    y = y + p["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(bsz, 1, d_inner).astype(x_t.dtype)
    y = L.rmsnorm_apply(p["norm"], y * jax.nn.silu(z), eps=cfg.norm_eps)
    out = L.dense_apply(p["out_proj"], y)
    return out, {"ssd": s, "conv": new_conv, "conv_bc": new_conv_bc}
