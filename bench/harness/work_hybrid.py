"""The work an interleaved Mamba-2 / attention model (Granite 4.0-H)
requires, from its configuration file's program block: the least bytes
of a decode step and of a slot's recurrent state and KV. Counts follow
the model's shapes (its layer types and widths), never a kernel's
tiles; bytes are each operand read once and each result written once,
in the model's dtypes (bf16 weights and activations, f32 state)."""

from __future__ import annotations

from harness.work import BF16, F32


def _p(config):
    return config["program"]


def layer_counts(config):
    """(Mamba-2 layers, attention layers, all layers)."""
    t = _p(config)["layer_types"]
    return t.count("mamba"), t.count("attention"), len(t)


def _ssd_dims(config):
    p = _p(config)
    s = p["ssm"]
    di = s["expand"] * p["d_model"]
    return s, di, di // s["head_dim"], s["n_groups"] * s["d_state"]


def weight_bytes(config) -> float:
    """Every weight one decode step reads: the layers' and the tied
    head's (the embedding table)."""
    p = _p(config)
    d, f, dh = p["d_model"], p["d_ff"], p["head_dim"]
    s, di, h, gn = _ssd_dims(config)
    conv = di + 2 * gn
    mamba = BF16 * (d * 2 * di + d * (2 * gn + h) + di * d
                    + (s["conv_width"] + 1) * conv + d + di) + F32 * 3 * h
    hq, hkv = p["n_heads"] * dh, p["n_kv_heads"] * dh
    attn = BF16 * (2 * d * hq + 2 * d * hkv + d)
    mlp = BF16 * (3 * d * f + d)
    nm, na, nl = layer_counts(config)
    return nm * mamba + na * attn + nl * mlp + BF16 * (p["vocab"] + 1) * d


def state_bytes(config) -> float:
    """One slot's recurrent state over all Mamba-2 layers: the f32 SSD
    state and the bf16 conv windows."""
    s, di, h, gn = _ssd_dims(config)
    per = F32 * h * s["head_dim"] * s["d_state"] \
        + BF16 * (s["conv_width"] - 1) * (di + 2 * gn)
    return layer_counts(config)[0] * per


def kv_bytes(config, context: int) -> float:
    """Cached keys and values of one slot at `context` positions, over
    the attention layers."""
    p = _p(config)
    return layer_counts(config)[1] * 2 * BF16 * context \
        * p["n_kv_heads"] * p["head_dim"]


def decode_floor_bytes(config, contexts) -> float:
    """Least HBM bytes of one decode step with a slot at each of
    `contexts`: the weights read once, each active slot's state read
    and written, its cached K/V read."""
    return weight_bytes(config) + sum(
        2 * state_bytes(config) + kv_bytes(config, c) for c in contexts)
