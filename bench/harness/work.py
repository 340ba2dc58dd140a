"""The work a model's shapes require: operations and bytes per token and
per kernel call, computed from the configuration file alone.

These counts are the numerators of every roofline share and of every
``mfu``: the work the tokens actually processed need (active rows, real
lengths), never what an implementation happens to do (padded rows, tile
sizes, recomputation). Bytes are the least a kernel must move: each
operand read once, each result written once.

Dense-GQA (qwen3-style) and Mamba-2 (SSD) families are covered.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Tuple

BF16 = 2
F32 = 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def seconds(self, peak: Dict[str, float]) -> float:
        """Least time on a chip with these peaks: the larger of the
        compute bound and the memory bound."""
        return max(self.flops / peak["flops_bf16"],
                   self.bytes / peak["hbm_bytes_per_s"])


ZERO = Work(0.0, 0.0)


def _p(config):
    return config["program"]


def gemm(m: int, k: int, n: int, out_bytes: int = BF16) -> Work:
    """(m, k) @ (k, n) in bf16."""
    return Work(2.0 * m * k * n, BF16 * (m * k + k * n) + out_bytes * m * n)


def gated_gemm(m: int, k: int, f: int) -> Work:
    """silu(x @ Wg) * (x @ Wu): two GEMMs sharing x, one result."""
    return Work(4.0 * m * k * f, BF16 * (m * k + 2 * k * f + m * f))


def layer_gemms(config) -> List[Tuple[str, int, int]]:
    """(name, K, N) of every dense projection of one layer."""
    p = _p(config)
    d = p["d_model"]
    if p["family"] == "dense":
        dh = p["head_dim"] or d // p["n_heads"]
        hq, hkv = p["n_heads"] * dh, p["n_kv_heads"] * dh
        return [("wq", d, hq), ("wk", d, hkv), ("wv", d, hkv),
                ("wo", hq, d), ("w_gate", d, p["d_ff"]),
                ("w_up", d, p["d_ff"]), ("w_down", p["d_ff"], d)]
    if p["family"] == "ssm":
        s = p["ssm"]
        di = s["expand"] * d
        h = di // s["head_dim"]
        gn2 = 2 * s["n_groups"] * s["d_state"]
        return [("in_proj", d, 2 * di), ("in_proj_bc", d, gn2 + h),
                ("out_proj", di, d)]
    raise ValueError(f"no work counts for family {p['family']!r}")


def head_shape(config) -> Tuple[int, int]:
    p = _p(config)
    return p["d_model"], p["vocab"]


def matmul_params(config) -> int:
    """Weights that take part in a matrix product, the head included."""
    p = _p(config)
    k, n = head_shape(config)
    return p["n_layers"] * sum(a * b for _, a, b in layer_gemms(config)) \
        + k * n


def param_count(config) -> int:
    """All parameters of the published model (embedding counted once
    where it is tied to the head)."""
    p = _p(config)
    d, v = p["d_model"], p["vocab"]
    embed = d * v * (1 if p.get("tie_embeddings") else 2)
    per_layer = sum(a * b for _, a, b in layer_gemms(config))
    if p["family"] == "dense":
        dh = p["head_dim"] or d // p["n_heads"]
        per_layer += 2 * d + (2 * dh if p.get("qk_norm") else 0)
    else:
        s = p["ssm"]
        di = s["expand"] * d
        h = di // s["head_dim"]
        conv_dim = di + 2 * s["n_groups"] * s["d_state"]
        per_layer += d + s["conv_width"] * conv_dim + conv_dim + 3 * h + di
    return p["n_layers"] * per_layer + embed + d


# ----------------------------------------------------------------------
# mixers: attention and SSD
# ----------------------------------------------------------------------

def _attn_dims(config):
    p = _p(config)
    dh = p["head_dim"] or p["d_model"] // p["n_heads"]
    return p["n_heads"], p["n_kv_heads"], dh


def attention_prefill(config, length: int, batch: int = 1) -> Work:
    """Causal self-attention over `length` tokens, one layer: QK^T and
    PV over the L(L+1)/2 causal pairs; q, k, v read and o written."""
    h, hkv, dh = _attn_dims(config)
    pairs = length * (length + 1) / 2
    return Work(batch * 4.0 * h * dh * pairs,
                batch * BF16 * length * dh * (2 * h + 2 * hkv))


def attention_backward(config, length: int, batch: int = 1) -> Work:
    """Its gradient: four products over the causal pairs (dV, dP, dQ,
    dK); q, k, v, o, dO read, dQ, dK, dV written."""
    h, hkv, dh = _attn_dims(config)
    pairs = length * (length + 1) / 2
    return Work(batch * 8.0 * h * dh * pairs,
                batch * BF16 * length * dh * (5 * h + 4 * hkv))


def attention_decode(config, context: int) -> Work:
    """One new token attending to `context` cached keys (itself
    included), one layer: the cached K and V prefix read once."""
    h, hkv, dh = _attn_dims(config)
    return Work(4.0 * h * dh * context,
                BF16 * (2 * context * hkv * dh + 2 * h * dh))


def ssd_token(config) -> Work:
    """One token through one layer's SSD recurrence: decay, B x^T and
    C s over the (H, P, N) state, with the f32 state read and written."""
    s = _p(config)["ssm"]
    d = _p(config)["d_model"]
    h = s["expand"] * d // s["head_dim"]
    hpn = h * s["head_dim"] * s["d_state"]
    return Work(5.0 * hpn, 2 * F32 * hpn)


def mixer_flops(config, context: int) -> float:
    """Mixer operations of one token at position `context` - 1, one
    layer (attention over `context` keys, or one SSD step)."""
    if _p(config)["family"] == "dense":
        h, _, dh = _attn_dims(config)
        return 4.0 * h * dh * context
    return ssd_token(config).flops


# ----------------------------------------------------------------------
# model operations per token (for mfu)
# ----------------------------------------------------------------------

def forward_flops_prompt(config, length: int) -> float:
    """Forward operations of a whole prompt of `length` tokens."""
    p = _p(config)
    dense = 2.0 * matmul_params(config) * length
    if p["family"] == "dense":
        mix = attention_prefill(config, length).flops
    else:
        mix = ssd_token(config).flops * length
    return dense + p["n_layers"] * mix


def forward_flops_token(config, context: int) -> float:
    """Forward operations of one decoded token with `context` keys."""
    return 2.0 * matmul_params(config) \
        + _p(config)["n_layers"] * mixer_flops(config, context)


def train_flops_step(config, batch: int, seq: int) -> float:
    """Forward and backward operations of one training step, without
    recomputation: the backward pass costs twice the forward."""
    return 3.0 * batch * forward_flops_prompt(config, seq)


# ----------------------------------------------------------------------
# GEMM work of the calls a step makes
# ----------------------------------------------------------------------

def least_s(calls: List[Work], peak: Dict[str, float]) -> float:
    """Least time of a list of calls: each call's own roofline bound,
    summed (one call cannot lend its idle compute to another)."""
    return sum(w.seconds(peak) for w in calls)


def train_gemm_calls(config, batch: int, seq: int) -> List[Work]:
    """GEMM calls of one training step: each forward projection, and
    the two products of its gradient, dX = dY W^T and dW = X^T dY (for
    SwiGLU, both of its weights')."""
    p = _p(config)
    m = batch * seq
    gemms = layer_gemms(config)
    calls: List[Work] = []
    for name, k, n in gemms:
        calls += [gemm(m, k, n), gemm(m, n, k), gemm(k, m, n)]
    calls = calls * p["n_layers"]
    k, n = head_shape(config)
    calls += [gemm(m, k, n, out_bytes=F32), gemm(m, n, k), gemm(k, m, n)]
    return calls
