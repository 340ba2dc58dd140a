"""The interleaved Mamba-2 / attention hybrid (granite-4.0-h-micro) at a
small width against its float32 reference (bench/reference/
granite_hybrid.py), on weights the reference makes from a seed, and the
unchanged lowering of the models that do not use the new fields.

Both sides run in float32 under "highest" matmul precision, so they
agree to rounding: the program sums in another order (chunked SSD and
chunked attention against the reference's sequential scan and dense
softmax), which moves logits by about 1e-6 of their largest magnitude.
The tolerance, 1e-4 of that magnitude, leaves a hundredfold margin for
rounding, while each part of the layer left out of the reference (a
multiplier, the gated norm) or added to it (RoPE) moves the logits by
more than 1e-2 of it."""

import dataclasses
import hashlib
import importlib.util
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT / "bench") not in sys.path:
    sys.path.insert(0, str(ROOT / "bench"))

from harness import spec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import spans  # noqa: E402
from repro.core.policy import Policy  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.serving import ServingEngine  # noqa: E402
from repro.serving.sampler import Sampler  # noqa: E402
from repro.training import train_loop as TL  # noqa: E402

# largest |logit| difference allowed, as a share of the largest |logit|
# (see the module docstring)
TOL = 1e-4

# two periods of a layer pattern with a run of two Mamba-2 layers
TYPES = ["mamba", "mamba", "attention", "mamba"] * 2
TINY = {"n_layers": len(TYPES), "d_model": 64, "n_heads": 4,
        "n_kv_heads": 2, "head_dim": 16, "d_ff": 128, "vocab": 250,
        "vocab_pad_to": 128, "dtype": "float32", "layer_types": TYPES,
        "ssm": {"d_state": 16, "head_dim": 16, "expand": 2,
                "conv_width": 4, "chunk": 16, "n_groups": 1}}


def _load_reference():
    path = ROOT / "bench" / "reference" / "granite_hybrid.py"
    sp = importlib.util.spec_from_file_location("granite_hybrid_ref", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


REF = _load_reference()


def tiny_config() -> dict:
    """The benchmark's configuration file with its widths cut; the
    published multipliers but one, epsilon and position embedding kept.
    At random weights the published attention scale (1/64) leaves the
    softmax almost flat, so that neither the scale nor RoPE would show
    in the logits; the tiny model's scale is 1.0 (4x head_dim^-0.5)
    in the program and the reference alike."""
    with open(ROOT / "bench" / "configs" / "granite-4.0-h-micro.json") as f:
        cfg = json.load(f)
    cfg["program"].update(TINY, attention_multiplier=1.0)
    cfg.update(layer_types=TYPES, attention_multiplier=1.0)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg_json = tiny_config()
    params = REF.init_params(cfg_json, jax.random.PRNGKey(11), jnp.float32)
    cfg = spec.model_config(cfg_json, "float32")
    return cfg_json, params, cfg


def _reference_logits(cfg_json, params, tokens):
    toks = jnp.asarray(REF._padded(np.asarray(tokens, np.int32)))
    with jax.default_matmul_precision("highest"):
        h = REF._hidden(cfg_json, params, toks, None)
        return REF.logits(cfg_json, params, h)[:len(tokens)]


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _tokens(n, seed=5):
    return np.random.default_rng(seed).integers(0, 250, (2, n)).astype(
        np.int32)


def test_config_file_runs_the_registered_program():
    """The benchmark's program block is the registered configuration."""
    with open(ROOT / "bench" / "configs" / "granite-4.0-h-micro.json") as f:
        cfg_json = json.load(f)
    got = spec.model_config(cfg_json, "float32")
    assert got == dataclasses.replace(get_config("granite-4.0-h-micro"),
                                      dtype="bfloat16")
    assert list(got.layer_types) == cfg_json["layer_types"]


def test_forward_matches_reference(tiny):
    cfg_json, params, cfg = tiny
    tokens = _tokens(40)
    with jax.default_matmul_precision("highest"):
        got, _ = M.forward(cfg, params, {"tokens": jnp.asarray(tokens)})
    for b in range(tokens.shape[0]):
        want = _reference_logits(cfg_json, params, tokens[b])
        assert _rel(got[b, :, :cfg.vocab], want) < TOL


class _RowLog(list):
    """A request's `generated` list that takes, with each token, the
    logits row the engine sampled it from."""

    def __init__(self, sampler):
        super().__init__()
        self.rows = []
        self._sampler = sampler

    def append(self, tok):
        self.rows.append(self._sampler.last)
        super().append(tok)


class _Greedy(Sampler):
    def __call__(self, logits):
        self.last = np.array(logits)
        return super().__call__(logits)


def _serve(cfg, params, prompts, gens, **kw):
    sampler = _Greedy()
    eng = ServingEngine(cfg, params, max_slots=2, max_len=64,
                        prefill_chunk=16, sampler=sampler, **kw)
    reqs = [eng.submit(p, g) for p, g in zip(prompts, gens)]
    for r in reqs:
        r.generated = _RowLog(sampler)
    with jax.default_matmul_precision("highest"):
        eng.run()
    return eng, reqs


def test_engine_prefill_and_cached_decode_match_reference(tiny):
    """Three requests over two slots: bucket prefill, one-token
    remainder steps, the slot copy of both kinds of state, batched
    decode and a mid-stream admission. The logits of every served
    position match the reference's full forward over prompt + served
    tokens."""
    cfg_json, params, cfg = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
               for n in (21, 37, 16)]
    eng, reqs = _serve(cfg, params, prompts, [6, 5, 7])
    assert max(r.t_admitted for r in reqs) > min(r.t_finished for r in reqs)
    for r, p in zip(reqs, prompts):
        seq = np.concatenate([p, np.asarray(r.generated[:-1], np.int32)])
        want = _reference_logits(cfg_json, params, seq)[len(p) - 1:]
        got = jnp.asarray(np.stack(r.generated.rows))
        assert got.shape == want.shape
        assert _rel(got, want) < TOL, r.rid


def test_engine_spans_carry_state_and_kv_bytes(tiny):
    cfg_json, params, cfg = tiny
    spans.disable()
    spans.reset()
    spans.enable()
    try:
        prompt = np.arange(20, dtype=np.int32)
        eng, _ = _serve(cfg, params, [prompt], [3])
    finally:
        spans.disable()
    ring = spans.snapshot()
    spans.reset()
    sub = M.init_cache(cfg, 1, eng.max_len)
    kv = sum(a.nbytes for a in jax.tree.leaves(sub["attn"]))
    state = sum(a.nbytes for a in jax.tree.leaves(sub["mamba"]))
    for name in ("repro.engine.admit", "repro.engine.admit.slot_copy"):
        (s,) = [s for s in ring if s.name == name]
        assert (s.attrs["state_bytes"], s.attrs["kv_bytes"]) == (state, kv)
    decodes = [s for s in ring if s.name == "repro.engine.decode"]
    assert decodes and all(s.attrs["state_bytes"] == 2 * state
                           for s in decodes)


def _left_out(part, cfg_json, monkeypatch):
    """The reference's configuration with one part of the layer left out
    (or, for RoPE, put in)."""
    # a key of its own, so that the reference's jitted layers trace anew
    bad = dict(cfg_json, left_out=part)
    if part == "rope":
        bad["position_embedding_type"] = "rope"
    elif part == "gated_norm":
        monkeypatch.setattr(REF, "_gated_norm",
                            lambda config, y, z, w: y * jax.nn.silu(z))
    elif part == "attention_multiplier":
        del bad["attention_multiplier"]
    else:
        bad[part] = 1
    return bad


@pytest.mark.parametrize("part", ["residual_multiplier",
                                  "attention_multiplier", "rope",
                                  "gated_norm", "embedding_multiplier",
                                  "logits_scaling"])
def test_reference_departs_when_a_part_is_left_out(tiny, part, monkeypatch):
    cfg_json, params, cfg = tiny
    tokens = _tokens(40)[0]
    with jax.default_matmul_precision("highest"):
        got, _ = M.forward(cfg, params, {"tokens": jnp.asarray(tokens[None])})
    got = got[0, :, :cfg.vocab]
    assert _rel(got, _reference_logits(cfg_json, params, tokens)) < TOL
    bad = _left_out(part, cfg_json, monkeypatch)
    assert _rel(got, _reference_logits(bad, params, tokens)) > 1e-2


# ----------------------------------------------------------------------
# models that do not use the new fields lower as they did before them
# ----------------------------------------------------------------------

# sha256 of the StableHLO text (jax 0.9.0, no debug info) of qwen3-0.6b
# (reduced) at the commit before layer_types, norm_eps and the four
# multipliers: a change here is a change to the program every qwen3
# cell runs. Regenerate deliberately, with the change that moves it.
LOWERED = {
    ("serve", "xla"):
        "2b981e2419865e360d0a46ab8ca701b607ba7bc8a01a4c69d673cbedd1ed5918",
    ("prefill", "xla"):
        "5ed9a8f09d8a27445b85d1d9437b6be5ac0ab4b881a842aa3002e145a5757862",
    ("serve", "pallas"):
        "3e5196f98f71cdd2f54d45bf2492841e622a5187c742e2e6448206db2c970653",
    ("prefill", "pallas"):
        "f9eb764a02c389ac0db9921f9606027507808ee4cd9f61b5572e3af9d2039366",
    ("train", "xla"):
        "e8ef3f1db7194857b90edac9a9916032783383d488e666a4354a10d345f4841e",
    ("train", "pallas"):
        "1e73e972991a8ba73c2f34d5d476289767b00cacc1cbf5fcdd4a59850315b7e2",
}
POLICIES = {"xla": Policy(),
            "pallas": Policy(backend="pallas", interpret=True)}


@pytest.mark.parametrize("step,backend", sorted(LOWERED))
def test_qwen3_lowers_as_before_the_new_fields(step, backend):
    cfg = get_config("qwen3-0.6b", reduced=True)
    pol = POLICIES[backend]
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if step == "serve":
        cache = jax.eval_shape(lambda: M.init_cache(cfg, 2, 64))
        low = jax.jit(TL.make_serve_step(cfg, policy=pol)).lower(
            params, i32(2, 1), i32(2), cache)
    elif step == "prefill":
        cache = jax.eval_shape(lambda: M.init_cache(cfg, 1, 64))
        low = jax.jit(TL.make_prefill(cfg, policy=pol)).lower(
            params, {"tokens": i32(1, 16)}, cache)
    else:
        opt = AdamW(lr=1e-3)
        state = jax.eval_shape(
            lambda: TL.init_state(cfg, opt, jax.random.PRNGKey(0)))
        low = jax.jit(TL.make_train_step(cfg, opt, policy=pol)).lower(
            state, {"tokens": i32(2, 16), "labels": i32(2, 16)})
    text = low.as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == LOWERED[step, backend]
