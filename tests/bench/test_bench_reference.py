"""The float32 references (bench/reference/) against the program's xla
path at a small width, on weights the benchmark makes from a seed: the
full-sequence logits of both families, and the training loss and its
gradient for the dense one. Both sides run in float32, so they agree to
rounding; a reference that left out a part of the layer (qk-norm, RoPE,
the gate, the conv, the D skip, the state decay) would not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from harness import spec
from repro.models import model as M


def _setup(name, seed=7):
    cfg_json = bench_tiny.tiny_config(name)
    cfg_json["program"]["dtype"] = "float32"
    if "norm_epsilon" in cfg_json:
        # the program's Mamba-2 RMSNorm has epsilon 1e-6, not the
        # published 1e-5 that the reference runs; the layer's structure
        # is compared at the program's epsilon
        cfg_json["norm_epsilon"] = 1e-6
    ref = spec.reference(cfg_json)
    params = ref.init_params(cfg_json, jax.random.PRNGKey(seed), jnp.float32)
    cfg = spec.model_config(cfg_json, "float32")
    tokens = np.random.default_rng(seed).integers(
        0, cfg.vocab, (2, 40)).astype(np.int32)
    return cfg_json, ref, params, cfg, tokens


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("name", ["qwen3-0.6b", "mamba2-2.7b"])
def test_logits_match_program(name):
    cfg_json, ref, params, cfg, tokens = _setup(name)
    with jax.default_matmul_precision("highest"):
        got, _ = M.forward(cfg, params, {"tokens": jnp.asarray(tokens)})
        got = got[..., :cfg.vocab]
        for b in range(tokens.shape[0]):
            toks = jnp.asarray(tokens[b])
            h = ref._hidden(cfg_json, params,
                            toks[None] if name.startswith("qwen") else toks,
                            None)
            h = h[0] if name.startswith("qwen") else h
            want = ref.logits(cfg_json, params, h)
            assert _rel(got[b], want) < 1e-4


def test_reference_departs_when_a_part_is_left_out():
    cfg_json, ref, params, cfg, tokens = _setup("qwen3-0.6b")
    toks = jnp.asarray(tokens[:1])
    with jax.default_matmul_precision("highest"):
        want = ref.logits(cfg_json, params,
                          ref._hidden(cfg_json, params, toks, None))
        bad = dict(cfg_json, program=dict(cfg_json["program"],
                                          rope_theta=10_000.0))
        other = ref.logits(bad, params, ref._hidden(bad, params, toks, None))
    assert _rel(other, want) > 1e-2


def test_train_loss_and_grad_match_program():
    cfg_json, ref, params, cfg, tokens = _setup("qwen3-0.6b")
    labels = np.roll(tokens, -1, axis=1)
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch), has_aux=True)(params)
        lr, gr = jax.value_and_grad(
            lambda p: ref.loss(cfg_json, p, batch["tokens"],
                               batch["labels"]))(params)
    assert abs(float(lp) - float(lr)) / float(lr) < 1e-5
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
            jnp.max(jnp.abs(b))) + 1e-7
