"""Per-architecture smoke tests (spec-required): REDUCED config of each
family, one forward/train step on CPU, asserting output shapes and no
NaNs — plus decode-vs-forward consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model as M


def make_batch(cfg, rng, B=2, S=32):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, S, cfg.d_model)) * 0.02, jnp.float32)
        pos = np.broadcast_to(np.arange(S)[None, :, None], (B, S, 3))
        batch["positions"] = jnp.asarray(pos, jnp.int32)
    if cfg.family == "encdec":
        batch["enc_frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.enc_ctx, cfg.d_model)), jnp.float32)
    return batch


@pytest.fixture(scope="module")
def arch_state():
    cache = {}

    def get(name, **overrides):
        key = (name, tuple(sorted(overrides.items())))
        if key not in cache:
            import dataclasses
            cfg = C.get_config(name, reduced=True)
            if cfg.moe is not None:
                # decode-vs-forward equality needs drop-free routing
                # (grouping differs between prefill and full forward)
                overrides.setdefault("moe", dataclasses.replace(
                    cfg.moe, capacity_factor=8.0))
            cfg = dataclasses.replace(cfg, **overrides)
            params = M.init_params(cfg, jax.random.PRNGKey(0))
            cache[key] = (cfg, params)
        return cache[key]
    return get


@pytest.mark.parametrize("name", C.ARCH_NAMES)
def test_forward_and_train_step(arch_state, rng, name):
    cfg, params = arch_state(name)
    batch = make_batch(cfg, rng)
    logits, _ = M.forward(cfg, params, batch)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all(), name

    loss, metrics = M.loss_fn(cfg, params, batch)
    assert np.isfinite(float(loss))
    grads = jax.grad(lambda p: M.loss_fn(cfg, p, batch)[0])(params)
    flat = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g)).all() for g in flat), name
    # at least one nonzero gradient leaf
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in flat), name


@pytest.mark.parametrize("name", C.ARCH_NAMES)
def test_prefill_decode_matches_forward(arch_state, rng, name):
    """Autoregressive consistency: full-sequence forward logits at
    position t must match prefill(t tokens) + decode steps. Run in f32
    activations so the tolerance is meaningful (bf16 path differences
    between the chunked-prefill and recurrent-decode forms are noise,
    not bugs — the f32 check is the real invariant)."""
    cfg, params = arch_state(name, dtype="float32")
    B, S, GEN = 2, 24, 4
    batch = make_batch(cfg, rng, B, S + GEN)
    if "patch_embeds" in batch:
        # image patches live in the prompt; generated positions are text
        batch["patch_embeds"] = batch["patch_embeds"].at[:, S:].set(0.0)
    full_logits, _ = M.forward(cfg, params, batch)

    prompt = {k: (v[:, :S] if v.ndim >= 2 and v.shape[1] == S + GEN else v)
              for k, v in batch.items() if k != "labels"}
    cache = M.init_cache(cfg, B, S + GEN)
    logits, cache = M.prefill(cfg, params, prompt, cache)
    np.testing.assert_allclose(
        np.asarray(logits[:, -1]), np.asarray(full_logits[:, S - 1]),
        rtol=5e-3, atol=5e-3, err_msg=f"{name} prefill")

    for i in range(GEN):
        tok = batch["tokens"][:, S + i][:, None]
        logits, cache = M.decode_step(cfg, params, tok, jnp.int32(S + i),
                                      cache)
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(full_logits[:, S + i]),
            rtol=5e-3, atol=5e-3, err_msg=f"{name} decode step {i}")


def test_swa_decode_fast_path(rng):
    """Mixtral's sliding-window decode path (cache slice) must equal the
    full-cache masked attention."""
    import dataclasses
    cfg = C.get_config("mixtral-8x22b", reduced=True)
    # long cache so the fast path triggers (cache > 2*window); f32 +
    # drop-free routing so equality is exact (see consistency test)
    cfg = dataclasses.replace(
        cfg, window=8, dtype="float32",
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 48
    batch = make_batch(cfg, rng, B, S)
    full_logits, _ = M.forward(cfg, params, batch)
    cache = M.init_cache(cfg, B, 64)
    logits, cache = M.prefill(
        cfg, params, {"tokens": batch["tokens"][:, :S - 1]}, cache)
    tok = batch["tokens"][:, S - 1][:, None]
    logits, _ = M.decode_step(cfg, params, tok, jnp.int32(S - 1), cache)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full_logits[:, S - 1]),
                               rtol=5e-3, atol=5e-3)


def test_param_counts_full_configs():
    """Full (non-reduced) configs must build shape trees matching their
    published parameter scale (sanity, via eval_shape: no allocation)."""
    from repro.roofline.analysis import count_params
    expected = {
        "qwen3-0.6b": (0.4e9, 1.2e9),
        "qwen1.5-32b": (28e9, 38e9),
        "granite-3-8b": (7e9, 10e9),
        # granite-20b is "20B" as GPT-BigCode (2-matrix GELU MLP); the
        # assignment pins llama-arch (SwiGLU, 3 matrices) at the same
        # d_ff -> 28.2B parameters. Recorded in DESIGN §6.
        "granite-20b": (18e9, 30e9),
        "mixtral-8x22b": (120e9, 150e9),
        "arctic-480b": (400e9, 520e9),
        "mamba2-2.7b": (2.2e9, 3.2e9),
        "zamba2-1.2b": (0.9e9, 1.7e9),
        "granite-4.0-h-micro": (3.0e9, 3.4e9),
        "whisper-tiny": (25e6, 80e6),
        "qwen2-vl-2b": (1.2e9, 2.4e9),
    }
    for name, (lo, hi) in expected.items():
        total, active = count_params(C.get_config(name))
        assert lo <= total <= hi, (name, total)
        assert active <= total
