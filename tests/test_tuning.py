"""Autotuner subsystem: cache round-trip, fingerprint safety, tuned
backend numerics, and sweep mechanics (all interpret-mode on CPU)."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.blocking import BlockConfig, FlashBlockConfig
from repro.kernels import ops
from repro.kernels.ref import attention_ref, matmul_ref
from repro.tuning import cache as tcache
from repro.tuning import autotuner, space


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    """Point the process-global cache at a throwaway file."""
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv(tcache.CACHE_ENV_VAR, path)
    tcache.reset_cache()
    yield path
    tcache.reset_cache()


def test_cache_roundtrip(tmp_path):
    path = str(tmp_path / "c.json")
    c = tcache.TuningCache(path, fingerprint="fp-a")
    c.put_matmul(512, 512, 512, "float32", "pallas",
                 BlockConfig(256, 128, 512), time_us=10.0, speedup=1.5)
    c.put_flash(1024, 1024, 64, "bfloat16", "pallas",
                FlashBlockConfig(128, 256), time_us=20.0)
    c.save()

    c2 = tcache.TuningCache(path, fingerprint="fp-a").load()
    assert c2.get_matmul(512, 512, 512, "float32", "pallas") == \
        BlockConfig(256, 128, 512)
    assert c2.get_flash(1024, 1024, 64, "bfloat16", "pallas") == \
        FlashBlockConfig(128, 256)
    entry = c2.entries[tcache.matmul_key(512, 512, 512, "float32", "pallas")]
    assert entry["speedup"] == 1.5 and "tuned_at" in entry


def test_save_merges_other_fingerprints(tmp_path):
    path = str(tmp_path / "c.json")
    tcache.TuningCache(path, fingerprint="fp-a").load().save()
    a = tcache.TuningCache(path, fingerprint="fp-a")
    a.put_matmul(64, 64, 64, "float32", "pallas", BlockConfig(64, 64, 64))
    a.save()
    b = tcache.TuningCache(path, fingerprint="fp-b")
    b.put_matmul(64, 64, 64, "float32", "pallas", BlockConfig(128, 128, 128))
    b.save()
    doc = json.load(open(path))
    assert set(doc["caches"]) == {"fp-a", "fp-b"}
    assert tcache.TuningCache(path, "fp-a").load().get_matmul(
        64, 64, 64, "float32", "pallas") == BlockConfig(64, 64, 64)


def test_fingerprint_mismatch_returns_none(tmp_path):
    path = str(tmp_path / "c.json")
    a = tcache.TuningCache(path, fingerprint="fp-a")
    a.put_matmul(64, 64, 64, "float32", "pallas_interpret",
                 BlockConfig(64, 64, 64))
    a.save()
    b = tcache.TuningCache(path, fingerprint="fp-b").load()
    assert b.get_matmul(64, 64, 64, "float32", "pallas_interpret") is None
    assert b.misses == 1 and b.hits == 0


def test_fingerprint_mismatch_falls_back_to_default(tmp_cache, rng):
    # A cache written on "other" hardware must be ignored: the tuned
    # backend silently uses the static chooser and stays correct.
    other = tcache.TuningCache(tmp_cache, fingerprint="some-other-machine")
    other.put_matmul(96, 96, 96, "float32", "pallas_interpret",
                     BlockConfig(8, 128, 128))
    other.save()
    tcache.reset_cache()
    a = jnp.asarray(rng.normal(size=(96, 96)), jnp.float32)
    out = ops.matmul(a, a, backend="tuned_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, a)),
                               rtol=1e-4, atol=1e-3)
    assert tcache.get_cache().get_matmul(
        96, 96, 96, "float32", "pallas_interpret") is None


def test_tuned_matches_tiled_numerics(tmp_cache, rng):
    a = jnp.asarray(rng.normal(size=(96, 160)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(160, 112)), jnp.float32)
    tuned = ops.matmul(a, b, backend="tuned_interpret")
    tiled = ops.matmul(a, b, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(tuned), np.asarray(tiled),
                               rtol=1e-5, atol=1e-5)


def test_tuned_serves_cached_config(tmp_cache, rng):
    # A non-default (but valid) config planted in the cache must be
    # served — observable via the hit counter — and stay correct.
    c = tcache.get_cache()
    c.put_matmul(128, 128, 128, "float32", "pallas_interpret",
                 BlockConfig(64, 128, 128))
    c.save()
    a = jnp.asarray(rng.normal(size=(128, 128)), jnp.float32)
    hits0 = c.hits
    out = ops.matmul(a, a, backend="tuned_interpret")
    assert c.hits == hits0 + 1
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, a)),
                               rtol=1e-4, atol=1e-3)


def test_tuned_flash_matches_ref(tmp_cache, rng):
    q = jnp.asarray(rng.normal(size=(1, 128, 2, 32)), jnp.float32)
    out = ops.flash_attention(q, q, q, causal=True, backend="tuned_interpret")
    ref = attention_ref(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-3)


def test_matmul_candidates_feasible():
    cands = space.matmul_candidates(4096, 4096, 4096, itemsize=2)
    assert len(cands) > 1
    from repro.core import hw
    budget = hw.DEFAULT_CHIP.vmem_bytes * 0.5
    assert all(c.vmem_bytes(2) <= budget for c in cands)
    assert len({(c.bm, c.bn, c.bk) for c in cands}) == len(cands)
    # the static chooser's pick leads the sweep (it is the baseline)
    from repro.core import blocking
    assert cands[0] == blocking.choose_block_config(4096, 4096, 4096, 2)


def test_flash_candidates_divide_sequences():
    cands = space.flash_candidates(1024, 2048, 128, itemsize=2)
    assert all(1024 % c.bq == 0 and 2048 % c.bk == 0 for c in cands)


def test_tune_matmul_populates_cache(tmp_cache):
    res = autotuner.tune_matmul(128, 128, 128, "float32",
                                backend="pallas_interpret",
                                warmup=0, iters=1, max_candidates=3)
    assert res.best_s > 0 and len(res.trials) >= 1
    served = tcache.TuningCache(tmp_cache).load().get_matmul(
        128, 128, 128, "float32", "pallas_interpret")
    assert served == res.best


def test_warm_start_reports_then_hits(tmp_cache):
    from repro.configs import get_config
    cfg = get_config("qwen3-0.6b", reduced=True)
    rep = autotuner.warm_start(cfg, batch=2, seq=16, autotune=False)
    assert rep["tuned"] == [] and rep["hits"] == []
    expected = (len(autotuner.model_gemm_shapes(cfg, 2, 16))
                + len(autotuner.model_attention_shapes(cfg, 2, 16)))
    assert len(rep["misses"]) == expected
    rep2 = autotuner.warm_start(cfg, batch=2, seq=16, autotune=True,
                                iters=1, max_candidates=2)
    assert len(rep2["tuned"]) == len(rep["misses"])
    rep3 = autotuner.warm_start(cfg, batch=2, seq=16, autotune=False)
    assert len(rep3["hits"]) == len(rep["misses"]) and rep3["misses"] == []


def test_warm_start_covers_attention_shapes(tmp_cache):
    from repro.configs import get_config
    cfg = get_config("qwen3-0.6b", reduced=True)
    rep = autotuner.warm_start(cfg, batch=2, seq=16, autotune=False,
                               backward=True, decode_len=64)
    ops_seen = {e[0] for e in rep["misses"]}
    assert {"flash", "flash_bwd", "flash_decode"} <= ops_seen


def test_model_attention_shapes_skips_ssm():
    from repro.configs import get_config
    cfg = get_config("mamba2-2.7b", reduced=True)
    assert autotuner.model_attention_shapes(cfg, 2, 16) == []


def test_flash_decode_candidates_divide_cache():
    cands = space.flash_decode_candidates(2048, 8, 64, itemsize=2)
    assert all(c.bq == 1 and 2048 % c.bk == 0 for c in cands)
    assert len({c.bk for c in cands}) == len(cands)
    from repro.core import blocking
    assert cands[0] == blocking.choose_decode_config(2048, 8, 64, 2)
    # every candidate's (bk, hkv, d) K and V blocks fit the VMEM budget
    assert all(blocking.decode_vmem_bytes(c.bk, 8, 64, 2)
               <= blocking.vmem_budget() for c in cands)


def test_flash_bwd_candidates_feasible():
    cands = space.flash_bwd_candidates(1024, 2048, 128, itemsize=2)
    assert cands and all(1024 % c.bq == 0 and 2048 % c.bk == 0
                         for c in cands)


def test_tune_flash_decode_populates_cache(tmp_cache):
    pol_fp = "pallas_interpret"
    res = autotuner.tune_flash_decode(256, 32, "float32", backend=pol_fp,
                                      batch=2, heads=4, kv_heads=2,
                                      warmup=0, iters=1, max_candidates=2)
    assert res.best_s > 0 and res.best.bq == 1
    served = tcache.TuningCache(tmp_cache).load()
    assert served.get_flash_decode(256, 2, 32, "float32", pol_fp) == res.best
    # the winner is keyed by its kv heads: another head count misses
    assert served.get_flash_decode(256, 1, 32, "float32", pol_fp) is None


def test_tune_flash_bwd_populates_cache(tmp_cache):
    pol_fp = "pallas_interpret"
    res = autotuner.tune_flash_bwd(256, 256, 32, "float32", backend=pol_fp,
                                   warmup=0, iters=1, max_candidates=2)
    assert res.best_s > 0
    served = tcache.TuningCache(tmp_cache).load().get_flash_bwd(
        256, 256, 32, "float32", pol_fp)
    assert served == res.best


def test_flash_decode_paged_candidates_divide_page():
    cands = space.flash_decode_paged_candidates(16, 64, itemsize=4)
    assert cands and all(c.bq == 1 and 16 % c.bk == 0 and c.bk <= 16
                         for c in cands)
    assert cands[0].bk == 16            # whole-page default first
    assert len({c.bk for c in cands}) == len(cands)


def test_tune_flash_decode_paged_populates_cache(tmp_cache):
    pol_fp = "pallas_interpret"
    res = autotuner.tune_flash_decode_paged(16, 32, "float32",
                                            backend=pol_fp, batch=2,
                                            pages_per_slot=2, warmup=0,
                                            iters=1, max_candidates=2)
    assert res.best_s > 0 and res.best.bq == 1
    served = tcache.TuningCache(tmp_cache).load().get_flash_decode_paged(
        16, 32, "float32", pol_fp)
    assert served == res.best
