"""Flash-attention suite: forward + lse residuals, the fused recompute
backward, the q_len=1 decode kernel, and the attention() router — every
Pallas path in interpret mode against the dense oracle and the chunked
XLA composition it replaced."""

import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.policy import Policy
from repro.kernels import ops
from repro.kernels.ops import flash_attention
from repro.kernels.ref import (attention_bwd_ref, attention_fwd_ref,
                               attention_ref, _LSE_EMPTY)
from repro.models.attention import attention, chunked_attention

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

_PI = Policy(backend="pallas", interpret=True)
_XLA = Policy(backend="xla")


def _qkv(rng, b, tq, tk, h, hkv, d, dtype="float32"):
    q = jnp.asarray(rng.normal(size=(b, tq, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, tk, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, tk, hkv, d)), dtype)
    return q, k, v


# ----------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------

@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 64)])
def test_flash_kernel_vs_ref(rng, h, hkv, causal, window):
    q, k, v = _qkv(rng, 2, 128, 128, h, hkv, 32)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          backend="pallas_interpret", bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("chunk", [32, 64, 256])
@pytest.mark.parametrize("window", [None, 48])
def test_chunked_attention_vs_ref(rng, chunk, window):
    q, k, v = _qkv(rng, 2, 256, 256, 4, 2, 32)
    out = chunked_attention(q, k, v, causal=True, window=window, chunk=chunk)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_chunked_attention_decode_offset(rng):
    """Decode semantics: 1 query at absolute position `pos` against a
    cache of kv_len valid entries."""
    tq, tk, pos = 1, 128, 57
    q, k, v = _qkv(rng, 2, tq, tk, 4, 4, 32)
    out = chunked_attention(q, k, v, causal=True, chunk=32,
                            q_offset=jnp.int32(pos), kv_len=jnp.int32(pos + 1))
    # oracle: dense attention over the first pos+1 keys only
    ref = attention_ref(q, k[:, :pos + 1], v[:, :pos + 1], causal=True,
                        q_offset=pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_bf16(rng):
    q, k, v = _qkv(rng, 1, 128, 128, 4, 2, 64, "bfloat16")
    out = flash_attention(q, k, v, causal=True, backend="pallas_interpret",
                          bq=64, bk=64)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_per_row_q_offset(rng):
    """Decode-style per-row offset vector: each batch row attends its
    own prefix depth through the SMEM operand, matching per-row dense."""
    b, tq, tk = 3, 8, 64
    q, k, v = _qkv(rng, b, tq, tk, 4, 2, 32)
    offs = jnp.asarray([0, 13, 56 - tq], jnp.int32)
    out = flash_attention(q, k, v, causal=True, q_offset=offs,
                          backend="pallas_interpret", bq=8, bk=32)
    ref = attention_ref(q, k, v, causal=True, q_offset=offs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_fwd_lse_matches_ref(rng):
    """The saved logsumexp residual (scaled-logit units) matches the
    dense oracle's — including the +1e30 sentinel on rows the causal
    mask empties (q_offset < 0 rows see no valid keys)."""
    q, k, v = _qkv(rng, 2, 64, 64, 4, 2, 32)
    o, lse = ops.flash_attention_fwd(q, k, v, causal=True, policy=_PI)
    o_ref, lse_ref = attention_fwd_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=1e-5, atol=1e-5)
    # fully-masked rows: q_offset = -tq puts every query before key 0
    offs = jnp.asarray([-64, 0], jnp.int32)
    _, lse2 = ops.flash_attention_fwd(q, k, v, causal=True, q_offset=offs,
                                      policy=_PI)
    assert bool(jnp.all(lse2[0] == _LSE_EMPTY))
    assert bool(jnp.all(jnp.isfinite(lse2[1])))


# ----------------------------------------------------------------------
# fused backward
# ----------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 4e-2)])
@pytest.mark.parametrize("h,hkv,causal,window",
                         [(4, 4, True, None), (4, 2, True, None),
                          (8, 1, False, None), (4, 2, True, 48)])
def test_fused_vjp_matches_chunked_grads(rng, dtype, tol, h, hkv, causal,
                                         window):
    """The tentpole contract: gradients through attention()'s fused
    custom-VJP (flash fwd saving lse + the two-sweep recompute bwd)
    match differentiating through the chunked composition it replaced —
    across dtype, GQA grouping, and masks."""
    q, k, v = _qkv(rng, 2, 128, 128, h, hkv, 32, dtype)

    def fused_loss(q_, k_, v_):
        out = attention(q_, k_, v_, causal=causal, window=window,
                        chunk=64, policy=_PI)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def chunked_loss(q_, k_, v_):
        out = chunked_attention(q_, k_, v_, causal=causal, window=window,
                                chunk=64)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    grads = jax.grad(fused_loss, argnums=(0, 1, 2))(q, k, v)
    refs = jax.grad(chunked_loss, argnums=(0, 1, 2))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), grads, refs):
        assert g.dtype == r.dtype, name
        gf, rf = g.astype(jnp.float32), r.astype(jnp.float32)
        bound = tol * max(float(jnp.max(jnp.abs(rf))), 1.0)
        err = float(jnp.max(jnp.abs(gf - rf)))
        assert err <= bound, (name, err, bound)


def test_fused_vjp_check_grads(rng):
    """Numerical-derivative check on the custom VJP itself (small shape:
    check_grads runs O(inputs) forward evaluations)."""
    from jax.test_util import check_grads
    q, k, v = _qkv(rng, 1, 16, 16, 2, 1, 8)
    check_grads(
        lambda q_, k_, v_: attention(q_, k_, v_, causal=True, window=None,
                                     chunk=16, policy=_PI),
        (q, k, v), order=1, modes=["rev"], rtol=2e-3, atol=2e-3)


def test_flash_bwd_op_matches_closed_form(rng):
    """Registry-level parity: both flash_attention_bwd backends agree
    with the closed-form dense backward from the same residuals."""
    q, k, v = _qkv(rng, 2, 64, 64, 4, 2, 32)
    do = jnp.asarray(np.random.default_rng(7).normal(size=q.shape),
                     jnp.float32)
    o, lse = attention_fwd_ref(q, k, v, causal=True)
    refs = attention_bwd_ref(q, k, v, o, do, lse, causal=True)
    for pol in (_PI, _XLA):
        grads = ops.flash_attention_bwd(q, k, v, o, do, lse, causal=True,
                                        policy=pol)
        for name, g, r in zip(("dq", "dk", "dv"), grads, refs):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=2e-4, atol=2e-4,
                err_msg=f"{pol.backend}:{name}")


def test_ragged_shapes_fall_back_chunked_and_differentiate(rng):
    """tq=300 is not block-divisible: the pallas policy must route the
    chunked path (same values as xla) and stay differentiable."""
    q, k, v = _qkv(rng, 1, 300, 300, 4, 2, 32)

    def loss(pol):
        return lambda q_: jnp.sum(attention(
            q_, k, v, causal=True, window=None, chunk=60, policy=pol) ** 2)

    np.testing.assert_allclose(
        np.asarray(jax.grad(loss(_PI))(q)),
        np.asarray(jax.grad(loss(_XLA))(q)), rtol=1e-5, atol=1e-5)


# ----------------------------------------------------------------------
# decode kernel
# ----------------------------------------------------------------------

# Slot depths around a block edge at bk=32 in a 128-deep cache: the
# block's last key, the next block's first, a full cache, the first key
# and an inactive slot.
_EDGES = [31, 32, 127, 0, -1]


@pytest.mark.parametrize("h,hkv,d,window,pos,bk,dtype", [
    pytest.param(4, 2, 32, None, [127, 37, 0], 512, "float32", id="None"),
    pytest.param(4, 2, 32, 24, [127, 37, 0], 512, "float32", id="24"),
    pytest.param(4, 4, 64, None, _EDGES, 32, "float32", id="group1-d64"),
    pytest.param(4, 2, 128, None, _EDGES, 32, "float32", id="group2-d128"),
    pytest.param(4, 1, 64, None, _EDGES, 32, "float32", id="groupH-d64"),
    pytest.param(4, 1, 128, None, _EDGES, 32, "float32", id="groupH-d128"),
    pytest.param(4, 2, 128, 40, _EDGES, 32, "float32",
                 id="group2-d128-window"),
    pytest.param(4, 4, 64, 40, _EDGES, 32, "float32", id="group1-d64-window"),
    pytest.param(4, 2, 128, None, 77, 32, "float32", id="scalar-pos"),
    pytest.param(4, 4, 128, None, _EDGES, 32, "bfloat16",
                 id="group1-d128-bf16"),
    pytest.param(4, 2, 128, 40, _EDGES, 32, "bfloat16",
                 id="group2-d128-window-bf16"),
    pytest.param(6, 3, 128, 40, _EDGES, 32, "bfloat16",
                 id="odd-kv-heads-d128-window-bf16"),
    pytest.param(4, 1, 128, None, 77, 32, "bfloat16",
                 id="groupH-d128-scalar-pos-bf16"),
])
def test_flash_decode_vs_ref(rng, h, hkv, d, window, pos, bk, dtype):
    """Per-slot depths (ragged, around block edges, inactive) and scalar
    depths against the dense oracle: one kv head per query head, a
    shared kv head, a single kv head, an odd kv-head count, head dims
    under and at 128 lanes, 32- and 16-bit caches, windows."""
    tk = 128
    b = len(pos) if isinstance(pos, list) else 2
    q, k, v = _qkv(rng, b, 1, tk, h, hkv, d, dtype)
    pos = jnp.asarray(pos, jnp.int32)
    out = ops.flash_decode(q, k, v, pos=pos, window=window, policy=_PI,
                           bk=bk)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, _ = attention_fwd_ref(*f32, causal=True, window=window,
                               q_offset=pos)
    tol = 2e-4 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [None, 40])
def test_flash_decode_index_map_stays_in_prefix(window):
    """The K/V index map, fed the prefetched depths, never names a block
    past a slot's depth or before its window, names every block the
    slot attends to, and names block 0 alone for an inactive slot — so
    the pipeline fetches each valid block once and nothing else."""
    from repro.kernels.flash_attention import decode_kv_block
    bk, n_kv = 32, 8
    for pos in [-1, 0, 31, 32, 100, 255]:
        named = [int(decode_kv_block(jnp.int32(j), jnp.int32(pos), bk,
                                     window)) for j in range(n_kv)]
        if pos < 0:
            assert set(named) == {0}
            continue
        lo = 0 if window is None else max(pos - window + 1, 0) // bk
        assert set(named) == set(range(lo, pos // bk + 1)), (pos, named)
        assert named == sorted(named)      # repeats only: no re-fetch


def test_flash_decode_inactive_slot_is_finite_zero(rng):
    """pos < 0 marks an inactive slot: every K/V block is skipped, the
    flush's l==0 guard yields zeros (finite — NaNs would poison the
    batched engine step), and both backends agree on it."""
    q, k, v = _qkv(rng, 2, 1, 64, 4, 2, 32)
    pos = jnp.asarray([-1, 63], jnp.int32)
    out_p = ops.flash_decode(q, k, v, pos=pos, policy=_PI)
    out_x = ops.flash_decode(q, k, v, pos=pos, policy=_XLA)
    assert bool(jnp.all(jnp.isfinite(out_p)))
    assert bool(jnp.all(out_p[0] == 0.0))
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_x),
                               rtol=2e-4, atol=2e-4)


def test_flash_decode_bf16(rng):
    q, k, v = _qkv(rng, 2, 1, 128, 4, 2, 64, "bfloat16")
    pos = jnp.asarray([127, 40], jnp.int32)
    out = ops.flash_decode(q, k, v, pos=pos, policy=_PI)
    ref, _ = attention_fwd_ref(q, k, v, causal=True, q_offset=pos)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_attention_router_decode_matches_chunked(rng):
    """attention(decode=True) under a pallas policy takes the decode
    kernel and agrees with the chunked masked path on active slots."""
    b, tk = 2, 128
    q, k, v = _qkv(rng, b, 1, tk, 4, 2, 32)
    pos = jnp.asarray([100, 17], jnp.int32)
    out = attention(q, k, v, causal=True, window=None, chunk=64,
                    q_offset=pos, kv_len=pos + 1, policy=_PI, decode=True)
    ref = chunked_attention(q, k, v, causal=True, window=None, chunk=64,
                            q_offset=pos, kv_len=pos + 1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------
# f64 reroute
# ----------------------------------------------------------------------

def test_float64_reroutes_to_xla():
    """f64 attention under a pallas policy must land on the XLA path
    (the kernel accumulates f32 by construction): output stays f64 and
    is BITWISE identical to the explicit xla-policy result — same code
    path, not a lookalike — and gradients flow. Subprocess — x64 is a
    process-global switch."""
    code = textwrap.dedent("""
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.core.policy import Policy
        from repro.models.attention import attention
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 64, 2, 16)), jnp.float64)
        kv = jnp.asarray(rng.normal(size=(1, 64, 1, 16)), jnp.float64)
        pol = Policy(backend="pallas", interpret=True)
        out = attention(q, kv, kv, causal=True, window=None, chunk=32,
                        policy=pol)
        ref = attention(q, kv, kv, causal=True, window=None, chunk=32,
                        policy=Policy(backend="xla"))
        assert out.dtype == jnp.float64, out.dtype
        assert bool(jnp.all(out == ref)), "pallas policy did not reroute"
        g = jax.grad(lambda x: jnp.sum(attention(
            x, kv, kv, causal=True, window=None, chunk=32,
            policy=pol) ** 2))(q)
        assert g.dtype == jnp.float64 and bool(jnp.all(jnp.isfinite(g)))
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO_ROOT, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]
