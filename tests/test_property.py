"""Hypothesis property tests on system invariants."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -e .[test])")
from hypothesis import given, settings, strategies as st

import repro.configs as C
from repro.core import blocking, intensity, precision
from repro.core.hw import TPU_V5E
from repro.core.policy import Policy
from repro.distributed import compression
from repro.kernels import ops, registry
from repro.kernels import matmul as mm_kernels
from repro.kernels import ref as kref
from repro.kernels.ref import matmul_ref
from repro.models import moe as MOE
from repro.models.layers import apply_rope, default_positions
from repro.models.ssm import _segsum

_settings = settings(max_examples=25, deadline=None)


@given(m=st.integers(8, 512), n=st.integers(8, 512), k=st.integers(8, 2048),
       itemsize=st.sampled_from([2, 4]))
@_settings
def test_block_config_always_fits_vmem(m, n, k, itemsize):
    """The paper's shared-memory-budget invariant, for every shape: the
    chosen tile set must fit the VMEM budget and stay MXU-aligned."""
    cfg = blocking.choose_block_config(m, n, k, itemsize)
    assert cfg.vmem_bytes(itemsize) <= TPU_V5E.vmem_bytes * 0.5 + 1
    assert cfg.bn % TPU_V5E.lane == 0 or cfg.bn >= n
    assert cfg.bm % TPU_V5E.sublane(itemsize) == 0 or cfg.bm >= m


@given(m=st.integers(1, 300), n=st.integers(1, 300), k=st.integers(1, 300))
@_settings
def test_tiled_traffic_never_exceeds_naive(m, n, k):
    """Blocking can only reduce HBM traffic (claim C1/C2)."""
    cfg = blocking.choose_block_config(m, n, k, 4)
    tiled = blocking.hbm_traffic_bytes(m, n, k, cfg, 4)
    naive = blocking.naive_traffic_bytes(m, n, k, 4)
    assert tiled <= naive


@given(st.integers(16, 512))
@_settings
def test_add_is_memory_bound_matmul_depends(n):
    """Claim C3: add is always memory-bound; square matmul crosses to
    compute-bound once n exceeds the machine balance point."""
    add = intensity.classify(intensity.add_profile(n, n, 4), itemsize=4)
    assert add["bound"] == "memory"
    mm = intensity.classify(intensity.matmul_profile(n, n, n, 2), itemsize=2)
    balance = intensity.machine_balance(itemsize=2)
    ai = mm["arithmetic_intensity"]
    assert (mm["bound"] == "compute") == (ai >= balance)


@given(m=st.integers(1, 96), k=st.integers(1, 96), n=st.integers(1, 96),
       seed=st.integers(0, 2**31))
@settings(max_examples=10, deadline=None)
def test_matmul_padding_path(m, k, n, seed):
    """ops.matmul pads ragged shapes; result must equal the oracle."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = ops.matmul(a, b, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-4, atol=1e-3)


# ----------------------------------------------------------------------
# Cross-backend differential harness: EVERY backend registered for an op
# in kernels.registry is run against the pure-jnp oracle on
# hypothesis-generated (shape, dtype, epilogue) tuples. A new backend
# (a single @register_op call) is conformance-tested here for free —
# including matmul_q, whose weights are drawn through the real
# quantizer so the oracle and the kernels see the same int8 grid.
# ----------------------------------------------------------------------

#: max|err| allowed as a fraction of max|ref| — scaled by the dtype's
#: accumulation/rounding granularity (bf16 epsilon is 2^-8).
_DIFF_TOL = {"float32": 1e-4, "bfloat16": 6e-2}


def _diff_operands(rng, m, n, k, dtype, epilogue):
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    b = jnp.asarray(rng.normal(size=(k, n)), dtype)
    bias = residual = None
    if epilogue == "residual":
        residual = jnp.asarray(rng.normal(size=(m, n)), dtype)
    elif epilogue != "none":
        bias = jnp.asarray(rng.normal(size=(n,)), dtype)
    return a, b, bias, residual


def _assert_backend_close(backend, out, ref_f32, dtype):
    tol = _DIFF_TOL[dtype]
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref_f32)))
    bound = tol * max(float(jnp.max(jnp.abs(ref_f32))), 1.0)
    assert err <= bound, (backend, err, bound)


@given(m=st.integers(1, 40), n=st.integers(1, 40), k=st.integers(1, 40),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       epilogue=st.sampled_from(mm_kernels.EPILOGUES),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_matmul_backends_match_reference(m, n, k, dtype, epilogue, seed):
    rng = np.random.default_rng(seed)
    a, b, bias, residual = _diff_operands(rng, m, n, k, dtype, epilogue)
    ref = kref.epilogue_ref(kref.matmul_ref(a, b, out_dtype=jnp.float32),
                            epilogue, bias, residual)
    for backend in registry.registered_backends("matmul"):
        out = ops.matmul(a, b, policy=Policy(backend=backend, interpret=True),
                         epilogue=epilogue, bias=bias, residual=residual)
        assert out.dtype == jnp.dtype(dtype), backend
        _assert_backend_close(backend, out, ref, dtype)


@given(m=st.integers(1, 40), n=st.integers(1, 40), k=st.integers(1, 40),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       epilogue=st.sampled_from(mm_kernels.EPILOGUES),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_matmul_q_backends_match_reference(m, n, k, dtype, epilogue, seed):
    rng = np.random.default_rng(seed)
    a, b, bias, residual = _diff_operands(rng, m, n, k, dtype, epilogue)
    wq, scale = precision.quantize_int8(b)
    ref = kref.epilogue_ref(
        kref.matmul_q_ref(a, wq, scale, out_dtype=jnp.float32),
        epilogue, bias, residual)
    for backend in registry.registered_backends("matmul_q"):
        out = ops.matmul_q(a, wq, scale,
                           policy=Policy(backend=backend, interpret=True),
                           epilogue=epilogue, bias=bias, residual=residual)
        assert out.dtype == jnp.dtype(dtype), backend
        _assert_backend_close(backend, out, ref, dtype)


@given(m=st.integers(1, 40), n=st.integers(1, 40), k=st.integers(1, 40),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_gated_matmul_backends_match_reference(m, n, k, dtype, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), dtype)
    wg = jnp.asarray(rng.normal(size=(k, n)), dtype)
    wu = jnp.asarray(rng.normal(size=(k, n)), dtype)
    ref = kref.gated_matmul_ref(a, wg, wu,
                                out_dtype=jnp.float32).astype(jnp.float32)
    for backend in registry.registered_backends("gated_matmul"):
        out = ops.gated_matmul(
            a, wg, wu, policy=Policy(backend=backend, interpret=True))
        assert out.dtype == jnp.dtype(dtype), backend
        _assert_backend_close(backend, out, ref, dtype)


# Attention ops: shapes are drawn from the kernels' divisibility lattice
# (tq % bq == 0, tk % bk == 0 after clamping) so every registered
# backend — pallas included — runs its real tiled path, not a fallback.
_ATTN_SEQ = st.sampled_from([16, 32, 64])
_ATTN_D = st.sampled_from([16, 32])
_ATTN_GROUP = st.sampled_from([1, 2, 4])
_ATTN_WINDOW = st.sampled_from([None, 8, 24])


def _attn_operands(rng, tq, tk, d, group, dtype):
    h = 4
    hkv = h // group
    q = jnp.asarray(rng.normal(size=(2, tq, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(2, tk, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(2, tk, hkv, d)), dtype)
    return q, k, v


@given(tq=_ATTN_SEQ, tk=_ATTN_SEQ, d=_ATTN_D, group=_ATTN_GROUP,
       causal=st.booleans(), window=_ATTN_WINDOW,
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_flash_attention_backends_match_reference(tq, tk, d, group, causal,
                                                  window, dtype, seed):
    rng = np.random.default_rng(seed)
    q, k, v = _attn_operands(rng, tq, tk, d, group, dtype)
    ref = kref.attention_ref(q, k, v, causal=causal,
                             window=window).astype(jnp.float32)
    for backend in registry.registered_backends("flash_attention"):
        out = ops.flash_attention(
            q, k, v, causal=causal, window=window,
            policy=Policy(backend=backend, interpret=True))
        assert out.dtype == jnp.dtype(dtype), backend
        _assert_backend_close(backend, out, ref, dtype)


@given(tk=st.sampled_from([32, 64, 128]), d=_ATTN_D, group=_ATTN_GROUP,
       window=_ATTN_WINDOW, dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_flash_decode_backends_match_reference(tk, d, group, window, dtype,
                                               seed):
    rng = np.random.default_rng(seed)
    q, k, v = _attn_operands(rng, 1, tk, d, group, dtype)
    # ragged per-slot depths, one mid-stream
    pos = jnp.asarray([tk - 1, int(rng.integers(0, tk))], jnp.int32)
    ref, _ = kref.attention_fwd_ref(q, k, v, causal=True, window=window,
                                    q_offset=pos)
    ref = ref.astype(jnp.float32)
    for backend in registry.registered_backends("flash_decode"):
        out = ops.flash_decode(
            q, k, v, pos=pos, window=window,
            policy=Policy(backend=backend, interpret=True))
        assert out.dtype == jnp.dtype(dtype), backend
        _assert_backend_close(backend, out, ref, dtype)


@given(ps=st.sampled_from([8, 16]), pp=st.sampled_from([2, 4]),
       d=_ATTN_D, group=_ATTN_GROUP, window=_ATTN_WINDOW,
       quant=st.booleans(), seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_flash_decode_paged_backends_match_reference(ps, pp, d, group,
                                                     window, quant, seed):
    """Paged decode conformance: every registered backend must match the
    gather+softmax oracle on a scattered page table with shared pages,
    an unmapped (-1) tail, ragged per-slot depths and — when quant is
    set — int8 pools with per-(position, head) f32 scale planes."""
    rng = np.random.default_rng(seed)
    B, hkv = 2, 2
    h = hkv * group
    n_pages = B * pp + 1                     # one page never mapped
    q = jnp.asarray(rng.normal(size=(B, 1, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, hkv, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, hkv, ps, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(n_pages)[:B * pp].reshape(B, pp),
                        jnp.int32)
    table = table.at[1, -1].set(-1)          # slot 1: last page unmapped
    pos = jnp.asarray([ps * pp - 1,
                       int(rng.integers(0, ps * (pp - 1)))], jnp.int32)
    ks = vs = None
    if quant:
        kp, ks = precision.quantize_kv(kp)   # ks: (P, hkv, ps)
        vp, vs = precision.quantize_kv(vp)
    ref = kref.flash_decode_paged_ref(q, kp, vp, table, pos=pos,
                                      window=window, ks=ks, vs=vs)
    ref = ref.astype(jnp.float32)
    for backend in registry.registered_backends("flash_decode_paged"):
        out = ops.flash_decode_paged(
            q, kp, vp, table, pos=pos, window=window, ks=ks, vs=vs,
            policy=Policy(backend=backend, interpret=True))
        assert out.dtype == q.dtype, backend
        _assert_backend_close(backend, out, ref, "float32")


@given(tq=_ATTN_SEQ, tk=_ATTN_SEQ, d=_ATTN_D, group=_ATTN_GROUP,
       causal=st.booleans(), window=_ATTN_WINDOW,
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_flash_bwd_backends_match_reference(tq, tk, d, group, causal,
                                            window, dtype, seed):
    rng = np.random.default_rng(seed)
    q, k, v = _attn_operands(rng, tq, tk, d, group, dtype)
    do = jnp.asarray(rng.normal(size=q.shape), dtype)
    o, lse = kref.attention_fwd_ref(q, k, v, causal=causal, window=window)
    # independent oracle: differentiate through the dense reference
    _, vjp = jax.vjp(lambda q_, k_, v_: kref.attention_ref(
        q_, k_, v_, causal=causal, window=window), q, k, v)
    refs = [g.astype(jnp.float32) for g in vjp(do)]
    for backend in registry.registered_backends("flash_attention_bwd"):
        grads = ops.flash_attention_bwd(
            q, k, v, o, do, lse, causal=causal, window=window,
            policy=Policy(backend=backend, interpret=True))
        for name, g, r in zip(("dq", "dk", "dv"), grads, refs):
            _assert_backend_close(f"{backend}:{name}", g.astype(jnp.float32),
                                  r, dtype)


@given(chunk=st.sampled_from([8, 16]), nc=st.sampled_from([1, 2, 4]),
       h=st.sampled_from([2, 4]), group=st.sampled_from([1, 2]),
       n=st.sampled_from([8, 16]), p=st.sampled_from([8, 16]),
       carried=st.booleans(),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 2**31))
@settings(max_examples=5, deadline=None)
def test_ssd_backends_match_reference(chunk, nc, h, group, n, p, carried,
                                      dtype, seed):
    """Every registered SSD backend vs the sequential per-token scan
    oracle — the chunked algebra (intra-chunk masks + inter-chunk
    recurrence) must be invisible, carried init_state and bf16 inputs
    included. States are compared at f32 tolerance regardless of input
    dtype: the f32-carry contract this PR pinned."""
    rng = np.random.default_rng(seed)
    l, g = chunk * nc, h // group
    x = jnp.asarray(rng.normal(size=(2, l, h, p)), dtype)
    a = -jnp.asarray(rng.uniform(0.01, 0.5, size=(2, l, h)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(2, l, g, n)), dtype)
    c = jnp.asarray(rng.normal(size=(2, l, g, n)), dtype)
    s0 = (jnp.asarray(rng.normal(size=(2, h, p, n)), jnp.float32)
          if carried else None)
    ref_y, ref_s = kref.ssd_ref(x, a, b, c, chunk, init_state=s0)
    ref_y = ref_y.astype(jnp.float32)
    for backend in registry.registered_backends("ssd"):
        y, s = ops.ssd(x, a, b, c, chunk, init_state=s0,
                       policy=Policy(backend=backend, interpret=True))
        assert y.dtype == jnp.dtype(dtype), backend
        assert s.dtype == jnp.float32, backend
        _assert_backend_close(backend, y, ref_y, dtype)
        _assert_backend_close(f"{backend}:state", s, ref_s, "float32")


@given(seed=st.integers(0, 2**31), scale=st.floats(0.01, 10.0))
@settings(max_examples=15, deadline=None)
def test_compression_error_feedback_bounded(seed, scale):
    """EF invariant: per-tensor residual is bounded by the quantisation
    step (|err| <= scale_q = max|g+e| / 127)."""
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.normal(size=(64, 64)) * scale, jnp.float32)}
    ef = compression.init_ef(g)
    for _ in range(3):
        q, ef = compression.compress_grads(g, ef)
        step = float(jnp.max(jnp.abs(g["w"] + 0))) / 127.0
        assert float(jnp.max(jnp.abs(ef.error["w"]))) <= 2 * step + 1e-6


@given(seed=st.integers(0, 2**31))
@settings(max_examples=10, deadline=None)
def test_mrope_degenerates_to_rope_on_text(seed):
    """Qwen2-VL M-RoPE with t=h=w equals standard RoPE (spec property)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(2, 16, 4, 32)), jnp.float32)
    pos = default_positions(2, 16)
    plain = apply_rope(x, pos, 10_000.0)
    pos3 = jnp.stack([pos, pos, pos], axis=-1)
    mrope = apply_rope(x, pos3, 10_000.0, mrope_sections=(4, 6, 6))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(mrope),
                               rtol=1e-5, atol=1e-5)


@given(seed=st.integers(0, 2**31), q=st.sampled_from([4, 8, 16]))
@settings(max_examples=10, deadline=None)
def test_segsum_telescopes(seed, q):
    """SSD decay identity: S[i,j] = cs[i] - cs[j] for i >= j."""
    rng = np.random.default_rng(seed)
    a = jnp.asarray(-rng.uniform(0.01, 1.0, size=(q,)), jnp.float32)
    s = np.asarray(_segsum(a))
    cs = np.cumsum(np.asarray(a))
    for i in range(q):
        for j in range(q):
            if j <= i:
                np.testing.assert_allclose(s[i, j], cs[i] - cs[j],
                                           rtol=1e-5, atol=1e-5)
            else:
                assert s[i, j] == -np.inf


@given(seed=st.integers(0, 2**31))
@settings(max_examples=8, deadline=None)
def test_moe_combine_is_convex(seed):
    """Router invariant: with top-k renormalised gates, an MoE whose
    experts all compute the identity returns (approximately) the input
    scaled by the kept-gate mass — dropped tokens lose exactly their
    dropped gate fraction."""
    rng = np.random.default_rng(seed)
    cfg = C.get_config("mixtral-8x22b", reduced=True)
    p = MOE.moe_init(jax.random.PRNGKey(seed % 1000), cfg)
    x = jnp.asarray(rng.normal(size=(1, 32, cfg.d_model)), jnp.float32)
    _, aux = MOE.moe_apply(p, x, cfg)
    assert 0.0 <= float(aux["moe_dropped_frac"]) <= 1.0
    assert float(aux["moe_lb_loss"]) >= 0.0
