"""Per-kernel allclose sweeps: Pallas (interpret mode) vs ref.py oracle,
over shapes x dtypes — including the paper's float / double / complex
matrix (Table 2)."""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gemm, precision
from repro.kernels import ops
from repro.kernels.matmul import matmul_tiled
from repro.kernels.matmul_naive import matmul_naive
from repro.kernels.ref import matmul_ref

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]

SHAPES = [
    (8, 8, 8),
    (128, 128, 128),
    (256, 384, 512),
    (100, 130, 50),      # ragged: exercises the padding path via ops
    (512, 256, 1024),
]


def _mats(rng, m, n, k, dtype):
    if np.dtype(dtype).kind == "c":
        a = rng.normal(size=(m, k)) + 1j * rng.normal(size=(m, k))
        b = rng.normal(size=(k, n)) + 1j * rng.normal(size=(k, n))
    else:
        a = rng.normal(size=(m, k))
        b = rng.normal(size=(k, n))
    return jnp.asarray(a, dtype), jnp.asarray(b, dtype)


@pytest.mark.parametrize("m,n,k", SHAPES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_tiled_matches_ref(rng, m, n, k, dtype):
    a, b = _mats(rng, m, n, k, dtype)
    out = ops.matmul(a, b, backend="pallas_interpret")
    ref = matmul_ref(a, b)
    tol = 1e-5 if dtype == "float32" else 1e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol * 8)


@pytest.mark.parametrize("m,n,k", SHAPES[:4])
def test_naive_matches_ref(rng, m, n, k):
    a, b = _mats(rng, m, n, k, "float32")
    out = ops.matmul(a, b, backend="naive_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(matmul_ref(a, b)),
                               rtol=1e-5, atol=1e-4)


def test_float64_interpret():
    """The paper's double column: validated in interpret mode w/ x64.
    Runs in a subprocess — x64 is a process-global switch."""
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import sys; sys.path.insert(0, "src")
        import numpy as np, jax
        jax.config.update("jax_enable_x64", True)
        import jax.numpy as jnp
        from repro.kernels.matmul import matmul_tiled
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.normal(size=(128, 96)), jnp.float64)
        b = jnp.asarray(rng.normal(size=(96, 64)), jnp.float64)
        out = matmul_tiled(a, b, bm=64, bn=64, bk=32, interpret=True)
        err = float(jnp.max(jnp.abs(out - np.asarray(a) @ np.asarray(b))))
        assert out.dtype == jnp.float64 and err < 1e-12, (out.dtype, err)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=REPO_ROOT, timeout=300)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]


@pytest.mark.parametrize("algorithm", ["naive4", "gauss3"])
def test_complex_decomposition(rng, algorithm):
    """The paper's complex-float column via real GEMMs (incl. the
    3-multiply beyond-paper variant)."""
    a, b = _mats(rng, 96, 80, 64, "complex64")
    real_mm = lambda x, y: ops.matmul(x, y, backend="pallas_interpret")
    out = precision.complex_matmul(a, b, real_mm, algorithm=algorithm)
    ref = np.asarray(a) @ np.asarray(b)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-3)


def test_gemm_chokepoint_backends(rng):
    a = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(32, 48)), jnp.float32)
    ref = np.asarray(matmul_ref(a, b))
    for backend in ("xla", "pallas_interpret", "naive_interpret"):
        out = gemm.matmul(a, b, backend=backend)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5,
                                   atol=1e-4, err_msg=backend)


def test_gemm_batched_and_vjp(rng):
    a = jnp.asarray(rng.normal(size=(3, 16, 24)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(24, 8)), jnp.float32)
    out = gemm.matmul(a, b, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=1e-5, atol=1e-4)

    def f(a_, b_):
        return jnp.sum(gemm.matmul(a_, b_, backend="pallas_interpret") ** 2)

    ga, gb = jax.grad(f, argnums=(0, 1))(a, b)
    ga_ref, gb_ref = jax.grad(
        lambda a_, b_: jnp.sum((a_ @ b_) ** 2), argnums=(0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(ga_ref), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb), np.asarray(gb_ref), rtol=1e-4,
                               atol=1e-3)


def test_elementwise_kernels(rng):
    from repro.kernels.elementwise import axpy, binary_op
    x = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(128, 256)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(binary_op(x, y, "add", interpret=True)),
        np.asarray(x + y), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(binary_op(x, y, "sub", interpret=True)),
        np.asarray(x - y), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(axpy(3.0, x, y, interpret=True)),
        np.asarray(3.0 * x + y), rtol=1e-5, atol=1e-5)
