"""Compile-only checks of the main-path Pallas kernels for a described
TPU v5e, at the widths the chip runs (qwen3-0.6b, mamba2-2.7b and the
paper's 4096^3 GEMM).

Nothing executes: each test lowers one kernel for a v5e that is
described, not attached, and asserts that Mosaic accepted it
(a `tpu_custom_call` in the compiled HLO). That is what interpret mode
cannot show — block shapes that break the (8, 128) tiling rule and
working sets over the scoped VMEM limit are refused here, at no chip
time. The topology is described inside a fixture, never at import:
only the worker that runs this file loads the TPU compiler.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.policy import Policy
from repro.kernels import ops

PALLAS = Policy(backend="pallas", interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_tiled_gemm_4096_compiles(one_chip, dtype):
    n = 4096
    _compile(lambda a, b: ops.matmul(a, b, policy=PALLAS), one_chip,
             ((n, n), dtype), ((n, n), dtype))


@pytest.mark.parametrize("m", [2048, 4])
def test_gated_gemm_compiles(one_chip, m):
    _compile(lambda a, g, u: ops.gated_matmul(a, g, u, policy=PALLAS),
             one_chip, ((m, 1024), jnp.bfloat16),
             ((1024, 3072), jnp.bfloat16), ((1024, 3072), jnp.bfloat16))


def test_matmul_q_decode_compiles(one_chip):
    _compile(lambda a, w, s: ops.matmul_q(a, w, s, policy=PALLAS),
             one_chip, ((4, 1024), jnp.bfloat16), ((1024, 3072), jnp.int8),
             ((3072,), jnp.float32))


_Q = ((1, 2048, 16, 128), jnp.bfloat16)
_KV = ((1, 2048, 8, 128), jnp.bfloat16)


def test_flash_fwd_compiles(one_chip):
    _compile(lambda q, k, v: ops.flash_attention_fwd(
        q, k, v, causal=True, policy=PALLAS), one_chip, _Q, _KV, _KV)


def test_flash_bwd_compiles(one_chip):
    _compile(lambda q, k, v, o, do, lse: ops.flash_attention_bwd(
        q, k, v, o, do, lse, causal=True, policy=PALLAS),
        one_chip, _Q, _KV, _KV, _Q, _Q, ((1, 16, 2048), jnp.float32))


def test_flash_decode_compiles(one_chip):
    _compile(lambda q, k, v, pos: ops.flash_decode(
        q, k, v, pos=pos, policy=PALLAS), one_chip,
        ((4, 1, 16, 128), jnp.bfloat16), ((4, 2048, 8, 128), jnp.bfloat16),
        ((4, 2048, 8, 128), jnp.bfloat16), ((4,), jnp.int32))


def _cache_consumers(text, params):
    """The ops of the compiled module's entry computation that read the
    named parameters, following bitcasts (which move no bytes)."""
    entry = text[text.index("ENTRY"):].split("\n}")[0]
    defs = re.findall(r"^\s*(?:ROOT )?%(\S+) = .*?\s([\w-]+)\((.*)$",
                      entry, re.M)
    names = {n for n, op, args in defs
             if op == "parameter" and re.match(r"(\d+)\)", args)
             and int(re.match(r"(\d+)\)", args).group(1)) in params}
    readers, grew = [], True
    while grew:
        grew = False
        for n, op, args in defs:
            if n in names or not any(f"%{m}" in re.findall(r"%[\w.\-]+", args)
                                     for m in names):
                continue
            if op == "bitcast":
                names.add(n)
                grew = True
            elif (n, op) not in readers:
                readers.append((n, op))
    return readers


@pytest.mark.parametrize("b,tk,h,hkv,d", [
    (24, 2560, 16, 8, 128),           # qwen3-0.6b chat: 24 slots x 2560
    (4, 2048, 12, 2, 128),            # qwen2-vl-2b
    (4, 2048, 48, 1, 128),            # granite-20b (one kv head)
    (4, 2048, 40, 40, 128),           # qwen1.5-32b (MHA)
])
def test_flash_decode_reads_cache_in_place(one_chip, b, tk, h, hkv, d):
    """The decode kernel takes K and V as the cache stores them: the
    only op of the compiled module that reads either is the kernel
    (through free bitcasts), so no copy or transpose of the cache runs
    per step."""
    text = _compile(lambda q, k, v, pos: ops.flash_decode(
        q, k, v, pos=pos, policy=PALLAS), one_chip,
        ((b, 1, h, d), jnp.bfloat16), ((b, tk, hkv, d), jnp.bfloat16),
        ((b, tk, hkv, d), jnp.bfloat16), ((b,), jnp.int32))
    readers = _cache_consumers(text, params={1, 2})
    assert readers and all(op == "custom-call" for _, op in readers), \
        readers


@pytest.mark.parametrize("b,tk,h,hkv,d", [
    (4, 448, 6, 6, 64),               # whisper-tiny self-attention
    (4, 2048, 32, 32, 64),            # zamba2-1.2b shared attention
])
def test_flash_decode_head_dim_64_compiles(one_chip, b, tk, h, hkv, d):
    # at 64-lane rows the compiled module relayouts K and V (a copy)
    # before the kernel, where the old kernel's operand transpose was
    _compile(lambda q, k, v, pos: ops.flash_decode(
        q, k, v, pos=pos, policy=PALLAS), one_chip,
        ((b, 1, h, d), jnp.bfloat16), ((b, tk, hkv, d), jnp.bfloat16),
        ((b, tk, hkv, d), jnp.bfloat16), ((b,), jnp.int32))


_POOL = (512, 8, 16, 128)             # [P, Hkv, page_size, D]
_DECODE = (((4, 1, 16, 128), jnp.bfloat16), ((4, 128), jnp.int32),
           ((4,), jnp.int32))


def test_flash_decode_paged_compiles(one_chip):
    (q, t, pos) = _DECODE
    _compile(lambda q, kp, vp, t, pos: ops.flash_decode_paged(
        q, kp, vp, t, pos=pos, policy=PALLAS), one_chip,
        q, (_POOL, jnp.bfloat16), (_POOL, jnp.bfloat16), t, pos)


def test_flash_decode_paged_int8_compiles(one_chip):
    (q, t, pos) = _DECODE
    _compile(lambda q, kp, vp, t, pos, ks, vs: ops.flash_decode_paged(
        q, kp, vp, t, pos=pos, ks=ks, vs=vs, policy=PALLAS), one_chip,
        q, (_POOL, jnp.int8), (_POOL, jnp.int8), t, pos,
        (_POOL[:3], jnp.float32), (_POOL[:3], jnp.float32))


def test_ssd_compiles(one_chip):
    # mamba2-2.7b: d_inner 5120 = 80 heads x 64, d_state 128, chunk 256
    _compile(lambda x, a, b, c: ops.ssd(x, a, b, c, 256, policy=PALLAS),
             one_chip, ((1, 2048, 80, 64), jnp.bfloat16),
             ((1, 2048, 80), jnp.float32), ((1, 2048, 1, 128), jnp.bfloat16),
             ((1, 2048, 1, 128), jnp.bfloat16))
