"""Multi-accelerator GEMM (the paper's Tesla S2050 section) on 8
forced-host devices: ring / column / row schedules, with weak-scaling
sanity and the ICI-byte model.

    PYTHONPATH=src python examples/distributed_gemm.py
(re-execs itself with XLA_FLAGS to get 8 devices)
"""

import os
import sys

if "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.execv(sys.executable, [sys.executable] + sys.argv)

sys.path.insert(0, "src")

import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.distributed import comm_model_bytes, sharded_matmul  # noqa: E402
from repro.launch.mesh import auto_axes  # noqa: E402


def main():
    mesh = jax.make_mesh((8,), ("model",), auto_axes(1))
    rng = np.random.default_rng(0)
    m = k = n = 1024
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    ref = a @ b

    print(f"devices: {len(jax.devices())}, GEMM {m}x{k}x{n}")
    for sched in ("column", "row", "ring"):
        f = jax.jit(lambda x, y, s=sched: sharded_matmul(x, y, mesh,
                                                         schedule=s))
        out = f(a, b)
        err = float(jnp.max(jnp.abs(out - ref)))
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(3):
            jax.block_until_ready(f(a, b))
        t = (time.perf_counter() - t0) / 3
        comm = comm_model_bytes(m, n, k, 8, 4, sched)
        print(f"  {sched:8s} {t*1e3:7.1f}ms  max|err|={err:.2e}  "
              f"model ICI bytes/dev={comm/1e6:.1f}MB")
    print("ring schedule overlaps collective-permute with local dots "
          "(see HLO); the paper's 'matrices must be very large' remark "
          "is the comm column above vs the n^3 compute.")


if __name__ == "__main__":
    main()
