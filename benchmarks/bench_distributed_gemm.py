"""Multi-accelerator GEMM (the paper's Tesla S2050 section).

Runs the three shard_map schedules in this process over every device
JAX sees (one device degenerates each schedule to a local GEMM; for a
multi-device run on a CPU host, start the interpreter with
XLA_FLAGS=--xla_force_host_platform_device_count=8), measures
wall-clock, and reports the ICI-byte model per schedule — the
quantified form of the paper's 'matrices must be very large to amortise
multi-GPU transfer' remark.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.distributed import comm_model_bytes, sharded_matmul
from repro.launch.mesh import auto_axes
from repro.tuning.timing import time_jax


def run() -> None:
    p = len(jax.devices())
    mesh = jax.make_mesh((p,), ("model",), auto_axes(1))
    rng = np.random.default_rng(0)
    m = k = n = 1024
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    ref = a @ b
    for sched in ("ring", "column", "row"):
        f = jax.jit(lambda x, y, s=sched: sharded_matmul(x, y, mesh,
                                                         schedule=s))
        err = float(jnp.max(jnp.abs(f(a, b) - ref)))
        t = time_jax(f, a, b)
        comm = comm_model_bytes(m, n, k, p, 4, sched)
        emit(f"distributed_gemm_{sched}_{p}dev_{m}", t,
             f"maxerr={err:.2e};model_ici_bytes_per_dev={comm}")


if __name__ == "__main__":
    run()
