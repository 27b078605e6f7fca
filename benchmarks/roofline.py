"""Practical MXU/HBM roofline of the attached chip; every timed region
ends in a host fetch of its result.

The bench MFU numbers are quoted against the *published* peak
(telemetry/perfmodel.PEAK_FLOPS_TABLE). This script measures what fraction of that peak a
pure dependent-chain matmul actually sustains here — the practical roof
every end-to-end MFU should be read against.

Prints one JSON line per experiment.
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np


def _fetch(x) -> float:
    return float(np.asarray(jax.device_get(x)).ravel()[0])


def bench_matmul(n: int, dtype, iters: int = 30) -> dict:
    a = jax.random.normal(jax.random.key(0), (n, n), dtype)
    b = jax.random.normal(jax.random.key(1), (n, n), dtype)

    @jax.jit
    def chain(a, b):
        # Dependent chain: each matmul consumes the previous result, so
        # nothing overlaps and the wall time is the sum of the matmuls.
        x = a
        for _ in range(iters):
            x = jnp.tanh(x @ b)   # tanh keeps values bounded (no inf)
        return x[0, 0]

    r = chain(a, b)
    _fetch(r)                      # compile + warm
    t0 = time.perf_counter()
    r = chain(a, b)
    _fetch(r)
    dt = time.perf_counter() - t0
    flops = 2.0 * n * n * n * iters
    return {"experiment": f"matmul_{n}_{jnp.dtype(dtype).name}",
            "tflops": round(flops / dt / 1e12, 1),
            "iters": iters, "seconds": round(dt, 3)}


def bench_hbm(mb: int = 512, iters: int = 30) -> dict:
    n = mb * (1 << 20) // 2          # bf16 elements
    x = jnp.ones((n,), jnp.bfloat16)

    @jax.jit
    def chain(x):
        # optimization_barrier between passes: without it XLA fuses the
        # whole elementwise chain into ONE kernel (one read, one write)
        # and `moved` would overcount traffic by up to iters×.
        for _ in range(iters):
            x = x * 1.0000001 + 1e-7   # read + write each pass
            (x,) = jax.lax.optimization_barrier((x,))
        return x[0]

    _fetch(chain(x))
    t0 = time.perf_counter()
    _fetch(chain(x))
    dt = time.perf_counter() - t0
    moved = 2.0 * mb * (1 << 20) * iters   # read + write per pass
    return {"experiment": f"hbm_stream_{mb}MB",
            "gbyte_per_sec": round(moved / dt / 1e9, 1),
            "seconds": round(dt, 3)}


def main() -> None:
    assert jax.default_backend() == "tpu", jax.devices()
    print(json.dumps({"device": jax.devices()[0].device_kind}))
    for n in (4096, 8192, 16384):
        print(json.dumps(bench_matmul(n, jnp.bfloat16)))
    print(json.dumps(bench_matmul(8192, jnp.float32, iters=8)))
    print(json.dumps(bench_hbm()))


if __name__ == "__main__":
    main()
