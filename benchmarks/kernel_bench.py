"""Kernel micro-bench: the coded sync's Pallas kernel vs its jnp oracle,
us/call.

On the CPU backend the Pallas kernel runs in interpret mode, so the
timings are an interface check, not a perf claim; on a TPU the same
calls run the compiled kernel.
"""
import time

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref


def _time(f, *args, n=3):
    out = f(*args)                       # one warmup: compile + execute
    jax.block_until_ready(out)           # handles tuples/pytrees too
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / n * 1e6


def run():
    rows = []
    print(f"\n== kernels: us/call on {jax.default_backend()} "
          "(pallas interpreted on cpu) ==")
    n = 4096
    x = jax.random.normal(jax.random.PRNGKey(0), (256, n), jnp.bfloat16)
    signs = jax.random.rademacher(jax.random.PRNGKey(7), (n,),
                                  dtype=jnp.float32)
    mask = jax.random.uniform(jax.random.PRNGKey(1), (n,)) >= 0.1
    colscale = mask * (n / jnp.sum(mask))

    us_ref = _time(jax.jit(ref.coded_roundtrip), x, signs, colscale)
    print(f"coded_roundtrip jnp-oracle (256,4096) bf16: {us_ref:10.1f} us")
    rows.append(("kernel_coded_roundtrip_ref_us", round(us_ref, 1), None))

    us_pal = _time(jax.jit(ops.coded_roundtrip), x, signs, colscale)
    print(f"coded_roundtrip pallas     (256,4096) bf16: {us_pal:10.1f} us")
    rows.append(("kernel_coded_roundtrip_pallas_us", round(us_pal, 1),
                 None))
    return rows
