"""Kernel micro-bench: Pallas kernels vs jnp oracles, us/call.

On the CPU backend the Pallas kernels run in interpret mode, so the
timings are an interface check, not a perf claim; on a TPU the same
calls run the compiled kernels.
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
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (256, 4096))
    signs = jax.random.rademacher(jax.random.PRNGKey(7), (4096,),
                                  dtype=jnp.float32)

    jit_ref = jax.jit(ref.fwht)
    us_ref = _time(jit_ref, x)
    print(f"fwht jnp-oracle    (256,4096): {us_ref:10.1f} us")
    rows.append(("kernel_fwht_ref_us", round(us_ref, 1), None))

    us_pal = _time(lambda a: ops.fwht(a), x)
    print(f"fwht pallas        (256,4096): {us_pal:10.1f} us")
    rows.append(("kernel_fwht_pallas_us", round(us_pal, 1), None))

    # fused sign-multiply + scale (what coding.encode issues)
    us_fused = _time(lambda a, s: ops.fwht(a, signs=s, scale=4096 ** -0.5),
                     x, signs)
    print(f"fwht pallas fused  (256,4096): {us_fused:10.1f} us")
    rows.append(("kernel_fwht_pallas_fused_us", round(us_fused, 1), None))

    noise = jax.random.uniform(jax.random.PRNGKey(1), (256, 4096))
    jit_q = jax.jit(lambda a, b: ref.quantize_int8(a, b))
    us_q = _time(jit_q, x, noise)
    print(f"quantize jnp       (256,4096): {us_q:10.1f} us")
    rows.append(("kernel_quant_ref_us", round(us_q, 1), None))

    us_qp = _time(lambda a, b: ops.quantize_int8(a, b), x, noise)
    print(f"quantize pallas    (256,4096): {us_qp:10.1f} us")
    rows.append(("kernel_quant_pallas_us", round(us_qp, 1), None))

    # fused rotate+quantize (one kernel, no HBM round trip between the
    # stages — what coding.encode_quantized issues) vs the unfused pair
    us_pair = _time(
        lambda a, s, b: ops.quantize_int8(
            ops.fwht(a, signs=s, scale=4096 ** -0.5), b),
        x, signs, noise)
    print(f"fwht+quant unfused (256,4096): {us_pair:10.1f} us")
    rows.append(("kernel_fwht_quant_unfused_us", round(us_pair, 1), None))

    us_fq = _time(
        lambda a, s, b: ops.fwht_quantize(a, b, signs=s,
                                          scale=4096 ** -0.5),
        x, signs, noise)
    print(f"fwht+quant fused   (256,4096): {us_fq:10.1f} us")
    rows.append(("kernel_fwht_quant_fused_us", round(us_fq, 1), None))
    return rows
