"""Serving launcher: batched prefill + greedy decode.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
        --batch 4 --prompt-len 32 --gen 16

On a real pod, drop --smoke and pick --mesh single|multi (the decode
cells of the dry-run prove the production lowering; this CLI is the
runnable host loop).
"""
import argparse
import time

import jax
import jax.numpy as jnp

import repro.configs as C
from repro import sharding as shd
from repro.launch import compile_cache
from repro.launch import mesh as mesh_mod
from repro.models import model as M
from repro.serve import serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", choices=["none", "single", "multi"],
                    default="none")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    if args.mesh != "none":
        mesh = mesh_mod.make_production_mesh(multi_pod=args.mesh == "multi")
        shd.set_global_mesh(mesh)

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    s_max = args.prompt_len + args.gen
    prefill = serve_step.make_prefill(cfg, s_max)
    decode = serve_step.make_decode(cfg)

    t0 = time.perf_counter()
    logits, caches = prefill(params, {"tokens": prompt})
    jax.block_until_ready(logits)
    print(f"prefill: {time.perf_counter()-t0:.2f}s")

    tok = jnp.argmax(logits, -1)[:, None]
    t0 = time.perf_counter()
    for i in range(args.gen - 1):
        logits, caches = decode(params, caches, {"tokens": tok},
                                jnp.int32(args.prompt_len + i))
        tok = jnp.argmax(logits, -1)[:, None]
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    n = args.batch * (args.gen - 1)
    print(f"decode: {n} tokens in {dt:.2f}s ({n/dt:.1f} tok/s)")


if __name__ == "__main__":
    main()
