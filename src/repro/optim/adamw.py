"""AdamW with mixed precision + global-norm clipping (pure JAX).

Mixed-precision layout: model params live in bf16 (compute dtype); the
optimizer keeps an fp32 master copy plus fp32 m/v moments.  Under the
production mesh the moments/master are additionally sharded over the
``data`` axis (ZeRO-1) via the sharding rules in
``repro.train.sharding_rules``.

Schedule: linear warmup -> cosine decay.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

Params = Any


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0


def schedule(cfg: OptConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = (step - cfg.warmup_steps) / jnp.maximum(
        cfg.total_steps - cfg.warmup_steps, 1)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params: Params) -> Dict[str, Any]:
    # copy=True: astype(f32) on an f32 leaf would alias the param buffer
    # and break donation (same buffer donated twice in the train step).
    f32 = lambda p: jnp.array(p, dtype=jnp.float32, copy=True)
    return {
        "master": jax.tree.map(f32, params),
        "mu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "nu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
        "count": jnp.zeros((), jnp.int32),
    }


def global_norm(tree: Params) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in jax.tree.leaves(tree)))


@jax.named_scope("optimizer")
def apply_updates(params: Params, grads: Params, state: Dict[str, Any],
                  cfg: OptConfig):
    """Returns (new_params (compute dtype), new_state, metrics).

    Runs under the ``optimizer`` name scope: clip norm, moments, master
    update and the cast back all carry it in their HLO metadata."""
    count = state["count"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-9))
    lr = schedule(cfg, count)

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** count.astype(jnp.float32)
    bc2 = 1 - b2 ** count.astype(jnp.float32)

    def upd(g, m, v, w):
        g = g.astype(jnp.float32) * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        step = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
        w = w - lr * (step + cfg.weight_decay * w)
        return m, v, w

    flat_g, tdef = jax.tree.flatten(grads)
    flat_m = tdef.flatten_up_to(state["mu"])
    flat_v = tdef.flatten_up_to(state["nu"])
    flat_w = tdef.flatten_up_to(state["master"])
    out = [upd(g, m, v, w) for g, m, v, w in
           zip(flat_g, flat_m, flat_v, flat_w)]
    mu = tdef.unflatten([o[0] for o in out])
    nu = tdef.unflatten([o[1] for o in out])
    master = tdef.unflatten([o[2] for o in out])

    flat_p = tdef.flatten_up_to(params)
    new_params = tdef.unflatten([
        w.astype(p.dtype) for w, p in
        zip([o[2] for o in out], flat_p)])
    new_state = {"master": master, "mu": mu, "nu": nu, "count": count}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
