"""The one generator that every traffic file is read by.

Train traffic: token batches, the per-step drop draws of a lognormal
straggler model, and the weights, all from ``--seed``.  Nothing here
imports the program.
"""
from __future__ import annotations

import math

import numpy as np

WEIGHTS_STREAM = 2 ** 31 - 1   # folded into the seed's key for weights


class TokenBatches:
    """Batch ``step`` is a pure function of (seed, step): i.i.d. tokens
    over the whole vocabulary, every row different.  ``labels`` are the
    tokens (the loss shifts them)."""

    def __init__(self, traffic: dict, vocab_size: int, seed: int):
        self.seq = int(traffic["seq_len"])
        self.batch = int(traffic["global_batch"])
        self.vocab = int(vocab_size)
        self.seed = int(seed)

    def global_batch(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed, int(step)))
        toks = rng.integers(0, self.vocab, size=(self.batch, self.seq),
                            dtype=np.int32)
        return {"tokens": toks, "labels": toks}


class Straggler:
    """Per-step drop rate from the controller's timeout: a chunk's
    latency is lognormal(ln median, sigma), its median scaled by
    ``burst_scale`` on a burst step, and drop = P(latency > timeout),
    held to [0, 0.5].  Draws come from the benchmark's own generator
    (seeded from ``--seed``), and every rate handed out is kept in
    ``drops`` for the reference."""

    def __init__(self, params: dict, seed: int):
        self.median_latency = float(params["median_latency"])
        self.sigma = float(params["sigma"])
        self.burst_prob = float(params["burst_prob"])
        self.burst_scale = float(params["burst_scale"])
        self._rng = np.random.default_rng((int(seed), 0x57a6))
        self.drops: list = []

    def drop_rate(self, timeout: float, rng=None) -> float:
        med = self.median_latency
        if self._rng.random() < self.burst_prob:
            med *= self.burst_scale
        z = (math.log(max(timeout, 1e-9)) - math.log(med)) / self.sigma
        p_late = 0.5 * (1.0 - math.erf(z / math.sqrt(2.0)))
        rate = float(min(max(p_late, 0.0), 0.5))
        self.drops.append(rate)
        return rate


def weight_init(shapes, seed: int, std: float):
    """Weights in the layout of ``shapes`` (a pytree of
    ``ShapeDtypeStruct``), made on the device in one jitted call:
    normal(0, ``std``) for matrices, zeros for biases and for the
    norms' ``scale`` (a norm multiplies by ``1 + scale``)."""
    import jax
    import jax.numpy as jnp

    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def kind(path) -> str:
        last = str(getattr(path[-1], "key", path[-1]))
        if last == "scale" or last.startswith("b"):
            return "zeros"
        return "normal"

    kinds = [kind(p) for p, _ in paths]

    def make(key):
        keys = jax.random.split(key, len(paths))
        out = []
        for k, (_, s), how in zip(keys, paths, kinds):
            if how == "zeros":
                out.append(jnp.zeros(s.shape, s.dtype))
            else:
                out.append((std * jax.random.normal(k, s.shape, jnp.float32))
                           .astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.fold_in(jax.random.PRNGKey(seed), WEIGHTS_STREAM)
    return make, key
