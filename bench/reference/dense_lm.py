"""Plain reference of the train cells: a dense decoder LM (Qwen2:
GQA with QKV bias, RoPE, SwiGLU, RMSNorm, tied embedding), its loss and
gradients, the Celeris coded gradient sync and AdamW, in straightforward
``jax.numpy`` at float32 with ``HIGHEST`` matmul precision.

It imports nothing of the program.  It reads the weights in the
program's parameter layout (layers stacked on a leading axis; a norm
multiplies by ``1 + scale``, so ``scale = weight - 1``), which the
benchmark itself makes from the seed.  Departures from the published
Qwen2, each one the program's, are in the configuration's
``departures``: the embedding is multiplied by sqrt(hidden_size), and
RoPE rotates interleaved pairs ``(2i, 2i+1)`` (a permutation of the
query and key columns of the half-split form).

The coded sync is the protocol's own definition, in its one-chip
form: per leaf ``i`` of at least ``min_coded_size`` elements, the
flattened leaf is laid out as tiles of ``n_rot`` wire rows, rotated by
``H D / sqrt(n_rot)`` with Rademacher signs from ``fold_in(key, 2i)``;
the chip (peer 0) loses wire row ``r`` where
``uniform(fold_in(fold_in(key, 2i+1), 0))[r] < drop``; the received
rows are scaled by n_rot/(rows received) and rotated back.  Smaller
leaves pass unchanged.

``precision="float8"`` is the control: every matmul operand is rounded
to float8 with a per-tensor scale, e4m3 forward and e5m2 for its
gradient (accumulation stays float32).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.checks import leaf_norms

HIGHEST = jax.lax.Precision.HIGHEST


def _round(x, dtype, top):
    s = jnp.max(jnp.abs(x)) / top
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _f8(x):
    """A matmul operand in float8 as FP8 training keeps it: e4m3 on the
    way forward, its gradient in e5m2 on the way back, each with a
    per-tensor scale."""
    return _round(x, jnp.float8_e4m3fn, 448.0)


def _f8_fwd(x):
    return _f8(x), None


def _f8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, 57344.0),)


_f8.defvjp(_f8_fwd, _f8_bwd)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, theta):
    """x: (S, H, D); rotates pairs (2i, 2i+1) by pos * theta^(-2i/D)."""
    s, _, d = x.shape
    freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freq
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x1 * sn + x2 * c], -1).reshape(
        x.shape)


def hadamard(n: int) -> np.ndarray:
    """Sylvester H_n (entries +-1)."""
    h = np.ones((1, 1), np.float32)
    while h.shape[0] < n:
        h = np.block([[h, h], [h, -h]])
    return h


class DenseLM:
    """The reference for one configuration file and traffic mix."""

    def __init__(self, cfg: dict, traffic: dict, *,
                 precision: str = "float32"):
        self.d = cfg["hidden_size"]
        self.h = cfg["num_attention_heads"]
        self.kv = cfg["num_key_value_heads"]
        self.hd = self.d // self.h
        self.theta = float(cfg["rope_theta"])
        self.eps = float(cfg["rms_norm_eps"])
        self.embed_scale = math.sqrt(self.d)
        self.opt = cfg["optimizer"]
        self.mode = traffic["mode"]
        self.coding = traffic.get("celeris", {})
        self.q = _f8 if precision == "float8" else (lambda x: x)
        self._jits: dict = {}
        self._scan_grads = jax.jit(self._rows_grads)

    # -- model ---------------------------------------------------------
    def _mm(self, a, b):
        return jnp.matmul(self.q(a), self.q(b), precision=HIGHEST)

    def _layer(self, x, p):
        s = x.shape[0]
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], self.eps)
        q = (self._mm(h, a["wq"]) + a["bq"]).reshape(s, self.h, self.hd)
        k = (self._mm(h, a["wk"]) + a["bk"]).reshape(s, self.kv, self.hd)
        v = (self._mm(h, a["wv"]) + a["bv"]).reshape(s, self.kv, self.hd)
        q, k = _rope(q, self.theta), _rope(k, self.theta)
        rep = self.h // self.kv           # query head j reads kv head j//rep
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", self.q(q), self.q(k),
                        precision=HIGHEST) / math.sqrt(self.hd)
        causal = jnp.tril(jnp.ones((s, s), bool))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", self.q(pr), self.q(v),
                       precision=HIGHEST).reshape(s, self.h * self.hd)
        x = x + self._mm(o, a["wo"])
        m = p["mlp"]
        h = _rms(x, p["ln2"]["scale"], self.eps)
        x = x + self._mm(jax.nn.silu(self._mm(h, m["wg"]))
                         * self._mm(h, m["wi"]), m["wo"])
        return x, None

    def _row_loss(self, params, tokens, labels):
        """Mean next-token cross-entropy of one row (S,)."""
        table = params["embed"]["table"]
        x = self.q(table)[tokens] * self.embed_scale
        x, _ = jax.lax.scan(jax.checkpoint(self._layer), x,
                            params["decoder"]["groups"][0])
        h = _rms(x, params["final_norm"]["scale"], self.eps)
        logits = self._mm(h, table.T)[:-1]
        tgt = labels[1:]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tgt[:, None], -1)[:, 0]
        return nll.mean()

    def _rows_grads(self, params, tokens, labels):
        """Mean loss and gradient over rows (B, S), one row at a time."""
        def body(acc, row):
            loss, g = jax.value_and_grad(self._row_loss)(params, *row)
            return (acc[0] + loss,
                    jax.tree.map(jnp.add, acc[1], g)), None
        zero = jax.tree.map(jnp.zeros_like, params)
        (loss, g), _ = jax.lax.scan(body, (jnp.float32(0.0), zero),
                                    (tokens, labels))
        n = tokens.shape[0]
        return loss / n, jax.tree.map(lambda a: a / n, g)

    # -- coded sync ------------------------------------------------------
    def _plan(self, shape):
        m = int(np.prod(shape))
        if m < self.coding.get("min_coded_size", 65536):
            return None
        n_rot = int(self.coding.get("n_rot", 4096))
        while n_rot > 1 and n_rot > m:
            n_rot //= 2
        n_rot = max(n_rot, 2)
        return {"m": m, "n_rot": n_rot, "tiles": -(-m // n_rot),
                "shape": tuple(shape)}

    @staticmethod
    def _rotate(t, n_rot):
        """Normalized Hadamard transform along axis 1 of (T, n_rot), as
        H_a (x) H_b with a * b = n_rot."""
        a = 2 ** (int(math.log2(n_rot)) // 2)
        b = n_rot // a
        x = t.reshape(t.shape[0], a, b)
        x = jnp.einsum("tab,Aa->tAb", x, jnp.asarray(hadamard(a)),
                       precision=HIGHEST)
        x = jnp.einsum("tAb,Bb->tAB", x, jnp.asarray(hadamard(b)),
                       precision=HIGHEST)
        return x.reshape(t.shape) * (n_rot ** -0.5)

    def _code(self, g, pl, key, i, drop):
        """Encode, lose the chip's dropped wire rows, decode unbiased."""
        n = pl["n_rot"]
        signs = jax.random.rademacher(jax.random.fold_in(key, 2 * i), (n,),
                                      dtype=jnp.float32)
        mask = jax.random.uniform(
            jax.random.fold_in(jax.random.fold_in(key, 2 * i + 1), 0),
            (n,)) >= drop
        t = jnp.pad(g.reshape(-1), (0, pl["tiles"] * n - pl["m"]))
        t = self._rotate(t.reshape(pl["tiles"], n) * signs, n)
        k = jnp.sum(mask)
        t = jnp.where(mask, t, 0.0) * jnp.where(k > 0, n / jnp.maximum(k, 1),
                                                 0.0)
        t = self._rotate(t, n) * signs
        return t.reshape(-1)[: pl["m"]].reshape(pl["shape"])

    def _jit(self, name, fn, **kw):
        if name not in self._jits:
            self._jits[name] = jax.jit(fn, **kw)
        return self._jits[name]

    def grads(self, params, batch, key, drop, *, half=False):
        """(loss, synced gradient) of one step.  ``half`` (a fault) leaves
        out the second half of the batch's rows."""
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        use = tokens.shape[0] // 2 if half else tokens.shape[0]
        loss, g = self._scan_grads(params, tokens[:use], labels[:use])
        if self.mode == "exact":
            return loss, g
        leaves, treedef = jax.tree_util.tree_flatten(g)
        drop = jnp.float32(drop)
        out = []
        for i, gi in enumerate(leaves):
            pl = self._plan(gi.shape)
            if pl is not None:
                gi = self._jit(("code", pl["n_rot"]) + pl["shape"],
                               lambda g_, k_, i_, d_, pl=pl:
                               self._code(g_, pl, k_, i_, d_))(
                                   gi, key, i, drop)
            out.append(gi)
        return loss, jax.tree_util.tree_unflatten(treedef, out)

    # -- optimizer -------------------------------------------------------
    def _lr(self, count):
        o = self.opt
        warm = count / max(o["warmup_steps"], 1)
        prog = min(max((count - o["warmup_steps"])
                       / max(o["total_steps"] - o["warmup_steps"], 1), 0.0),
                   1.0)
        cos = o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5 * (
            1 + math.cos(math.pi * prog))
        return o["lr"] * (warm if count < o["warmup_steps"] else cos)

    def _adamw(self, params, grads, mu, nu, lr, count):
        o = self.opt
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        b1, b2 = o["b1"], o["b2"]
        bc1 = 1 - b1 ** count.astype(jnp.float32)
        bc2 = 1 - b2 ** count.astype(jnp.float32)

        def one(w, g, m, v):
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            step = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
            return w - lr * (step + o["weight_decay"] * w), m, v

        out = jax.tree.map(one, params, grads, mu, nu)
        tdef = jax.tree.structure(params)
        flat = tdef.flatten_up_to(out)
        return tuple(tdef.unflatten([x[j] for x in flat]) for j in range(3))

    def adamw(self, params, grads, mu, nu, count: int):
        """AdamW on float32 weights with global-norm clipping; ``count``
        is the step number from 1.  Consumes ``params``, ``mu``, ``nu``."""
        return self._jit("adamw", self._adamw, donate_argnums=(0, 2, 3))(
            params, grads, mu, nu, jnp.float32(self._lr(count)),
            jnp.int32(count))

    # -- the checked steps -------------------------------------------------
    def steps(self, init, batches, keys, drops, *, half=False):
        """Runs ``len(batches)`` steps from ``init()`` (float32 weights,
        made anew on each call); returns the loss of each step, per-leaf
        norms of the first moment after step 1, and per-leaf norms of
        the weights' change after the last step.  ``half`` goes to
        :meth:`grads`."""
        params = init()
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, mu1 = [], None
        for t, (batch, key, drop) in enumerate(zip(batches, keys, drops)):
            loss, g = self.grads(params, batch, key, drop, half=half)
            losses.append(float(loss))
            params, mu, nu = self.adamw(params, g, mu, nu, t + 1)
            del g
            if t == 0:
                mu1 = leaf_norms(mu)
        del mu, nu
        p0 = init()
        change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        return {"loss": losses, "grad_norms": mu1, "change_norms": change}

