"""Plain reference of the Granite-3.0 MoE train cells: a decoder LM whose
every layer is GQA attention then a mixture of experts (Granite-3.0
MoE: no biases, RoPE, RMSNorm, tied embedding, top-k of the routed
experts with gates renormalised over the k, SwiGLU experts, dropless),
Granite's scaled paths, its training objective, the Celeris coded
gradient sync and AdamW, in straightforward ``jax.numpy`` at float32
with ``HIGHEST`` matmul precision.

It builds on ``DenseLM`` (``bench/reference/dense_lm.py``), which it
does not change: the coded sync, AdamW and the float8 control are
that file's.  It imports nothing of the program, and reads the weights
in the program's layout (layers stacked on a leading axis; a norm
multiplies by ``1 + scale``; experts stacked as ``wg``, ``wi`` (E, d, f)
and ``wo`` (E, f, d); the router (d, E)).

By definition, per token and layer:

- the router's logits ``h @ router``; gates: softmax over the experts,
  its top ``k`` renormalised to sum to 1 (the same as a softmax over the
  top-k logits); every one of the ``E`` experts is computed for every
  token and the outputs are summed with those gates, zero for the
  experts not chosen: nothing is dropped;
- ``x + residual_multiplier * f(x)`` on both branches, attention scores
  times ``attention_multiplier``, embeddings times
  ``embedding_multiplier``, logits divided by ``logits_scaling``;
- the objective: the mean next-token cross-entropy plus, per layer,
  ``aux_loss_coef * E * sum_e mean_t(p_te) * f_e`` (``f_e`` the share of
  the batch's routed picks that went to expert ``e``, a constant) and
  ``router_z_loss_coef * mean_t(logsumexp_e(logit_te)^2)``, over the
  whole batch's tokens.  Both are linear in per-token terms once ``f``
  is known, so a forward pass over the batch first gives ``f`` and the
  gradient is then taken one row at a time.

Departures, each the program's and listed in the configuration's
``departures``: RoPE rotates interleaved pairs; the router's logits are
float32 (float32 weights, the matmul at ``HIGHEST``) where the published
bfloat16 model rounds them to bfloat16.

It is computed in blocks so that it fits the chip once the program's
state is freed: one row of the batch at a time, each layer under
``jax.checkpoint``, and AdamW's moments kept on the host while the
gradient is computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.checks import leaf_norms
from bench.reference.dense_lm import HIGHEST, DenseLM, _rms, _rope


class GraniteMoE(DenseLM):
    """The reference for one configuration file and traffic mix."""

    def __init__(self, cfg: dict, traffic: dict, *,
                 precision: str = "float32"):
        super().__init__(cfg, traffic, precision=precision)
        self.embed_scale = float(cfg["embedding_multiplier"])
        self.attn_scale = float(cfg["attention_multiplier"])
        self.res = float(cfg["residual_multiplier"])
        self.logits_scaling = float(cfg["logits_scaling"])
        self.n_experts = int(cfg["num_local_experts"])
        self.top_k = int(cfg["num_experts_per_tok"])
        self.aux_coef = float(cfg["aux_loss_coef"])
        self.z_coef = float(cfg["router_z_loss_coef"])
        self._routes_all = jax.jit(self._batch_routes)

    # -- model ---------------------------------------------------------
    def _attn(self, x, p):
        s = x.shape[0]
        a = p["attn"]
        h = _rms(x, p["ln1"]["scale"], self.eps)
        q = self._mm(h, a["wq"]).reshape(s, self.h, self.hd)
        k = self._mm(h, a["wk"]).reshape(s, self.kv, self.hd)
        v = self._mm(h, a["wv"]).reshape(s, self.kv, self.hd)
        q, k = _rope(q, self.theta), _rope(k, self.theta)
        rep = self.h // self.kv           # query head j reads kv head j//rep
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", self.q(q), self.q(k),
                        precision=HIGHEST) * self.attn_scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", self.q(pr), self.q(v),
                       precision=HIGHEST).reshape(s, self.h * self.hd)
        return x + self.res * self._mm(o, a["wo"])

    def _router(self, h, m):
        """(logits (S, E), probs (S, E), top-k ids (S, k), gates (S, E))."""
        logits = self._mm(h, m["router"])
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, self.top_k)
        top_p = top_p / top_p.sum(-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(h.shape[0])[:, None], top_i].set(top_p)
        return logits, probs, top_i, gates

    def _moe(self, x, p, share):
        """The MoE branch of one layer and its auxiliary terms, given
        ``share`` (E,), each expert's share of the batch's routed picks."""
        m = p["moe"]
        h = _rms(x, p["ln2"]["scale"], self.eps)
        logits, probs, _, gates = self._router(h, m)
        qh = self.q(h)
        a = jnp.einsum("sd,edf->sef", qh, self.q(m["wg"]), precision=HIGHEST)
        b = jnp.einsum("sd,edf->sef", qh, self.q(m["wi"]), precision=HIGHEST)
        y = jnp.einsum("sef,efd->sed", self.q(jax.nn.silu(a) * b),
                       self.q(m["wo"]), precision=HIGHEST)
        y = jnp.einsum("se,sed->sd", gates, y, precision=HIGHEST)
        aux = (self.aux_coef * self.n_experts
               * jnp.sum(probs.mean(0) * share)
               + self.z_coef * jnp.mean(
                   jnp.square(jax.nn.logsumexp(logits, axis=-1))))
        return x + self.res * y, aux

    def _layer_loss(self, x, inp):
        p, share = inp
        x, aux = self._moe(self._attn(x, p), p, share)
        return x, aux

    def _embed(self, params, tokens):
        return self.q(params["embed"]["table"])[tokens] * self.embed_scale

    def _row_routes(self, params, tokens):
        """Each layer's top-k expert ids of one row (L, S, k)."""
        def body(x, p):
            x = self._attn(x, p)
            h = _rms(x, p["ln2"]["scale"], self.eps)
            top_i = self._router(h, p["moe"])[2]
            x, _ = self._moe(x, p, jnp.zeros((self.n_experts,)))
            return x, top_i
        _, ids = jax.lax.scan(body, self._embed(params, tokens),
                              params["decoder"]["groups"][0])
        return ids

    def _batch_routes(self, params, tokens):
        """(B, L, S, k) expert ids of every row, one row at a time."""
        return jax.lax.map(lambda t: self._row_routes(params, t), tokens)

    def routes(self, params, tokens) -> np.ndarray:
        """Every layer's top-k expert ids for a batch, (L, B*S, k)."""
        ids = np.asarray(self._routes_all(params, jnp.asarray(tokens)))
        b, n_l, s, k = ids.shape
        return ids.transpose(1, 0, 2, 3).reshape(n_l, b * s, k)

    def _row_loss(self, params, tokens, labels, shares):
        """One row's cross-entropy plus its share of the auxiliary
        terms, given every layer's expert shares (L, E)."""
        table = params["embed"]["table"]
        x, aux = jax.lax.scan(jax.checkpoint(self._layer_loss),
                              self._embed(params, tokens),
                              (params["decoder"]["groups"][0], shares))
        h = _rms(x, params["final_norm"]["scale"], self.eps)
        logits = self._mm(h, table.T)[:-1] / self.logits_scaling
        tgt = labels[1:]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tgt[:, None], -1)[:, 0]
        return nll.mean() + aux.sum()

    def _rows_grads(self, params, tokens, labels):
        """Mean objective and gradient over rows (B, S): a forward pass
        over the batch gives each layer's expert shares, then one row at
        a time."""
        ids = self._batch_routes(params, tokens)           # (B, L, S, k)
        n_l = ids.shape[1]
        shares = jax.vmap(lambda i: jnp.zeros(self.n_experts).at[
            i.reshape(-1)].add(1.0) / i.size)(
                ids.transpose(1, 0, 2, 3).reshape(n_l, -1))
        shares = jax.lax.stop_gradient(shares)

        def body(acc, row):
            loss, g = jax.value_and_grad(self._row_loss)(params, *row,
                                                         shares)
            return (acc[0] + loss,
                    jax.tree.map(jnp.add, acc[1], g)), None
        zero = jax.tree.map(jnp.zeros_like, params)
        (loss, g), _ = jax.lax.scan(body, (jnp.float32(0.0), zero),
                                    (tokens, labels))
        n = tokens.shape[0]
        return loss / n, jax.tree.map(lambda a: a / n, g)

    # -- the checked steps -------------------------------------------------
    def steps(self, init, batches, keys, drops, *, half=False):
        """As ``DenseLM.steps``, with AdamW's moments on the host while
        the gradient is computed; also returns ``routes``, each step's
        expert ids (L, B*S, k) as this reference routes them."""
        params = init()
        host = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), params)
        mu, nu = host, host
        losses, mu1, routes = [], None, []
        for t, (batch, key, drop) in enumerate(zip(batches, keys, drops)):
            tokens = np.asarray(batch["tokens"])
            routes.append(self.routes(
                params, tokens[: tokens.shape[0] // 2] if half else tokens))
            loss, g = self.grads(params, batch, key, drop, half=half)
            losses.append(float(loss))
            params, mu_d, nu_d = self.adamw(params, g, jax.device_put(mu),
                                            jax.device_put(nu), t + 1)
            del g
            if t == 0:
                mu1 = leaf_norms(mu_d)
            if t + 1 < len(batches):
                mu, nu = jax.device_get(mu_d), jax.device_get(nu_d)
            del mu_d, nu_d
        p0 = init()
        change = leaf_norms(jax.tree.map(jnp.subtract, params, p0))
        return {"loss": losses, "grad_norms": mu1, "change_norms": change,
                "routes": routes}


def routing_flips(program: list, reference: list) -> dict:
    """Routing decisions (token, layer, chosen expert) of the program
    that the reference did not make, over the checked steps: each list
    holds one (L, G, k) array of expert ids per step."""
    flips = total = 0
    for a, b in zip(program, reference):
        a, b = np.asarray(a), np.asarray(b)
        same = (a[..., :, None] == b[..., None, :]).any(-1)
        flips += int((~same).sum())
        total += a.size
    return {"flips": flips, "decisions": total,
            "share": flips / max(total, 1)}
