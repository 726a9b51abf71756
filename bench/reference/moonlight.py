"""Plain reference of the Moonlight-16B-A3B train cells (DeepSeek-V3's
block): a decoder LM whose first layer is multi-head latent attention
then a dense SwiGLU MLP, and whose other layers are latent attention
then a mixture of experts routed by sigmoid scores with a
load-balancing bias, plus shared experts; its training objective, the
bias update, the Celeris coded gradient sync and AdamW, in
straightforward ``jax.numpy`` at float32 with ``HIGHEST`` matmul
precision.

It builds on ``DenseLM`` (``bench/reference/dense_lm.py``), which it
does not change: the coded sync's definition, AdamW and the float8
control are that file's.  It imports nothing of the program, and reads
the weights in the program's layout: the MoE layers stacked on a
leading axis (``decoder.groups[0]``), the dense layer in
``decoder.lead[0]``, a norm multiplies by ``1 + scale``; attention
``wq`` (d, H*(nope+rope)), ``wkv_a`` (d, latent+rope), the latent's
``kv_norm``, ``wkv_b`` (latent, H*(nope+v)), ``wo`` (H*v, d); experts
stacked as ``wg``, ``wi`` (E_held, d, f) and ``wo`` (E_held, f, d),
the router (d, E) and its ``bias`` (E,); shared experts as one SwiGLU.

By definition, per token and layer (transformers' deepseek_v3):

- attention: q = h wq split per head into q_nope (128) and q_rope (64);
  [latent, k_rope] = h wkv_a; [k_nope, v] = rms(latent) wkv_b per
  head; RoPE on q_rope and on the one k_rope all heads share; scores
  [q_nope, q_rope].[k_nope, k_rope] x (nope+rope)^-1/2, causal
  softmax, values v, out through wo;
- the router: scores s = sigmoid(h router); the top k of s + bias (the
  bias chooses and is no gate); gates the chosen s over their sum,
  times ``routed_scaling_factor``;
- the held share: of the ``experts_routed_over`` experts the chip holds
  ``n_routed_experts`` from ``experts_held_first``; every held expert
  is computed on every token and summed with its gate, zero where the
  token did not choose it; the absent experts' part is left out, as on
  the chip; the shared experts are added on every token;
- the objective: the mean next-token cross-entropy (no auxiliary
  loss: ``seq_aux_loss_alpha`` is 0);
- after each step's AdamW, each layer's bias moves by
  ``bias_update_rate * sign(mean_e(c) - c_e)``, c the step's routed
  count of each of the layer's experts (the bias has no gradient, no
  AdamW and no coding).

Departures, each the program's and in the configuration's
``departures``: the router's logits are float32.

It is computed in blocks so that it fits the chip once the program's
state is freed: one row of the batch at a time, each layer under
``jax.checkpoint``, attention one block of queries at a time (each
under ``jax.checkpoint``), and AdamW's moments on the host while the
gradient is computed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.checks import leaf_norms
from bench.reference.dense_lm import HIGHEST, DenseLM, _rms, _rope

Q_BLOCK = 1024   # queries per block of attention scores


def split_bias(params):
    """(weights, biases): ``params`` without the MoE layers' router bias,
    and the bias (L, E)."""
    moe = params["decoder"]["groups"][0]["moe"]
    group = {**params["decoder"]["groups"][0],
             "moe": {k: v for k, v in moe.items() if k != "bias"}}
    weights = {**params, "decoder": {**params["decoder"],
                                     "groups": [group]}}
    return weights, moe["bias"]


class MoonlightMoE(DenseLM):
    """The reference for one configuration file and traffic mix."""

    def __init__(self, cfg: dict, traffic: dict, *,
                 precision: str = "float32"):
        super().__init__(cfg, traffic, precision=precision)
        self.nope = int(cfg["qk_nope_head_dim"])
        self.rope = int(cfg["qk_rope_head_dim"])
        self.dv = int(cfg["v_head_dim"])
        self.latent = int(cfg["kv_lora_rank"])
        self.scale = (self.nope + self.rope) ** -0.5
        self.n_routed = int(cfg["experts_routed_over"])
        self.first = int(cfg["experts_held_first"])
        self.n_held = int(cfg["n_routed_experts"])
        self.top_k = int(cfg["num_experts_per_tok"])
        self.routed_scaling = float(cfg["routed_scaling_factor"])
        self.bias_rate = float(cfg["bias_update_rate"])
        self._grads_jit = jax.jit(self._rows_grads)

    # -- model ---------------------------------------------------------
    def _attn(self, x, a):
        """Latent attention of the normed rows ``x`` (S, d)."""
        s = x.shape[0]
        h, nope, rope = self.h, self.nope, self.rope
        q = self._mm(x, a["wq"]).reshape(s, h, nope + rope)
        ckv = self._mm(x, a["wkv_a"])
        lat = _rms(ckv[:, :self.latent], a["kv_norm"]["scale"], self.eps)
        kv = self._mm(lat, a["wkv_b"]).reshape(s, h, nope + self.dv)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                                  self.theta)], -1)
        k_rope = _rope(ckv[:, None, self.latent:], self.theta)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (s, h, rope))], -1)
        v = kv[..., nope:]
        kq, vq = self.q(k), self.q(v)

        def block(args):
            qb, q0 = args
            sc = jnp.einsum("qhd,khd->hqk", self.q(qb), kq,
                            precision=HIGHEST) * self.scale
            mask = (jnp.arange(s)[None, :]
                    <= q0 + jnp.arange(qb.shape[0])[:, None])
            pr = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", self.q(pr), vq,
                              precision=HIGHEST)

        bq = min(Q_BLOCK, s)
        o = jax.lax.map(jax.checkpoint(block),
                        (q.reshape(s // bq, bq, h, nope + rope),
                         jnp.arange(0, s, bq)))
        return self._mm(o.reshape(s, h * self.dv), a["wo"])

    def _swiglu(self, x, m):
        return self._mm(jax.nn.silu(self._mm(x, m["wg"]))
                        * self._mm(x, m["wi"]), m["wo"])

    def _router(self, h, m, bias):
        """(top-k ids (S, k), the held experts' gates (S, E_held),
        each routed expert's count (E,))."""
        s = jax.nn.sigmoid(self._mm(h, m["router"]))
        top_i = jax.lax.top_k(s + bias, self.top_k)[1]
        top_s = jnp.take_along_axis(s, top_i, -1)
        top_s = top_s / (top_s.sum(-1, keepdims=True) + 1e-20) \
            * self.routed_scaling
        gates = jnp.zeros_like(s).at[
            jnp.arange(h.shape[0])[:, None], top_i].set(top_s)
        counts = jax.nn.one_hot(top_i, self.n_routed).sum((0, 1))
        return top_i, gates[:, self.first:self.first + self.n_held], counts

    def _moe(self, h, m, bias):
        _, gates, counts = self._router(h, m, bias)
        qh = self.q(h)
        a = jnp.einsum("sd,edf->sef", qh, self.q(m["wg"]), precision=HIGHEST)
        b = jnp.einsum("sd,edf->sef", qh, self.q(m["wi"]), precision=HIGHEST)
        y = jnp.einsum("sef,efd->sed", self.q(jax.nn.silu(a) * b),
                       self.q(m["wo"]), precision=HIGHEST)
        y = jnp.einsum("se,sed->sd", gates, y, precision=HIGHEST)
        return y + self._swiglu(h, m["shared"]), counts

    def _dense_layer(self, x, p):
        x = x + self._attn(_rms(x, p["ln1"]["scale"], self.eps), p["attn"])
        return x + self._swiglu(_rms(x, p["ln2"]["scale"], self.eps),
                                p["mlp"])

    def _moe_layer(self, x, inp):
        p, bias = inp
        x = x + self._attn(_rms(x, p["ln1"]["scale"], self.eps), p["attn"])
        y, counts = self._moe(_rms(x, p["ln2"]["scale"], self.eps),
                              p["moe"], bias)
        return x + y, counts

    def _row_loss(self, weights, biases, tokens, labels):
        """One row's mean cross-entropy, and each MoE layer's routed
        counts (L, E)."""
        x = self.q(weights["embed"]["table"])[tokens]
        for lead in weights["decoder"]["lead"]:
            x = jax.checkpoint(self._dense_layer)(x, lead)
        x, counts = jax.lax.scan(jax.checkpoint(self._moe_layer), x,
                                 (weights["decoder"]["groups"][0], biases))
        h = _rms(x, weights["final_norm"]["scale"], self.eps)
        logits = self._mm(h, weights["embed"]["unembed"])[:-1]
        tgt = labels[1:]
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, tgt[:, None], -1)[:, 0]
        return nll.mean(), counts

    def _rows_grads(self, weights, biases, tokens, labels):
        """Mean loss and gradient over rows (B, S), one row at a time,
        and the routed counts summed over the rows."""
        def body(acc, row):
            (loss, counts), g = jax.value_and_grad(
                self._row_loss, has_aux=True)(weights, biases, *row)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g),
                    acc[2] + counts), None
        zero = jax.tree.map(jnp.zeros_like, weights)
        (loss, g, counts), _ = jax.lax.scan(
            body, (jnp.float32(0.0), zero, jnp.zeros_like(biases)),
            (tokens, labels))
        n = tokens.shape[0]
        return loss / n, jax.tree.map(lambda a: a / n, g), counts

    def grads(self, weights, biases, batch, key, drop, *, half=False):
        """(loss, synced gradient, routed counts (L, E)) of one step.
        ``half`` (a fault) leaves out the second half of the batch's
        rows, or of its one row's tokens."""
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        if half and tokens.shape[0] > 1:
            tokens, labels = (t[: t.shape[0] // 2] for t in (tokens, labels))
        elif half:
            tokens, labels = (t[:, : t.shape[1] // 2]
                              for t in (tokens, labels))
        loss, g, counts = self._grads_jit(weights, biases, tokens, labels)
        if self.mode == "exact":
            return loss, g, counts
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
        return loss, jax.tree_util.tree_unflatten(treedef, out), counts

    def update_bias(self, biases, counts):
        return biases + self.bias_rate * jnp.sign(
            counts.mean(-1, keepdims=True) - counts)

    # -- the checked steps -------------------------------------------------
    def steps(self, init, batches, keys, drops, *, half=False):
        """As ``DenseLM.steps`` over the weights (the bias is no leaf of
        the comparison), with AdamW's moments on the host while the
        gradient is computed; also returns the router biases after the
        last step (``biases``, (L, E))."""
        weights, biases = split_bias(init())
        host = jax.tree.map(lambda a: np.zeros(a.shape, np.float32), weights)
        mu, nu = host, host
        losses, mu1 = [], None
        for t, (batch, key, drop) in enumerate(zip(batches, keys, drops)):
            loss, g, counts = self.grads(weights, biases, batch, key, drop,
                                         half=half)
            losses.append(float(loss))
            weights, mu_d, nu_d = self.adamw(weights, g, jax.device_put(mu),
                                             jax.device_put(nu), t + 1)
            biases = self.update_bias(biases, counts)
            del g
            if t == 0:
                mu1 = leaf_norms(mu_d)
            if t + 1 < len(batches):
                mu, nu = jax.device_get(mu_d), jax.device_get(nu_d)
            del mu_d, nu_d
        w0 = split_bias(init())[0]
        change = leaf_norms(jax.tree.map(jnp.subtract, weights, w0))
        return {"loss": losses, "grad_norms": mu1, "change_norms": change,
                "biases": np.asarray(biases)}
