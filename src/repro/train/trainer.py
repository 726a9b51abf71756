"""Host training loop: data prefetch, Celeris timeout coupling,
checkpoint/restart, straggler mitigation.

Fault-tolerance story (designed for 1000+ nodes, exercised here at
container scale):

- **checkpoint/restart**: atomic sharded checkpoints every
  ``ckpt_every`` steps (async, overlapped with compute); on start the
  trainer resumes from LATEST automatically.  Checkpoints are
  mesh-agnostic, so a job can restart elastically on a different
  topology (``Trainer(..., mesh=new_mesh)``).
- **straggler mitigation** IS the paper's mechanism: each step's
  collective is bounded by the timeout controller; the realized
  received fraction feeds back into the controller (EWMA + cluster
  median), and late data is simply dropped and recovered by the
  Hadamard pipeline.  A ``straggler_model`` maps the current timeout to
  a drop probability via the transport latency distribution.
- **data restart safety**: batches are pure functions of (seed, step,
  shard) — no data-iterator state to lose.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sharding as shd
from repro.checkpoint import checkpoint as ckpt
from repro.configs.base import ModelConfig
from repro.core import timeout as timeout_mod
from repro.data import pipeline as data_pipe
from repro.models import model as M
from repro.optim import adamw
from repro.train import train_step as ts
from repro.train import sharding_rules as rules


@dataclasses.dataclass
class StragglerModel:
    """Maps the controller's current timeout to a per-step drop rate.

    The per-chunk latency is modeled lognormal(mu, sigma) (matching the
    transport simulator's contention tails); drop = P(latency > T).
    """
    median_latency: float = 1.0       # in units of clean step time
    sigma: float = 0.6
    burst_prob: float = 0.08          # step hit by a burst
    burst_scale: float = 3.0

    def drop_rate(self, timeout: float, rng: np.random.Generator) -> float:
        med = self.median_latency
        if rng.random() < self.burst_prob:
            med *= self.burst_scale
        # P(lognormal(ln med, sigma) > timeout)
        z = (np.log(max(timeout, 1e-9)) - np.log(med)) / self.sigma
        from math import erf
        p_late = 0.5 * (1 - erf(z / np.sqrt(2)))
        return float(np.clip(p_late, 0.0, 0.5))


def _span(name: str, step: int):
    """A profiler span of the host loop, tagged with its step: all spans
    of one step share ``step``.  With no profiler active it costs about
    a microsecond."""
    return jax.profiler.TraceAnnotation(name, step=step)


class Trainer:
    def __init__(self, cfg: ModelConfig, *,
                 data_cfg: data_pipe.DataConfig,
                 opt_cfg: Optional[adamw.OptConfig] = None,
                 celeris: Optional[ts.CelerisConfig] = None,
                 mesh=None,
                 ckpt_dir: Optional[str] = None,
                 ckpt_every: int = 50,
                 seed: int = 0,
                 straggler: Optional[StragglerModel] = None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg or adamw.OptConfig()
        self.celeris = celeris or ts.CelerisConfig()
        self.mesh = mesh
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.source = data_pipe.make_source(data_cfg)
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.PRNGKey(seed)
        self.straggler = straggler or StragglerModel()
        self.controller = timeout_mod.TimeoutController(
            timeout_mod.TimeoutConfig(init_timeout=2.0, min_timeout=0.5,
                                      max_timeout=8.0))
        if mesh is not None:
            shd.set_global_mesh(mesh)
        self.step_fn = ts.make_train_step(cfg, mesh, self.opt_cfg,
                                          self.celeris)
        self.state = ts.init_state(jax.random.fold_in(self.key, 0), cfg)
        self.start_step = 0
        self._pending_ckpt = None
        if ckpt_dir is not None and ckpt.latest_step(ckpt_dir) is not None:
            self.restore()

    # ------------------------------------------------------------------
    def restore(self):
        shardings = None
        if self.mesh is not None:
            shardings = ts.state_shardings(self.state, self.mesh)
        self.state, step, extra = ckpt.restore(
            self.ckpt_dir, self.state, shardings=shardings)
        self.start_step = int(step)
        if "timeout" in (extra or {}):
            self.controller.adopt(extra["timeout"])

    def _put_batch(self, step: int) -> Dict[str, Any]:
        if self.mesh is None:
            return {k: jnp.asarray(v)
                    for k, v in self.source.global_batch(step).items()}
        dp = shd.dp_axes(self.mesh)
        n_shards = 1
        for a in dp:
            n_shards *= self.mesh.shape[a]
        host = self.source.global_batch(step, n_shards)
        specs = rules.batch_specs(self.mesh, host)
        return {k: jax.device_put(
                    v, jax.sharding.NamedSharding(self.mesh, specs[k]))
                for k, v in host.items()}

    # ------------------------------------------------------------------
    def run(self, n_steps: int,
            on_metrics: Optional[Callable[[int, Dict], None]] = None,
            simulate_fault_at: Optional[int] = None) -> Dict[str, list]:
        """Train ``n_steps`` (from the resumed position).

        ``simulate_fault_at``: raise after that step to exercise
        checkpoint/restart in tests.
        """
        history: Dict[str, list] = {"loss": [], "nll": [], "recv_frac": [],
                                    "drop_rate": [], "timeout": []}
        for step in range(self.start_step, self.start_step + n_steps):
            with _span("trainer.step", step):
                self._run_step(step, history, on_metrics)
            if simulate_fault_at is not None and step == simulate_fault_at:
                if self._pending_ckpt is not None:
                    self._pending_ckpt.result()
                raise RuntimeError(f"simulated node failure at step {step}")

        if self._pending_ckpt is not None:
            self._pending_ckpt.result()
        self.start_step += n_steps
        return history

    def _run_step(self, step: int, history: Dict[str, list],
                  on_metrics: Optional[Callable[[int, Dict], None]]):
        """One step of ``run``, each host phase under its own span."""
        with _span("trainer.batch", step):
            batch = self._put_batch(step)
        with _span("trainer.drop", step):
            if self.celeris.collective_mode().lossy or self.celeris.lossy_moe:
                # scalar for the flat modes; a (2,) [intra, cross] axis
                # vector when a HierStragglerModel drives hierarchical
                # mode (the step consumes whichever shape it was traced
                # with)
                drop = self.straggler.drop_rate(self.controller.timeout,
                                                self.rng)
            else:
                drop = 0.0
            # wall_s runs from here to the metrics read, as it always has
            t0 = time.perf_counter()
            drop_rate = jnp.asarray(drop, dtype=jnp.float32)
        with _span("trainer.dispatch", step):
            self.state, metrics = self.step_fn(
                self.state, batch, jax.random.fold_in(self.key, step),
                drop_rate)
        with _span("trainer.read_metrics", step):
            metrics = {k: float(v) for k, v in metrics.items()}
        wall = time.perf_counter() - t0

        with _span("trainer.controller", step):
            # --- Celeris software stack: bounded-window adaptation.
            # duration is the emulated step latency: stragglers that got
            # dropped no longer extend it (min with the timeout).
            emu = min(self.straggler.median_latency
                      * (1 + self.rng.lognormal(0, 0.2)),
                      self.controller.timeout)
            local = self.controller.update(emu, metrics["recv_frac"])
            # cluster coordination (median of emulated node estimates)
            agreed = timeout_mod.coordinate(
                [local * (1 + self.rng.normal(0, 0.01)) for _ in range(8)])
            self.controller.adopt(agreed)

        history["loss"].append(metrics["loss"])
        history["nll"].append(metrics["nll"])
        history["recv_frac"].append(metrics["recv_frac"])
        history["drop_rate"].append(drop)
        history["timeout"].append(self.controller.timeout)
        if on_metrics:
            on_metrics(step, {**metrics, "wall_s": wall,
                              "drop_rate": drop})

        if self.ckpt_dir and (step + 1) % self.ckpt_every == 0:
            with _span("trainer.checkpoint", step):
                if self._pending_ckpt is not None:
                    self._pending_ckpt.result()
                self._pending_ckpt = ckpt.save_async(
                    self.ckpt_dir, step + 1, self.state,
                    extra={"timeout": self.controller.timeout,
                           "arch": self.cfg.name})
