"""Production mesh definitions (TPU v5e-pod-scale).

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single pod: 16x16 = 256 chips (data, model).  Multi-pod: P pods
x 256 with a leading "pod" axis; "pod" composes with "data" for
gradient reduction (DP = pod x data) and is the axis Celeris's lossy
sync cares about most (cross-pod DCI links are the slow, lossy hops).

All construction goes through :func:`repro.sharding.make_mesh`.
"""
from __future__ import annotations

import jax

from repro import sharding as shd


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return shd.make_mesh(shape, axes)


def make_scale_mesh(n_devices: int) -> jax.sharding.Mesh:
    """Simulated scale-out mesh for the lossy-collective dry runs.

    256 stays the single-pod (data, model) layout; 512/1024/... stack
    pods of 16x16 chips (pod, data, model) — the DP group the lossy
    gradient sync reduces over is pod x data = n_devices / 16.
    """
    if n_devices == 256:
        return shd.make_mesh((16, 16), ("data", "model"))
    if n_devices % 256 or n_devices < 512:
        raise ValueError(f"n_devices={n_devices} must be 256 or a "
                         "multiple of 256 >= 512")
    return shd.make_mesh((n_devices // 256, 16, 16),
                         ("pod", "data", "model"))


def make_host_mesh(shape=(4, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for container-scale integration tests."""
    return shd.make_mesh(shape, axes)


def make_pod_mesh(n_pods: int, data: int = 16, model: int = 16
                  ) -> jax.sharding.Mesh:
    """An N-pod (pod, data, model) mesh at arbitrary per-pod size.

    The production shape is ``make_pod_mesh(P)`` = P x 16 x 16 (what
    ``make_scale_mesh`` builds for 512+ devices); small ``data``/
    ``model`` values give container-scale hierarchical test meshes,
    e.g. ``make_pod_mesh(2, 2, 2)`` on 8 simulated devices.
    """
    if n_pods < 2:
        raise ValueError(f"n_pods={n_pods}: a hierarchical mesh needs >= 2 "
                         "pods (use make_production_mesh for one pod)")
    return shd.make_mesh((n_pods, data, model), ("pod", "data", "model"))


HW = {
    # TPU v5e-like hardware constants for the roofline (per chip)
    "peak_flops_bf16": 197e12,      # FLOP/s
    "hbm_bw": 819e9,                # B/s
    "ici_bw_per_link": 50e9,        # B/s per link direction
}
