"""Compile the device paths for a described TPU v5e chip, ahead of time.

Nothing runs: these lower and compile the Pallas kernels and the
engine's jitted window assembly at real widths for a ``v5e:2x2``
topology that is described, not attached, and check that the chip's
compiler accepts them (interpret mode on the CPU accepts kernels the
chip refuses: lane-splitting reshapes, 1-D blocks of longer 1-D
arrays, too much VMEM).  The topology is described inside a fixture so
that only the worker running this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import fwht, quantize, unbias

ROWS = 256


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the
    # persistent cache without the chip: keep them out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


KERNELS = {
    "fwht": lambda x, z, s, n: fwht.fwht_pallas(x, interpret=False),
    "fwht_signs_scale": lambda x, z, s, n: fwht.fwht_pallas(
        x, s, scale=n ** -0.5, interpret=False),
    "fwht_quantize": lambda x, z, s, n: fwht.fwht_quantize_pallas(
        x, z, interpret=False),
    "fwht_quantize_signs_scale": lambda x, z, s, n: fwht.fwht_quantize_pallas(
        x, z, s, scale=n ** -0.5, interpret=False),
    "quantize_int8": lambda x, z, s, n: quantize.quantize_int8_pallas(
        x, z, interpret=False),
    "masked_unbias": lambda x, z, s, n: unbias.masked_unbias_pallas(
        x, z[:, 0], total=4, interpret=False),
    # the train step's one-chip coded sync: a leaf's rows, bf16 or f32,
    # at the default tile (which has to fit the scoped VMEM)
    "coded_roundtrip": lambda x, z, s, n: fwht.coded_roundtrip_pallas(
        x.astype(jnp.bfloat16), s, z[0], interpret=False),
    "coded_roundtrip_f32": lambda x, z, s, n: fwht.coded_roundtrip_pallas(
        x, s, z[0], interpret=False),
}
CASES = [(name, 4096) for name in KERNELS] + [("fwht", 1024)]


@pytest.mark.parametrize("name,n", CASES)
def test_kernel_compiles_for_v5e(one_chip, name, n):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    fn = KERNELS[name]
    compiled = _compile(lambda x, z, s: fn(x, z, s, n),
                        sds((ROWS, n)), sds((ROWS, n)), sds((n,)))
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_window_assembly_compiles_for_v5e(one_chip):
    """The jitted phase-window twin at a 1024-node, 4-pod per-rail block
    (f64, as the engine feeds it)."""
    import dataclasses
    from repro.core.transport import engine_jax, topology
    from repro.core.transport.schedule import make_plan

    base = topology.hier_params(4, dci_oversubscription=8.0)
    p = dataclasses.replace(
        base, net=dataclasses.replace(base.net, n_nodes=1024),
        work=dataclasses.replace(base.work, schedule="perrail"))
    plan = make_plan(p.net, p.topo, p.work)
    steps = plan.phase_of_step.size
    ph_rows = [np.flatnonzero(plan.phase_of_step == k)
               for k in range(len(plan.phases))]
    fn = engine_jax._make_window(ph_rows, plan.budget_fracs(), 2)
    with jax.enable_x64(True):
        def sds(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.float64,
                                        sharding=one_chip)
        r = 20
        compiled = fn.lower(sds(r, steps), sds(r, steps), sds(),
                            [sds(r, steps, 3), sds(r, steps, 4)]).compile()
    assert compiled.memory_analysis() is not None
