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

from repro.kernels import fwht

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


# the train step's one-chip coded sync: a leaf's rows, bf16 or f32, at
# every rotation width a fused leaf can have and the default tile (which
# has to fit the scoped VMEM)
KERNELS = {"coded_roundtrip": jnp.bfloat16, "coded_roundtrip_f32": jnp.float32}
CASES = [(name, n) for name in KERNELS
         for n in (128, 256, 512, 1024, 2048, 4096)]


@pytest.mark.parametrize("name,n", CASES)
def test_kernel_compiles_for_v5e(one_chip, name, n):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def fn(x, signs, colscale):
        return fwht.coded_roundtrip_pallas(x.astype(KERNELS[name]), signs,
                                           colscale, interpret=False)

    compiled = _compile(fn, sds((ROWS, n)), sds((n,)), sds((n,)))
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


def test_expert_grouped_matmuls_compile_for_v5e(one_chip):
    """The MoE block's expert SwiGLU on one chip, forward and backward,
    at Granite's widths (32,768 routed rows, 40 experts, 1536 -> 512)
    with the grouped matmul's tiles (``ops.grouped_matmul``)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from repro.kernels import ops

    rows, e, d, f = 32768, 40, 1536, 512
    t = ops.GMM_TILE

    def gmm(x, w, sizes):
        return megablox.gmm(x, w, sizes, x.dtype,
                            (t, min(t, x.shape[1]), min(t, w.shape[2])),
                            None, None, False, False)

    def loss(x, wg, wi, wo, sizes):
        h = jax.nn.silu(gmm(x, wg, sizes)) * gmm(x, wi, sizes)
        return jnp.sum(gmm(h, wo, sizes).astype(jnp.float32))

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)),
                        sds(rows, d), sds(e, d, f), sds(e, d, f),
                        sds(e, f, d), sds(e, dtype=jnp.int32))
    assert compiled.as_text().count("tpu_custom_call") >= 6


def test_granite_flash_attention_compiles_for_v5e(one_chip, monkeypatch):
    """Granite's attention at the benchmark cell's shape, (2, 2048, 24,
    64) queries over 8 kv heads, forward and backward through the
    Pallas kernels (``ops.flash_attention``), with the tiles it runs."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def loss(q, k, v):
        out = ops.flash_attention(q, k, v, scale=1 / 64)
        return jnp.sum(out.astype(jnp.float32))

    def sds(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                        sds(2, 2048, 24, 64), sds(2, 2048, 8, 64),
                        sds(2, 2048, 8, 64))
    # forward, dq and dkv
    assert compiled.as_text().count("tpu_custom_call") >= 3


def _granite_on(topo, shape):
    """The granite smoke model and a (data, model) mesh of described
    chips, set as the model code's mesh."""
    import repro.configs as C
    from repro import sharding as shd
    mesh = jax.sharding.Mesh(np.array(topo.devices[:4]).reshape(shape),
                             ("data", "model"))
    shd.set_global_mesh(mesh)
    return C.get_smoke("granite-moe-3b-a800m"), mesh


def _placed(tree, shardings):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


@pytest.fixture
def on_chip_kernels(one_chip, monkeypatch):
    """Kernels compiled as the chip compiles them (Mosaic), not
    interpreted; the model code's mesh cleared afterwards."""
    from repro import sharding as shd
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    shd.set_global_mesh(None)


def test_moe_decode_compiles_on_a_model_sharded_v5e_mesh(topo,
                                                          on_chip_kernels):
    """Decode on a (data 2, model 2) mesh: one token is fewer than the
    model axis, so the MoE block takes its dropless path, in ops that
    GSPMD partitions (a Mosaic kernel cannot be partitioned)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import model as M
    from repro.serve import serve_step
    from repro.train import sharding_rules as rules

    cfg, mesh = _granite_on(topo, (2, 2))
    params = jax.eval_shape(lambda k: M.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: M.init_caches(cfg, 2, 32))
    rep = NamedSharding(mesh, P())
    compiled = serve_step.make_decode(cfg).lower(
        _placed(params, rules.param_shardings(params, mesh)),
        _placed(caches, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                     rules.cache_specs(mesh, caches))),
        {"tokens": jax.ShapeDtypeStruct((2, 1), jnp.int32, sharding=rep)},
        jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)).compile()
    assert compiled.memory_analysis() is not None


def test_moe_coded_train_step_compiles_on_a_dp_v5e_mesh(topo,
                                                        on_chip_kernels):
    """The coded train step on a data-parallel (data 4, model 1) mesh:
    the MoE block runs inside the dp-manual island, where a Mosaic
    kernel cannot be partitioned either."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.optim.adamw import OptConfig
    from repro.train import sharding_rules as rules
    from repro.train import train_step as ts

    cfg, mesh = _granite_on(topo, (4, 1))
    step = ts.make_train_step(
        cfg, mesh, OptConfig(warmup_steps=1),
        ts.CelerisConfig(mode="lossy_hadamard", n_rot=256,
                         min_coded_size=1024), donate=False)
    state = jax.eval_shape(lambda k: ts.init_state(k, cfg),
                           jax.random.PRNGKey(0))
    batch = {k: jax.ShapeDtypeStruct((8, 32), jnp.int32)
             for k in ("tokens", "labels")}
    rep = NamedSharding(mesh, P())
    compiled = step.lower(
        _placed(state, ts.state_shardings(state, mesh)),
        _placed(batch, jax.tree.map(lambda s: NamedSharding(mesh, s),
                                    rules.batch_specs(mesh, batch))),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)).compile()
    assert compiled.memory_analysis() is not None


def test_moonlight_smoke_train_step_compiles_for_v5e(one_chip,
                                                     on_chip_kernels):
    """DeepSeek-V3's block at smoke widths (latent attention at seq 2048
    through the splash kernels, 4 of 16 experts held through the grouped
    matmul, the router bias) in the one-chip coded train step, with the
    kernels as the chip compiles them; the experts 128 wide, the
    grouped matmul's least tile."""
    import dataclasses

    import repro.configs as C
    from repro.optim.adamw import OptConfig
    from repro.train import train_step as ts

    cfg = C.get_smoke("moonlight-16b-a3b")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, n_held=4, held_first=4, d_expert=128))
    step = ts.make_train_step(
        cfg, None, OptConfig(warmup_steps=1),
        ts.CelerisConfig(mode="lossy_hadamard", n_rot=256,
                         min_coded_size=1024), donate=False)
    state = jax.eval_shape(lambda k: ts.init_state(k, cfg),
                           jax.random.PRNGKey(0))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=one_chip), state)
    batch = {k: jax.ShapeDtypeStruct((1, 2048), jnp.int32,
                                     sharding=one_chip)
             for k in ("tokens", "labels")}
    compiled = step.lower(
        state, batch, jax.ShapeDtypeStruct((2,), jnp.uint32,
                                           sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)).compile()
    text = compiled.as_text()
    for kernel in ("splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"):
        assert kernel in text, kernel
    assert compiled.memory_analysis() is not None
