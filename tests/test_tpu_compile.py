"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX and compiles for a topology that is
described rather than attached, so these tests prove, on a CPU-only host,
what interpret mode cannot: that Mosaic lowers the fused CC-tick kernel at
cluster sizes (every op, every block and SMEM layout), and that whole
kernel-enabled sweep programs compile on one chip and sharded over four.
A compile that passes is not a chip run: nothing executes here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test runner's
workers all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro import netsim
from repro.core import Algo, CCParams, Feedback, MLTCPConfig, Variant, init_state
from repro.kernels import ops
from repro.netsim import engine

DT = 2e-5
ALGOS = {"reno": Algo.RENO, "cubic": Algo.CUBIC, "dcqcn": Algo.DCQCN}


@pytest.fixture(scope="module")
def topo():
    # keep the TPU compiler's logs out of the filesystem
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices), ("k",))
    return NamedSharding(mesh, PartitionSpec("k"))


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The engine asks `ops.resolve_interpret` for its kernel mode, which
    sees this process's CPU backend; steer it to the chip's choice."""
    monkeypatch.setattr(ops, "resolve_interpret",
                        lambda override=None: False if override is None
                        else override)


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding), tree)


def _proto(algo: str, variant=Variant.WI) -> MLTCPConfig:
    return MLTCPConfig(cc=CCParams(algo=int(ALGOS[algo]), variant=int(variant),
                                   tick_dt=DT, rtt=100e-6))


@pytest.mark.parametrize("n_flows", [1024, 10240])
@pytest.mark.parametrize("algo", ["reno", "cubic", "dcqcn"])
def test_cc_tick_kernel_compiles_for_v5e(one_chip, algo, n_flows):
    cfg = _proto(algo)
    n_jobs = n_flows // 8
    state = jax.eval_shape(lambda: init_state(n_flows, cfg))
    fb = Feedback(num_acks=jnp.zeros((n_flows,), jnp.float32),
                  loss=jnp.zeros((n_flows,), bool),
                  cnp=jnp.zeros((n_flows,), bool),
                  now=jnp.zeros((), jnp.float32))
    total = jnp.zeros((n_flows,), jnp.float32)
    f2j = jnp.zeros((n_flows,), jnp.int32)

    def tick(state, fb, total, f2j):
        return ops.mltcp_cc_tick(cfg, state, fb, total, flow_to_job=f2j,
                                 n_jobs=n_jobs, interpret=False)

    args = _shapes((state, fb, total, f2j), one_chip)
    compiled = jax.jit(tick).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _sweep_cfg(algo: str) -> netsim.SimConfig:
    # the paper's dumbbell deployment (§4.1: 50 Gbps, 100 us RTT), cut to
    # a short run: the tick program, not the run length, is compiled
    sockets = {"reno": 8, "cubic": 4, "dcqcn": 1}[algo]
    topo = netsim.dumbbell(6, sockets_per_job=sockets)
    jobs = netsim.JobSpec.simple([0.02] * 6, [40e6] * 6)
    return netsim.SimConfig(topo=topo, jobs=jobs, protocol=_proto(algo),
                            sim_time=0.0117, dt=DT, use_pallas_kernel=True)


def test_kernel_sweep_program_compiles_on_one_chip(one_chip,
                                                   compiled_kernels):
    cfg = _sweep_cfg("cubic")
    sweep = _shapes(netsim.make_sweep(cfg, seed=[0, 1, 2, 3]), one_chip)
    compiled = engine.lower_sweep(cfg, sweep).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_sweep_program_compiles_sharded_on_four_chips(
        four_chips, compiled_kernels):
    cfg = _sweep_cfg("reno")
    sweep = _shapes(netsim.make_sweep(cfg, seed=[0, 1, 2, 3]), four_chips)
    assert engine._sweep_mesh(sweep) is four_chips.mesh
    compiled = engine.lower_sweep(cfg, sweep).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # each chip runs its own sweep points: nothing crosses chips
    for collective in ("all-gather", "all-reduce", "all-to-all",
                       "collective-permute"):
        assert collective not in text
