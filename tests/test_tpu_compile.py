"""Compile the main path's programs for a described TPU v5e.

on-chip-measurement §2's third rehearsal, kept as tests: each compiles one
program at its real width for a v5e that is described, not attached, so what
the chip's compiler refuses fails here at no chip time. Nothing runs: these
say nothing about results or times.

The topology is described inside a module fixture (never at import), so every
xdist worker collects the same tests and only the worker given this file
loads the TPU library.
"""

import importlib.util
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from sentinel_tpu.core.batch import (EntryBatch, ExitBatch, make_entry_batch_np,
                                     make_exit_batch_np)

REPO = Path(__file__).resolve().parent.parent


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this image
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_route(monkeypatch):
    """Trace the kernels the chip runs (the dense MXU forms), not the CPU
    backend's sort/scatter route."""
    from sentinel_tpu.ops import segment

    monkeypatch.setattr(segment, "_use_cpu_exact", lambda: False)


def _shapes(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        jnp.shape(x), jnp.result_type(x), sharding=sharding), tree)


@pytest.fixture(scope="module")
def headline():
    """The smoke's engine: capacity 32768, 10k resources, headline mix."""
    from sentinel_tpu.core.engine import SentinelEngine

    cs = _chip_smoke()
    eng = SentinelEngine(capacity=cs.CAPACITY)
    flow, degrade, param = cs.headline_rules(cs.N_RES)
    eng.flow_rules.load_rules(flow)
    eng.degrade_rules.load_rules(degrade)
    eng.param_rules.load_rules(param)
    eng._ensure_compiled()
    yield cs, eng
    eng.close()


def test_prefix_pallas_compiles(one_chip):
    from sentinel_tpu.ops.pallas_prefix import prefix_pallas

    ids = jax.ShapeDtypeStruct((8192,), jnp.int32, sharding=one_chip)
    vals = jax.ShapeDtypeStruct((8192, 2), jnp.float32, sharding=one_chip)
    lowered = jax.jit(prefix_pallas).lower(ids, vals)
    assert "tpu_custom_call" in lowered.as_text()
    lowered.compile()


@pytest.mark.parametrize("direction", ["entry", "exit"])
def test_headline_step_compiles(direction, headline, one_chip, tpu_route):
    cs, eng = headline
    batch = (EntryBatch(**make_entry_batch_np(cs.WIDTH)) if direction == "entry"
             else ExitBatch(**make_exit_batch_np(cs.WIDTH)))
    args = (_shapes(eng._state, one_chip), _shapes(eng._rules, one_chip),
            _shapes(batch, one_chip),
            jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip))
    if direction == "entry":
        lowered = eng._entry_jit.lower(
            *args, occupy_timeout_ms=eng._occupy_timeout_ms,
            shadow_rules=None, canary_bps=None, canary_salt=None)
    else:
        lowered = eng._exit_jit.lower(*args, shadow_rules=None)
    mem = lowered.compile().memory_analysis()
    if mem is not None:  # 16 GB of HBM on one v5e chip
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_token_acquire_step_compiles(one_chip):
    import sentinel_tpu as st
    from sentinel_tpu.cluster.rules import ClusterFlowRuleManager
    from sentinel_tpu.cluster.token_service import DefaultTokenService

    rules = ClusterFlowRuleManager()
    rules.load_rules("default", [
        st.FlowRule(resource=f"clus{i}", count=3, cluster_mode=True,
                    cluster_config={"flowId": 1000 + i, "thresholdType": 1})
        for i in range(64)])
    svc = DefaultTokenService(rules)
    svc._ensure_compiled()
    n = 256  # the first padded rung of cluster/server.py's width ladder
    vec = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one_chip)
    svc._acquire_jit.lower(
        _shapes(svc._state, one_chip), _shapes(svc._rt, one_chip),
        _shapes(svc._conn_tensor(), one_chip), vec(jnp.int32),
        vec(jnp.int32), vec(jnp.bool_),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip),
        max_occupy_ratio=svc.max_occupy_ratio).compile()


def test_pod_entry_step_compiles_on_2x2(topo, tpu_route):
    from sentinel_tpu.parallel import cluster as PC

    cs = _chip_smoke()
    devices = list(topo.devices)[:cs.POD_DEVICES]
    rows, pack, one = cs._pod_pack(cs._pod_rules(cs.POD_RES, ("pod",)),
                                   cs.POD_CAPACITY, cs.T0)
    mesh = Mesh(np.asarray(devices), (PC.AXIS,))
    entry, _ = PC.make_pod_steps(mesh, cluster_param=False)
    sharded = NamedSharding(mesh, P(PC.AXIS))
    batch = EntryBatch(**make_entry_batch_np(len(devices) * cs.POD_PER_DEV))
    lowered = jax.jit(entry, donate_argnums=(0,)).lower(
        _shapes(PC.make_pod_state(len(devices), one), sharded),
        _shapes(pack, NamedSharding(mesh, P())), _shapes(batch, sharded),
        jax.ShapeDtypeStruct((), jnp.int64, sharding=NamedSharding(mesh, P())))
    text = lowered.compile().as_text()
    assert "all-reduce" in text
