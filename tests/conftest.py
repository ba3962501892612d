"""Test config: run JAX on a virtual 8-device CPU topology.

Per the build environment contract, tests run on CPU with
``xla_force_host_platform_device_count=8`` so multi-chip sharding logic is
exercised without TPU hardware; the bench runs on the real chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

# Pin the platform through config too, so a host whose environment names
# its accelerator still runs the tests on the CPU (must happen before the
# backend initializes).
jax.config.update("jax_platforms", "cpu")

import pytest

import sentinel_tpu as st
from sentinel_tpu.utils import time_util


@pytest.fixture()
def frozen_time():
    """Pin the clock to a deterministic epoch; yield the controller."""
    time_util.freeze_time(1_700_000_000_000)
    yield time_util
    time_util.unfreeze_time()


@pytest.fixture()
def engine(frozen_time):
    """Fresh default engine with a pinned clock and a clean context."""
    from sentinel_tpu.core.context import replace_context

    replace_context(None)
    eng = st.reset(capacity=512)
    yield eng
    replace_context(None)
    st.reset(capacity=512)


# -- quick tier ---------------------------------------------------------------
# `pytest -m quick` (< ~2 min): one representative per engine path, chosen to
# cover the regression classes that shipped broken HEADs in rounds 2-3
# (engine/lease/checkpoint/retune interactions) plus a smoke per subsystem.
# Run it before EVERY commit; the full suite before the round's final one.

QUICK = (
    "test_flow.py::test_flow_qps_demo_golden",
    "test_flow.py::test_rule_swap_wholesale",
    "test_flow.py::TestWindowGeometry::test_retune_resets_instant_window_and_keeps_quota_rate",
    "test_lease.py::test_lease_admission_is_exact",
    "test_lease.py::test_lease_stats_reach_the_device",
    "test_lease.py::test_rule_push_does_not_regrant_spent_quota",
    "test_lease.py::test_retune_with_compiled_leased_engine",
    "test_checkpoint.py::test_stats_survive_restart",
    "test_checkpoint.py::test_restore_after_rule_load_seeds_lease_mirror",
    "test_checkpoint_scenarios.py::test_leased_traffic_checkpoint_crash_restore",
    "test_occupy.py::test_prioritized_borrows_once_bucket_expires",
    "test_degrade.py::test_exception_ratio_opens_and_recovers",
    "test_window.py::test_rotation_drops_old_buckets",
    "test_cluster.py::test_codec_flow_round_trip",
    "test_transport.py::test_get_set_rules_round_trip",
    "test_dashboard.py::test_discovery_from_heartbeats",
    "test_transport.py::test_gateway_rules_and_api_definitions_commands",
    "test_tlv_fixtures.py",     # whole file: 2.5s
    "test_redis_datasource.py",  # whole file: 2.5s
    # Differential-fuzz representatives (the FULL fuzz file has grown to
    # ~15 scenarios / several minutes — r5 added mixed-count, hot-key,
    # system, geometry, and warm-up regimes; the full set runs in the
    # suite, the quick tier keeps ONE seed of the core oracle scenario,
    # the trace regression, and ONE mixed-count pin — exact parametrized
    # ids, or the prefix match would drag in every seed including the
    # 150-step soak):
    "test_step_fuzz.py::test_fuzz_step_matches_serial_oracle[11-40]",
    "test_step_fuzz.py::test_width_zero_batches_trace_and_preserve_state",
    "test_step_fuzz.py::test_fuzz_mixed_acquire_counts[13-50]",
    "test_token_service_fuzz.py",  # token-service fuzz vs oracle: ~2s
)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "quick: pre-commit smoke tier (pytest -m quick)")


def pytest_collection_modifyitems(config, items):
    for item in items:
        rel = item.nodeid.split("tests/")[-1]
        for q in QUICK:
            if rel == q or rel.startswith(q + "::") or rel.startswith(q + "["):
                item.add_marker(pytest.mark.quick)
                break
