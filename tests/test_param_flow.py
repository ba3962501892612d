"""Hot-parameter flow tests.

Modeled on the reference's ``ParamFlowCheckerTest`` / demo behavior
(SURVEY.md §2.2): per-value QPS token buckets with burst, per-value
exception items, THREAD-grade concurrency, throttle behavior, and the
bounded-key-space eviction semantics.
"""

import pytest

import sentinel_tpu as st
from sentinel_tpu.core import constants as C


def hits(resource, value, n, **kw):
    """Attempt n entries with one hot param; return pass count."""
    passed = 0
    for _ in range(n):
        h = st.entry_ok(resource, args=(value,), **kw)
        if h is not None:
            passed += 1
            h.exit()
    return passed


class TestParamFlowQps:
    def test_per_value_isolation(self, engine):
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=0, count=3)])
        assert hits("hot", "keyA", 5) == 3
        # A different value has its own bucket.
        assert hits("hot", "keyB", 5) == 3

    def test_refill_after_duration(self, engine, frozen_time):
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=0, count=2)])
        assert hits("hot", 42, 4) == 2
        frozen_time.advance_time(1100)
        assert hits("hot", 42, 4) == 2

    def test_burst_capacity(self, engine, frozen_time):
        st.load_param_flow_rules([
            st.ParamFlowRule("hot", param_idx=0, count=2, burst_count=3)
        ])
        # Full bucket = count + burst on first touch.
        assert hits("hot", "k", 10) == 5
        # After one idle window only `count` tokens drip back in.
        frozen_time.advance_time(1100)
        assert hits("hot", "k", 10) == 2

    def test_duration_in_sec(self, engine, frozen_time):
        st.load_param_flow_rules([
            st.ParamFlowRule("hot", param_idx=0, count=2, duration_in_sec=2)
        ])
        assert hits("hot", "k", 4) == 2
        frozen_time.advance_time(1100)  # only half the window elapsed
        assert hits("hot", "k", 4) == 0
        frozen_time.advance_time(1000)
        assert hits("hot", "k", 4) == 2

    def test_item_exception_overrides(self, engine):
        st.load_param_flow_rules([
            st.ParamFlowRule(
                "hot", param_idx=0, count=1,
                items=[st.ParamFlowItem("vip", 5)],
            )
        ])
        assert hits("hot", "vip", 8) == 5
        assert hits("hot", "pleb", 8) == 1

    def test_zero_threshold_blocks_all(self, engine):
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=0, count=0)])
        assert hits("hot", "k", 3) == 0

    def test_param_idx_selects_argument(self, engine):
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=1, count=1)])
        # Same arg0, different arg1: separate buckets.
        assert st.entry_ok("hot", args=("x", "a")) is not None
        assert st.entry_ok("hot", args=("x", "b")) is not None
        assert st.entry_ok("hot", args=("y", "a")) is None

    def test_missing_param_passes(self, engine):
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=2, count=1)])
        # Entry carries no index-2 argument: the rule does not apply.
        passed = 0
        for _ in range(5):
            h = st.entry_ok("hot", args=("only0",))
            if h:
                passed += 1
                h.exit()
        assert passed == 5

    def test_count_acquires_tokens(self, engine):
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=0, count=5)])
        h = st.entry_ok("hot", count=4, args=("k",))
        assert h is not None
        h.exit()
        assert st.entry_ok("hot", count=4, args=("k",)) is None
        h = st.entry_ok("hot", count=1, args=("k",))
        assert h is not None
        h.exit()


class TestParamFlowThread:
    def test_concurrency_per_value(self, engine):
        st.load_param_flow_rules([
            st.ParamFlowRule("hot", param_idx=0, count=2,
                             grade=C.PARAM_FLOW_GRADE_THREAD)
        ])
        e1 = st.entry("hot", args=("k",))
        e2 = st.entry("hot", args=("k",))
        assert st.entry_ok("hot", args=("k",)) is None
        # Another value is free.
        e3 = st.entry("hot", args=("other",))
        e3.exit()
        e1.exit()
        # Slot released.
        e4 = st.entry("hot", args=("k",))
        e4.exit()
        e2.exit()


class TestParamFlowThrottle:
    def test_paced_admission_with_wait(self, engine, frozen_time):
        st.load_param_flow_rules([
            st.ParamFlowRule(
                "hot", param_idx=0, count=10,  # 100ms per token
                control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                max_queueing_time_ms=500,
            )
        ])
        # First passes immediately; next few pace out until the 500ms queue
        # cap rejects.
        got = [st.entry_ok("hot", args=("k",)) for _ in range(8)]
        passed = [h for h in got if h is not None]
        assert 5 <= len(passed) <= 6  # 500ms cap / 100ms cost (+head slack)
        for h in passed:
            h.exit()


class TestEviction:
    def test_distinct_values_beyond_table_conflate_bounded(self, engine):
        # Keys are hashed into a fixed table; a *new* key evicts its slot
        # and starts a fresh bucket (tensor analog of the reference's LRU
        # cap). Protection per hot value still holds.
        st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=0, count=1)])
        for i in range(50):
            h = st.entry_ok("hot", args=(f"key{i}",))
            assert h is not None
            h.exit()
        # The hot key within its bucket is still limited.
        assert hits("hot", "key0", 3) <= 1


def test_negative_burst_rule_is_dropped(engine):
    """Reference parity: malformed rules are discarded, traffic passes."""
    st.load_param_flow_rules([
        st.ParamFlowRule("hot", param_idx=0, count=5, burst_count=-10)
    ])
    for _ in range(3):
        h = st.entry_ok("hot", args=("k",))
        assert h is not None
        h.exit()


def test_empty_family_compiles_zero_slots_with_ratchet_floor():
    """Rule-free families compile to ZERO slots (their per-slot loop
    vanishes at trace time — a no-rules step measured ~4x cheaper), and
    ``min_slots`` restores the wider shape so the engine's ratchet can
    keep rule pushes retrace-free after a family's first use."""
    from sentinel_tpu.core.registry import NodeRegistry
    from sentinel_tpu.models import param_flow as P

    reg = NodeRegistry(64)
    assert P.compile_param_rules([], reg, 64).slots == 0
    pt = P.compile_param_rules(
        [st.ParamFlowRule("r", param_idx=0, count=5)], reg, 64)
    assert pt.slots == 1
    # The ratchet case: rules dropped back to zero keeps the shape.
    assert P.compile_param_rules([], reg, 64, min_slots=1).slots == 1


def _device_path(engine):
    """Single-param rules are lease-eligible (core/lease.py), so these
    compile-ratchet tests turn the lease off to pin the device path they
    measure (with it on they passed only when an earlier test in the same
    worker had left a device dispatch behind)."""
    engine.lease_enabled = False
    engine._rebuild_leases()


def test_engine_slot_floor_ratchets_across_pushes(engine, frozen_time):
    """Pushing a family's first rule widens its slot floor permanently:
    clearing the rules later compiles the SAME tensor shape, so the
    fused step is not retraced by the push cycle (the round-4
    'rule pushes don't recompile' guarantee, kept under zero-slot
    compiles of empty families)."""
    _device_path(engine)
    assert engine._slot_floor["param"] == 0
    st.load_param_flow_rules([st.ParamFlowRule("hot", param_idx=0, count=2)])
    h = st.entry_ok("hot", args=("k",))  # forces compile + dispatch
    if h:
        h.exit()
    assert engine._slot_floor["param"] == 1
    shape_with_rules = tuple(engine._rules.param.rules_by_row.shape)
    st.load_param_flow_rules([])  # clear the family
    h = st.entry_ok("hot", args=("k",))
    if h:
        h.exit()
    assert engine._slot_floor["param"] == 1
    assert tuple(engine._rules.param.rules_by_row.shape) == shape_with_rules


def test_reset_slot_floor_shrinks_after_transient_burst(engine, frozen_time):
    """The ratchet's escape hatch (r4 advisory): after a transient burst
    widens a family's loop, ``reset_slot_floor()`` (the ``resetSlotFloor``
    ops command) shrinks the compiled shapes back to what current rules
    need, at the documented cost of one retrace."""
    _device_path(engine)
    st.load_param_flow_rules([
        st.ParamFlowRule("hot", param_idx=0, count=2, duration_in_sec=i + 1)
        for i in range(4)  # 4 rules on ONE resource -> 4 slots
    ])
    h = st.entry_ok("hot", args=("k",))
    if h:
        h.exit()
    assert engine._slot_floor["param"] == 4
    st.load_param_flow_rules(
        [st.ParamFlowRule("hot", param_idx=0, count=2)])  # burst over
    h = st.entry_ok("hot", args=("k",))
    if h:
        h.exit()
    assert engine._slot_floor["param"] == 4  # ratchet held the wide shape
    wide = tuple(engine._rules.param.rules_by_row.shape)

    old = engine.reset_slot_floor()
    assert old["param"] == 4
    h = st.entry_ok("hot", args=("k",))  # forces the shrink recompile
    if h:
        h.exit()
    assert engine._slot_floor["param"] == 1
    narrow = tuple(engine._rules.param.rules_by_row.shape)
    assert narrow != wide and narrow[-1] == 1

    # still admits correctly after the shrink
    blocked = 0
    for _ in range(6):
        h = st.entry_ok("hot", args=("k",))
        if h:
            h.exit()
        else:
            blocked += 1
    assert blocked > 0  # count=2 rule still enforced post-reset


def test_reset_slot_floor_command(engine, frozen_time):
    import json

    from sentinel_tpu.transport.command_center import CommandRequest
    from sentinel_tpu.transport.handlers import cmd_reset_slot_floor

    st.load_param_flow_rules([
        st.ParamFlowRule("hot", param_idx=0, count=2, duration_in_sec=i + 1)
        for i in range(3)
    ])
    h = st.entry_ok("hot", args=("k",))
    if h:
        h.exit()
    st.load_param_flow_rules([])
    resp = cmd_reset_slot_floor(CommandRequest(engine=engine))
    assert resp.success
    body = json.loads(resp.result)
    assert body["previousFloor"]["param"] == 3
    assert body["floor"]["param"] == 0


def _jit_cache_size(jitted):
    """jax-private trace-cache probe; skip rather than fail if a jax
    bump renames it (the ratchet behavior itself is version-agnostic)."""
    probe = getattr(jitted, "_cache_size", None)
    if probe is None:
        pytest.skip("jax _cache_size API unavailable in this version")
    return probe()


def test_rule_push_cycle_never_retraces_after_first_use(engine, frozen_time):
    """The compile-count guarantee behind the ratchet: after a family's
    first use is compiled, pushing new rule VALUES, clearing the family,
    and re-pushing must all hit the same jit specialization — the
    entry jit's trace-cache size stays at 1."""
    _device_path(engine)
    st.load_flow_rules([st.FlowRule(resource="api", count=100)])
    st.load_param_flow_rules([st.ParamFlowRule("api", param_idx=0, count=50)])
    h = st.entry_ok("api", args=("k",))
    if h:
        h.exit()
    jit0 = engine._entry_jit  # identity-pin: a rebuilt jit would reset
    assert _jit_cache_size(jit0) == 1
    # Value-only push, family clear, and re-push: no new specialization.
    st.load_param_flow_rules([st.ParamFlowRule("api", param_idx=0, count=9)])
    h = st.entry_ok("api", args=("k",))
    if h:
        h.exit()
    st.load_param_flow_rules([])
    h = st.entry_ok("api", args=("k",))
    if h:
        h.exit()
    st.load_param_flow_rules([st.ParamFlowRule("api", param_idx=0, count=2)])
    h = st.entry_ok("api", args=("k",))
    if h:
        h.exit()
    assert engine._entry_jit is jit0  # not silently rebuilt per push
    assert _jit_cache_size(jit0) == 1
