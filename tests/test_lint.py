"""Syntax-rot and lint gates (CI/tooling tier-1 smoke).

Most datasource connector modules import lazily (their wire deps are
optional extras), so a syntax error in one can sit unnoticed until a
production config first selects it. ``compileall`` forces every module
through the parser/compiler on every tier-1 run. The ruff gate runs the
repo's pyproject config when a ruff binary is available (the container
image does not ship one; CI images that do get the full lint).
"""

import py_compile
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def test_compileall_package():
    proc = subprocess.run(
        [sys.executable, "-m", "compileall", "-q", "-f",
         str(REPO / "sentinel_tpu")],
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_compile_driver_entry_points():
    for name in ("__graft_entry__.py", "bench.py", "chip_smoke.py"):
        py_compile.compile(str(REPO / name), doraise=True)


def test_no_bare_print_in_package():
    """Telemetry goes through the record log / telemetry subsystem, not
    stdout: a bare ``print(`` in library code is invisible to operators
    scraping /metrics and pollutes embedding hosts' stdout. CLI entry
    points (``__main__.py``) are the one legitimate stdout surface."""
    import re

    pattern = re.compile(r"(?<![\w.])print\(")
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        if path.name == "__main__.py":
            continue  # CLI surface: user-facing stdout is the point
        in_doc = False
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            stripped = line.strip()
            # crude but sufficient docstring/comment skip for this gate
            if stripped.count('"""') % 2 == 1 or stripped.count("'''") % 2 == 1:
                in_doc = not in_doc
                continue
            if in_doc or stripped.startswith("#"):
                continue
            code = line.split("#", 1)[0]
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "bare print( in library code (route through record_log): "
        + ", ".join(offenders))


def _code_lines(path: Path):
    """(lineno, code) pairs with comments and (crudely) docstrings
    stripped — the same skip logic the bare-print gate uses."""
    in_doc = False
    for lineno, line in enumerate(path.read_text().splitlines(), 1):
        stripped = line.strip()
        if stripped.count('"""') % 2 == 1 or stripped.count("'''") % 2 == 1:
            in_doc = not in_doc
            continue
        if in_doc or stripped.startswith("#"):
            continue
        yield lineno, line.split("#", 1)[0]


def test_no_wall_clock_in_device_ops():
    """Device code (sentinel_tpu/ops/) must take ``now_ms`` as an
    argument: kernels cannot call clocks under jit, and an ambient
    ``time.time()``/``datetime.now()`` read in ops code either leaks a
    trace-time constant into the compiled program (frozen forever) or
    silently diverges host/device clocks. The module docstring of
    ops/window.py states the contract; this pins it."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(")
    offenders = []
    for path in sorted((REPO / "sentinel_tpu" / "ops").rglob("*.py")):
        for lineno, code in _code_lines(path):
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in device ops code (pass now_ms instead): "
        + ", ".join(offenders))


def test_no_wall_clock_in_simulator():
    """Replay must be deterministic BY CONSTRUCTION: the simulator
    (sentinel_tpu/simulator/) drives everything off the injected
    program clock, so an ambient wall-clock read anywhere in the
    package would silently couple a replay to the host's clock. Same
    rule (and skip logic) as the device-ops gate above; the one
    sanctioned wall read is ``time.perf_counter`` — it MEASURES replay
    speed (the ``sim_replay`` bench metric), it never drives replay."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    offenders = []
    for path in sorted((REPO / "sentinel_tpu" / "simulator").rglob("*.py")):
        for lineno, code in _code_lines(path):
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in simulator code (drive everything off the "
        "SimClock; perf_counter only for speed measurement): "
        + ", ".join(offenders))


def test_sim_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.sim.*`` config key must (a) be defined and
    read ONLY in core/config.py — the rest of the package goes through
    the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md "Trace capture & replay", so the runbook can
    never silently drift from the knobs the code actually reads (same
    rule shape as the cluster-HA / overload / pipeline gates)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.sim\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.sim.* literals outside core/config.py "
        "(use the SentinelConfig sim_* accessors): " + ", ".join(offenders))
    assert keys, "no sim config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "sim config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_fault_points_documented_and_wired():
    """Every name in ``resilience.faults.FAULT_POINTS`` must (a) appear
    in docs/OPERATIONS.md (the fault-point table operators arm in chaos
    drills) and (b) have at least one ``fire(``/``mutate(`` call site in
    the package — a fault point with no call site rots silently: tests
    arm it, nothing ever fires, and the drill asserts nothing."""
    import re
    import sys

    sys.path.insert(0, str(REPO))
    from sentinel_tpu.resilience.faults import FAULT_POINTS

    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(p for p in FAULT_POINTS if p not in ops)
    assert not undocumented, (
        "fault points missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))
    package_text = "\n".join(
        path.read_text()
        for path in sorted((REPO / "sentinel_tpu").rglob("*.py")))
    dead = []
    for point in FAULT_POINTS:
        pat = re.compile(
            r"(?:fire|mutate)(?:_targeted)?\(\s*[\"']"
            + re.escape(point) + r"[\"']")
        if not pat.search(package_text):
            dead.append(point)
    assert not dead, (
        "fault points with no fire(/mutate( call site (dead seams): "
        + ", ".join(dead))


def test_chaos_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.chaos.*`` config key must (a) be defined
    and read ONLY in core/config.py — the rest of the package goes
    through the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md "Chaos campaign", so the runbook can never
    silently drift from the knobs the code actually reads (same rule
    shape as the cluster-HA / overload / sim gates)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.chaos\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.chaos.* literals outside core/config.py "
        "(use the SentinelConfig chaos_* accessors): "
        + ", ".join(offenders))
    assert keys, "no chaos config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "chaos config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_no_wall_clock_in_chaos():
    """Chaos campaigns must be deterministic BY CONSTRUCTION: everything
    in sentinel_tpu/chaos/ runs on the engine timebase (the SimClock the
    campaign advances), so an ambient wall-clock read anywhere in the
    package would couple an episode's verdict stream to the host clock
    and void the seed-replay contract. Same rule (and skip logic) as the
    simulator/journal gates; ``time.perf_counter`` stays sanctioned — it
    MEASURES episodes/s, it never drives an episode."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    offenders = []
    for path in sorted((REPO / "sentinel_tpu" / "chaos").rglob("*.py")):
        for lineno, code in _code_lines(path):
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in chaos code (ride the campaign SimClock; "
        "perf_counter only for speed measurement): " + ", ".join(offenders))


def test_exported_metric_names_registered_exactly_once():
    """Every ``sentinel_tpu_*`` metric family must be declared exactly
    once across the telemetry exporters — a name declared twice renders
    duplicate ``# TYPE`` lines, which strict OpenMetrics parsers reject
    (and which silently splits one series across two declarations)."""
    import re

    # Two declaration sites: builder calls (b.family/b.counter) and the
    # _EVENT_FAMILIES-style tuple tables whose first element is the name.
    decl = re.compile(
        r"(?:b\.(?:family|counter)\(\s*|^\s*\()\"(sentinel_tpu_[a-z0-9_]+)\"")
    seen = {}
    dupes = []
    for path in sorted((REPO / "sentinel_tpu" / "telemetry").rglob("*.py")):
        for lineno, code in _code_lines(path):
            for name in decl.findall(code):
                where = f"{path.relative_to(REPO)}:{lineno}"
                if name in seen:
                    dupes.append(f"{name} ({seen[name]} and {where})")
                else:
                    seen[name] = where
    assert seen, "no exported metric declarations found (regex rot?)"
    assert not dupes, "metric family declared twice: " + ", ".join(dupes)
    # and the declarations must actually cover the families the live
    # exposition renders (catches emission helpers bypassing family())
    assert "sentinel_tpu_pass" in seen
    assert "sentinel_tpu_second_pass" in seen
    # the SLO engine's families (ISSUE 7): every sentinel_tpu_slo_* /
    # sentinel_tpu_alert_* family the exposition renders is declared
    # exactly once (the dupe gate above), and the load-bearing ones exist
    for name in ("sentinel_tpu_slo_burn_rate",
                 "sentinel_tpu_slo_health_score",
                 "sentinel_tpu_slo_instance_health",
                 "sentinel_tpu_alert_active",
                 "sentinel_tpu_alert_fired",
                 "sentinel_tpu_step_duration_ms"):
        assert name in seen, f"{name} not declared in the exporters"
    # adaptive-limiting families (ISSUE 10): declared exactly once (the
    # dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_adaptive_enabled",
                 "sentinel_tpu_adaptive_frozen",
                 "sentinel_tpu_adaptive_proposals",
                 "sentinel_tpu_adaptive_promotions",
                 "sentinel_tpu_adaptive_aborts",
                 "sentinel_tpu_adaptive_clamped",
                 "sentinel_tpu_adaptive_target_delta"):
        assert name in seen, f"{name} not declared in the exporters"
    # wire-path families (ISSUE 11): declared exactly once (the dupe
    # gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_wire_connections",
                 "sentinel_tpu_wire_coalesced_batch",
                 "sentinel_tpu_wire_rtt_ms",
                 "sentinel_tpu_wire_outbuf_shed"):
        assert name in seen, f"{name} not declared in the exporters"
    # sharded-cluster families (ISSUE 12): declared exactly once (the
    # dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_shard_slices_owned",
                 "sentinel_tpu_shard_slice_epoch",
                 "sentinel_tpu_shard_wrong_slice_rejected",
                 "sentinel_tpu_shard_handoffs",
                 "sentinel_tpu_shard_degraded_slices"):
        assert name in seen, f"{name} not declared in the exporters"
    # trace-replay simulator families (ISSUE 13): declared exactly once
    # (the dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_sim_lab_runs",
                 "sentinel_tpu_sim_replayed_seconds",
                 "sentinel_tpu_sim_replay_rate",
                 "sentinel_tpu_sim_policy_score"):
        assert name in seen, f"{name} not declared in the exporters"
    # fleet observability families (ISSUE 14): declared exactly once
    # (the dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_journal_last_seq",
                 "sentinel_tpu_journal_records",
                 "sentinel_tpu_journal_dropped_partial",
                 "sentinel_tpu_journal_rotations",
                 "sentinel_tpu_fleet_leaders",
                 "sentinel_tpu_fleet_stale_leaders",
                 "sentinel_tpu_fleet_health",
                 "sentinel_tpu_fleet_skew_ms",
                 "sentinel_tpu_fleet_polls"):
        assert name in seen, f"{name} not declared in the exporters"
    # chaos-campaign families (ISSUE 15): declared exactly once (the
    # dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_chaos_episodes",
                 "sentinel_tpu_chaos_violations",
                 "sentinel_tpu_chaos_faults_fired",
                 "sentinel_tpu_chaos_shrink_steps"):
        assert name in seen, f"{name} not declared in the exporters"
    # governed-rebalancer families (ISSUE 16): declared exactly once
    # (the dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_rebalance_plans",
                 "sentinel_tpu_rebalance_applies",
                 "sentinel_tpu_rebalance_rollbacks",
                 "sentinel_tpu_rebalance_vetoes",
                 "sentinel_tpu_rebalance_slices_moved",
                 "sentinel_tpu_rebalance_frozen",
                 "sentinel_tpu_rebalance_skew"):
        assert name in seen, f"{name} not declared in the exporters"
    # LLM-admission families (ISSUE 17): declared exactly once (the
    # dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_llm_rules",
                 "sentinel_tpu_llm_streams_active",
                 "sentinel_tpu_llm_streams_opened",
                 "sentinel_tpu_llm_streams_blocked",
                 "sentinel_tpu_llm_streams_aborted",
                 "sentinel_tpu_llm_streams_evicted",
                 "sentinel_tpu_llm_tokens_debited",
                 "sentinel_tpu_llm_tokens_streamed",
                 "sentinel_tpu_llm_tokens_released",
                 "sentinel_tpu_llm_reservation_outstanding",
                 "sentinel_tpu_llm_credit_tokens"):
        assert name in seen, f"{name} not declared in the exporters"
    # latency-waterfall families (ISSUE 18): declared exactly once (the
    # dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_waterfall_stage_ms",
                 "sentinel_tpu_waterfall_rtt_ms",
                 "sentinel_tpu_waterfall_stage_concurrency",
                 "sentinel_tpu_waterfall_device_utilization",
                 "sentinel_tpu_waterfall_coalesce_efficiency",
                 "sentinel_tpu_waterfall_seconds",
                 "sentinel_tpu_waterfall_exemplars",
                 "sentinel_tpu_waterfall_budget_ms"):
        assert name in seen, f"{name} not declared in the exporters"
    # namespace-telescope families (ISSUE 19): declared exactly once
    # (the dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_population_enabled",
                 "sentinel_tpu_population_observed",
                 "sentinel_tpu_population_distinct",
                 "sentinel_tpu_population_window_distinct",
                 "sentinel_tpu_population_ss_floor",
                 "sentinel_tpu_population_hot_mass",
                 "sentinel_tpu_population_churn_entered",
                 "sentinel_tpu_population_churn_exited",
                 "sentinel_tpu_population_cardinality_z",
                 "sentinel_tpu_population_cardinality_alarm",
                 "sentinel_tpu_population_fold_ms"):
        assert name in seen, f"{name} not declared in the exporters"
    # slot-table admission families (ISSUE 20): declared exactly once
    # (the dupe gate above) and every family the ISSUE names exists
    for name in ("sentinel_tpu_slots_budget",
                 "sentinel_tpu_slots_hot",
                 "sentinel_tpu_slots_free",
                 "sentinel_tpu_slots_pinned",
                 "sentinel_tpu_slots_frozen",
                 "sentinel_tpu_slots_admits",
                 "sentinel_tpu_slots_evictions",
                 "sentinel_tpu_slots_rehydrations",
                 "sentinel_tpu_slots_rehydrations_cold",
                 "sentinel_tpu_slots_steals",
                 "sentinel_tpu_slots_storms",
                 "sentinel_tpu_slots_hot_hits",
                 "sentinel_tpu_slots_cold_pass",
                 "sentinel_tpu_slots_cold_block",
                 "sentinel_tpu_slots_cold_unenforced",
                 "sentinel_tpu_slots_spill_torn",
                 "sentinel_tpu_slots_spill_dropped",
                 "sentinel_tpu_slots_spill_records",
                 "sentinel_tpu_slots_late_exits",
                 "sentinel_tpu_slots_pin_overflow",
                 "sentinel_tpu_slots_hit_rate",
                 "sentinel_tpu_registry_overflow"):
        assert name in seen, f"{name} not declared in the exporters"
    # pipelined-admission families (ISSUE 8): declared exactly once (the
    # dupe gate above) and the load-bearing ones exist
    for name in ("sentinel_tpu_pipeline_active",
                 "sentinel_tpu_pipeline_inflight_depth",
                 "sentinel_tpu_pipeline_inflight_depth_max",
                 "sentinel_tpu_pipeline_cycles",
                 "sentinel_tpu_pipeline_entries",
                 "sentinel_tpu_pipeline_fail_open_cycles",
                 "sentinel_tpu_pipeline_queue_wait_ms",
                 "sentinel_tpu_pipeline_device_wait_ms"):
        assert name in seen, f"{name} not declared in the exporters"


def test_cluster_ha_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.cluster.ha.*`` config key must (a) be defined
    and read ONLY in core/config.py — the rest of the package goes
    through the ``SentinelConfig`` accessors, so defaults/validation
    live in exactly one place — and (b) appear in docs/OPERATIONS.md,
    so the failover-drill runbook can never silently drift from the
    knobs the code actually reads."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.cluster\.ha\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.cluster.ha.* literals outside core/config.py "
        "(use the SentinelConfig cluster_ha_* accessors): "
        + ", ".join(offenders))
    assert keys, "no cluster HA config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "cluster HA config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_shard_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.cluster.shard.*`` config key must (a) be
    defined and read ONLY in core/config.py — the rest of the package
    goes through the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md, so the sharded-cluster runbook can never
    silently drift from the knobs the code actually reads (same rule
    shape as the cluster-HA gate above)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.cluster\.shard\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.cluster.shard.* literals outside core/config.py "
        "(use the SentinelConfig cluster_shard_* accessors): "
        + ", ".join(offenders))
    assert keys, "no cluster shard config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "cluster shard config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_slice_hashing_only_in_the_shared_routing_helper():
    """Client-side routing and server-side ownership checks must agree
    BYTE-FOR-BYTE on the flowId→slice mapping, so there is exactly one
    implementation: ``sharding.slice_of``. A re-implementation anywhere
    else in the package (a copied hash constant, a second ``slice_of``
    definition, or a bare flowId modulus) can silently diverge and void
    the per-slice fencing bound."""
    import re

    helper = Path("sentinel_tpu") / "cluster" / "sharding.py"
    mix = re.compile(r"0x9E3779B97F4A7C15", re.IGNORECASE)
    # Module-level definitions only: parallel/namespaces.py's
    # NamespaceShardMap.slice_of METHOD hashes NAMESPACES for host-side
    # pod routing — a different domain with no wire-agreement contract.
    defn = re.compile(r"^def\s+slice_of\s*\(")
    modulus = re.compile(r"flow_id\s*%|fid\s*%\s*n_slices")
    offenders = []
    seen_helper = False
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        is_helper = rel == helper
        for lineno, code in _code_lines(path):
            if is_helper:
                seen_helper = seen_helper or bool(defn.search(code))
                continue
            for pat, what in ((mix, "the slice-hash constant"),
                              (defn, "a second slice_of definition"),
                              (modulus, "a bare flowId modulus")):
                if pat.search(code):
                    offenders.append(f"{rel}:{lineno} carries {what}")
    assert seen_helper, "sharding.slice_of not found (helper moved?)"
    assert not offenders, (
        "flowId→slice hashing outside cluster/sharding.py "
        "(route through sharding.slice_of): " + ", ".join(offenders))


def test_no_unbounded_queues_in_serving_paths():
    """Serving-path code (the TLV token server, command plane, Envoy
    RLS, dashboard) must never hold an unbounded ``queue.Queue()``: an
    unbounded admission queue converts overload into unbounded latency
    and memory — the queue-collapse failure mode ISSUE 6 closed. Every
    queue on a request path needs an explicit ``maxsize`` (and a shed
    story for when it fills)."""
    import re

    pattern = re.compile(r"queue\.Queue\(\s*\)")
    offenders = []
    for sub in ("cluster", "transport", "envoy_rls", "dashboard"):
        for path in sorted((REPO / "sentinel_tpu" / sub).rglob("*.py")):
            for lineno, code in _code_lines(path):
                if pattern.search(code):
                    offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "unbounded queue.Queue() in a serving path (pass maxsize= and "
        "shed on full): " + ", ".join(offenders))


def test_overload_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.overload.*`` config key must (a) be defined
    and read ONLY in core/config.py — the rest of the package goes
    through the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md, so the overload runbook can never silently
    drift from the knobs the code actually reads (same rule shape as
    the cluster-HA gate above)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.overload\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.overload.* literals outside core/config.py "
        "(use the SentinelConfig overload_* accessors): "
        + ", ".join(offenders))
    assert keys, "no overload config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "overload config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_pipeline_cycle_path_never_allocates_staging_buffers():
    """The pipeline's cycle path must stage into the recycled
    ``BatchBufferPool`` (core/batch.py), never allocate: a
    ``make_entry_batch_np``/``make_exit_batch_np`` call inside
    core/pipeline.py re-introduces the per-cycle allocation ISSUE 8
    removed (and, with async dispatch, risks mutating a buffer a live
    transfer still reads)."""
    import re

    pattern = re.compile(r"\bmake_(?:entry|exit)_batch_np\s*\(")
    path = REPO / "sentinel_tpu" / "core" / "pipeline.py"
    offenders = [f"{path.relative_to(REPO)}:{lineno}"
                 for lineno, code in _code_lines(path)
                 if pattern.search(code)]
    assert not offenders, (
        "staging-buffer allocation in the pipeline cycle path (acquire "
        "from BatchBufferPool instead): " + ", ".join(offenders))


def test_pipeline_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.pipeline.*`` config key must (a) be defined
    and read ONLY in core/config.py — the rest of the package goes
    through the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md "Pipelined admission tuning", so the runbook can
    never silently drift from the knobs the code actually reads (same
    rule shape as the cluster-HA / overload / SLO gates)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.pipeline\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.pipeline.* literals outside core/config.py "
        "(use the SentinelConfig pipeline_* accessors): "
        + ", ".join(offenders))
    assert keys, "no pipeline config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "pipeline config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_slo_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.slo.*`` / ``csp.sentinel.alert.*`` config
    key must (a) be defined and read ONLY in core/config.py — the rest
    of the package goes through the ``SentinelConfig`` accessors — and
    (b) appear in docs/OPERATIONS.md "SLOs & alerting", so the runbook
    can never silently drift from the knobs the code actually reads
    (same rule shape as the cluster-HA and overload gates above)."""
    import re

    pattern = re.compile(
        r"[\"']csp\.sentinel\.(?:slo|alert)\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.slo.* / csp.sentinel.alert.* literals outside "
        "core/config.py (use the SentinelConfig slo_* / alert_* "
        "accessors): " + ", ".join(offenders))
    assert keys, "no SLO/alert config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "SLO/alert config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_adaptive_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.adaptive.*`` config key must (a) be defined
    and read ONLY in core/config.py — the rest of the package goes
    through the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md "Adaptive limiting", so the runbook can never
    silently drift from the knobs the code actually reads (same rule
    shape as the cluster-HA / overload / SLO / pipeline gates)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.adaptive\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.adaptive.* literals outside core/config.py "
        "(use the SentinelConfig adaptive_* accessors): "
        + ", ".join(offenders))
    assert keys, "no adaptive config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "adaptive config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_adaptive_actuates_only_through_the_rollout_manager():
    """The safety story of sentinel_tpu/adaptive/ is that EVERY rule
    change rides the staged-rollout lifecycle (shadow evaluation, the
    block-rate guardrail, the SLO auto-abort). A ``load_rules`` call —
    or any direct write into an engine rule manager — from inside the
    adaptive package would be an actuation path with no blast shield;
    so would constructing its own RolloutManager (a private manager
    shares no device state with the engine's). Forbid all three."""
    import re

    patterns = [
        # the wholesale rule-application entry point every family shares
        (re.compile(r"\.load_rules\s*\("), "load_rules("),
        # direct replacement of a rule manager on the engine
        (re.compile(r"\.(?:flow|degrade|authority|system|param)_rules\s*="),
         "rule-manager assignment"),
        (re.compile(r"RolloutManager\s*\("), "private RolloutManager"),
    ]
    offenders = []
    for path in sorted((REPO / "sentinel_tpu" / "adaptive").rglob("*.py")):
        for lineno, code in _code_lines(path):
            for pattern, what in patterns:
                if pattern.search(code):
                    offenders.append(
                        f"{path.relative_to(REPO)}:{lineno} ({what})")
    assert not offenders, (
        "adaptive code must actuate ONLY via the engine's RolloutManager "
        "(load_candidate/set_stage/promote/abort): " + ", ".join(offenders))


def test_wire_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.wire.*`` config key must (a) be defined and
    read ONLY in core/config.py — the rest of the package goes through
    the ``SentinelConfig`` accessors — and (b) appear in
    docs/OPERATIONS.md "Wire-path tuning", so the runbook can never
    silently drift from the knobs the code actually reads (same rule
    shape as the cluster-HA / overload / pipeline gates)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.wire\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.wire.* literals outside core/config.py "
        "(use the SentinelConfig wire_* accessors): " + ", ".join(offenders))
    assert keys, "no wire config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "wire config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_reactor_path_zero_copy_and_coalesced_writes():
    """The reactor ingest/egress hygiene gates (ISSUE 11):

    * no ``sendall(`` — every write must go through the per-connection
      coalesced non-blocking flush (one buffer per connection per
      flush), never a blocking per-request write;
    * no ``+= b...`` / rolling bytes accumulation — frame parsing is
      the zero-copy ``FrameScanner`` (memoryview slices), and reply
      buffers are chunk deques, not growing byte strings.
    """
    import re

    patterns = [
        (re.compile(r"\.sendall\s*\("), "per-request sendall"),
        (re.compile(r"\+=\s*(?:b[\"']|data\b|chunk\b|frame\b|body\b|"
                    r"raw\b|reply\b|payload\b)"),
         "rolling bytes accumulation"),
    ]
    path = REPO / "sentinel_tpu" / "cluster" / "reactor.py"
    offenders = []
    for lineno, code in _code_lines(path):
        for pattern, what in patterns:
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno} ({what})")
    assert not offenders, (
        "reactor wire path must stay zero-copy with coalesced "
        "non-blocking writes: " + ", ".join(offenders))


def test_journal_fleet_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.journal.*`` / ``csp.sentinel.fleet.*``
    config key must (a) be defined and read ONLY in core/config.py —
    the rest of the package goes through the ``SentinelConfig``
    accessors — and (b) appear in docs/OPERATIONS.md "Fleet
    observability & forensics", so the runbook can never silently
    drift from the knobs the code actually reads (same rule shape as
    the cluster-HA / overload / SLO / sim gates)."""
    import re

    pattern = re.compile(
        r"[\"']csp\.sentinel\.(?:journal|fleet)\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.journal.* / csp.sentinel.fleet.* literals outside "
        "core/config.py (use the SentinelConfig journal_* / fleet_* "
        "accessors): " + ", ".join(offenders))
    assert keys, "no journal/fleet config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "journal/fleet config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_no_wall_clock_in_journal_and_fleet():
    """The audit journal and the fleet collector must ride the ENGINE
    timebase only (injected clock callables): an ambient wall-clock
    read in either would (a) break the simulator's journal-determinism
    contract — the same trace + seed must replay to an identical
    record stream — and (b) let a collector's staleness/skew math mix
    two clocks. Same rule (and skip logic) as the simulator gate;
    ``time.perf_counter`` stays sanctioned for speed measurement."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    offenders = []
    for name in ("journal.py", "fleet.py"):
        path = REPO / "sentinel_tpu" / "telemetry" / name
        for lineno, code in _code_lines(path):
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in journal/fleet code (ride the injected "
        "engine clock): " + ", ".join(offenders))


def test_waterfall_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.waterfall.*`` config key must (a) be
    defined and read ONLY in core/config.py — the rest of the package
    goes through the ``SentinelConfig`` ``waterfall_*`` accessors — and
    (b) appear in docs/OPERATIONS.md "Latency waterfall & saturation
    probe", so the runbook can never silently drift from the knobs the
    code actually reads (same rule shape as the journal/fleet gate)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.waterfall\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.waterfall.* literals outside core/config.py (use "
        "the SentinelConfig waterfall_* accessors): "
        + ", ".join(offenders))
    assert keys, "no waterfall config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "waterfall config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_no_wall_clock_in_waterfall():
    """The waterfall recorder must ride the ENGINE timebase only: its
    per-second staging cells are what the simulator-inertness contract
    (ISSUE 13) seals, and an ambient wall-clock read would stamp them
    with a second clock. ``time.perf_counter`` stays sanctioned — it is
    the module's DURATION source (stage deltas, probe windows), never a
    timestamp. Same rule shape as the journal/fleet gate."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    path = REPO / "sentinel_tpu" / "telemetry" / "waterfall.py"
    offenders = []
    for lineno, code in _code_lines(path):
        if pattern.search(code):
            offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in the waterfall recorder (ride the injected "
        "engine clock; perf_counter is for durations only): "
        + ", ".join(offenders))


def test_population_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.population.*`` config key must (a) be
    defined and read ONLY in core/config.py — the rest of the package
    goes through the ``SentinelConfig`` ``population_*`` accessors —
    and (b) appear in docs/OPERATIONS.md "Namespace telescope &
    admission readiness", so the runbook can never silently drift from
    the knobs the code actually reads (same rule shape as the
    waterfall gate above)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.population\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.population.* literals outside core/config.py (use "
        "the SentinelConfig population_* accessors): "
        + ", ".join(offenders))
    assert keys, "no population config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "population config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_no_wall_clock_in_population():
    """The namespace telescope must ride the ENGINE timebase only: its
    churn windows and cardinality series are part of the replay-
    determinism contract (two runs of the same trace produce identical
    population series), and an ambient wall-clock read would stamp
    them with a second clock. ``time.perf_counter`` stays sanctioned —
    it is the fold's DURATION source (self-timed overhead counter),
    never a timestamp. Same rule shape as the waterfall gate."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    path = REPO / "sentinel_tpu" / "telemetry" / "population.py"
    offenders = []
    for lineno, code in _code_lines(path):
        if pattern.search(code):
            offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in the namespace telescope (ride the injected "
        "engine clock; perf_counter is for durations only): "
        + ", ".join(offenders))


def test_sketch_hashing_only_in_the_population_module():
    """Leader pages merge EXACTLY only if every tracker places a given
    key in the same count-min cells and HLL register, so there is
    exactly one sketch-hash implementation: ``population.sketch_hash``
    plus its splitmix64 row finalizer. A re-implementation anywhere
    else in the package (a copied mix constant or a second
    ``sketch_hash`` definition) can silently diverge and void the
    cell-wise merge identity (same rule shape as the slice-hashing
    gate)."""
    import re

    helper = Path("sentinel_tpu") / "telemetry" / "population.py"
    mix = re.compile(r"0xBF58476D1CE4E5B9", re.IGNORECASE)
    defn = re.compile(r"^def\s+sketch_hash\s*\(")
    offenders = []
    seen_helper = False
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        is_helper = rel == helper
        for lineno, code in _code_lines(path):
            if is_helper:
                seen_helper = seen_helper or bool(defn.search(code))
                continue
            for pat, what in ((mix, "the sketch-mix constant"),
                              (defn, "a second sketch_hash definition")):
                if pat.search(code):
                    offenders.append(f"{rel}:{lineno} carries {what}")
    assert seen_helper, "population.sketch_hash not found (helper moved?)"
    assert not offenders, (
        "sketch hashing outside telemetry/population.py (route through "
        "population.sketch_hash): " + ", ".join(offenders))


def test_slots_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.slots.*`` config key must (a) be defined
    and read ONLY in core/config.py — the rest of the package goes
    through the ``SentinelConfig`` ``slots_*`` accessors — and (b)
    appear in docs/OPERATIONS.md "Slot-table admission", so the
    runbook can never silently drift from the knobs the code actually
    reads (same rule shape as the population gate above)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.slots\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.slots.* literals outside core/config.py (use the "
        "SentinelConfig slots_* accessors): " + ", ".join(offenders))
    assert keys, "no slots config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "slots config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_no_wall_clock_in_slots():
    """The slot table must ride the ENGINE timebase only: admit/evict
    stamps, spill-record ages, the rebalance throttle, and the
    staleness freeze gate are all part of the replay-determinism
    contract (the SlotStormCampaign's sha256 oracles replay episodes
    bit-identically), and an ambient wall-clock read would stamp them
    with a second clock. Same rule shape as the population gate."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    path = REPO / "sentinel_tpu" / "core" / "slots.py"
    offenders = []
    for lineno, code in _code_lines(path):
        if pattern.search(code):
            offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in the slot table (take now_ms from the "
        "caller — the engine clock): " + ", ".join(offenders))


def test_slot_translation_single_implementation():
    """There is exactly ONE resource -> device-slot translation:
    ``SlotTable.device_row`` (plus the engine's thin ``_device_row_of``
    dispatcher that falls back to the registry in fixed-capacity
    mode). A second ``def device_row`` — or any module outside
    core/slots.py and core/engine.py reaching into the private
    ``_hot`` tenancy map — could translate against stale tenancy and
    book state onto a reused slot's successor, the exact leak the
    generation stamps exist to prevent."""
    import re

    defn = re.compile(r"^\s*def\s+device_row\s*\(")
    hot = re.compile(r"\bslots?\._hot\b|\.slots\._hot\b")
    sanctioned = {Path("sentinel_tpu") / "core" / "slots.py"}
    hot_ok = sanctioned | {Path("sentinel_tpu") / "core" / "engine.py"}
    defs = []
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            if defn.search(code):
                defs.append((rel, lineno))
            if rel not in hot_ok and hot.search(code):
                offenders.append(f"{rel}:{lineno} touches the private "
                                 "tenancy map")
    assert [d for d in defs if d[0] in sanctioned], \
        "SlotTable.device_row not found (helper moved?)"
    stray = [f"{rel}:{line}" for rel, line in defs
             if rel not in sanctioned]
    assert not stray, ("second device_row translation implementation: "
                      + ", ".join(stray))
    assert not offenders, (
        "slot tenancy read outside the sanctioned modules (go through "
        "SlotTable's accessors): " + ", ".join(offenders))


def test_rebalance_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.rebalance.*`` config key must (a) be
    defined and read ONLY in core/config.py — the rest of the package
    goes through the ``SentinelConfig`` rebalance_* accessors — and
    (b) appear in docs/OPERATIONS.md "Self-driving rebalancing", so the
    runbook can never silently drift from the knobs the code actually
    reads (same rule shape as the journal/fleet gate)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.rebalance\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.rebalance.* literals outside core/config.py (use "
        "the SentinelConfig rebalance_* accessors): "
        + ", ".join(offenders))
    assert keys, "no rebalance config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "rebalance config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_rebalancer_actuates_only_through_ha_apply():
    """The rebalancer's ONLY shard-state mutation is ``ha.apply_map``:
    it must never call the HA internals, assign a shard map, or touch
    a token service's shard state directly — everything it does to the
    cluster flows through the same journal-audited, fault-seamed map
    path the datasource uses (the provenance + veto story depends on
    this single choke point)."""
    import re

    patterns = [
        (re.compile(r"apply_shard_map\s*\("), "apply_shard_map call"),
        (re.compile(r"\.shard_map\s*="), "shard_map assignment"),
        (re.compile(r"_become_"), "HA transition internal"),
        (re.compile(r"set_shard\s*\("), "set_shard call"),
        (re.compile(r"\.slice_epochs\s*="), "epoch table assignment"),
    ]
    path = REPO / "sentinel_tpu" / "cluster" / "rebalance.py"
    offenders = []
    for lineno, code in _code_lines(path):
        for pattern, what in patterns:
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno} ({what})")
    assert not offenders, (
        "rebalancer must mutate shard state only via ha.apply_map: "
        + ", ".join(offenders))


def test_no_wall_clock_in_rebalance():
    """The rebalancer rides the injected clock / engine timebase only:
    its cooldown stamps, freeze-gate staleness math, and certify
    episodes must all replay deterministically — one ambient wall-clock
    read would make a certify veto (or a cooldown) irreproducible from
    the campaign seed. Same rule as the journal/fleet gate."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    path = REPO / "sentinel_tpu" / "cluster" / "rebalance.py"
    offenders = []
    for lineno, code in _code_lines(path):
        if pattern.search(code):
            offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in rebalance.py (ride the injected clock): "
        + ", ".join(offenders))


def test_llm_config_keys_accessor_only_and_documented():
    """Every ``csp.sentinel.llm.*`` config key must (a) be defined and
    read ONLY in core/config.py — the rest of the package goes through
    the ``SentinelConfig`` llm_* accessors — and (b) appear in
    docs/OPERATIONS.md "LLM admission & streaming reservations", so the
    runbook can never silently drift from the knobs the code actually
    reads (same rule shape as the cluster-HA / overload / sim gates)."""
    import re

    pattern = re.compile(r"[\"']csp\.sentinel\.llm\.[a-z.]+[\"']")
    keys = set()
    offenders = []
    for path in sorted((REPO / "sentinel_tpu").rglob("*.py")):
        rel = path.relative_to(REPO)
        for lineno, code in _code_lines(path):
            for m in pattern.findall(code):
                key = m.strip("\"'")
                keys.add(key)
                if path.name != "config.py":
                    offenders.append(f"{rel}:{lineno} reads {key!r}")
    assert not offenders, (
        "csp.sentinel.llm.* literals outside core/config.py "
        "(use the SentinelConfig llm_* accessors): " + ", ".join(offenders))
    assert keys, "no llm config keys found (regex rot?)"
    ops = (REPO / "docs" / "OPERATIONS.md").read_text()
    undocumented = sorted(k for k in keys if k not in ops)
    assert not undocumented, (
        "llm config keys missing from docs/OPERATIONS.md: "
        + ", ".join(undocumented))


def test_no_wall_clock_in_llm():
    """The streaming-reservation ledger (sentinel_tpu/llm/) rides the
    engine timebase only — every public entry point takes ``now_ms``.
    An ambient wall-clock read would couple credit expiry / idle
    eviction to the host clock and void both the replay-determinism
    contract and the numpy differential oracle (tests/test_llm.py).
    Same rule (and skip logic) as the simulator/chaos gates."""
    import re

    pattern = re.compile(
        r"\btime\.time\(|\bdatetime\.now\(|\btime\.monotonic\(|"
        r"\btime_util\.current_time_millis\(")
    offenders = []
    for path in sorted((REPO / "sentinel_tpu" / "llm").rglob("*.py")):
        for lineno, code in _code_lines(path):
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno}")
    assert not offenders, (
        "wall-clock read in llm code (take now_ms from the engine "
        "timebase): " + ", ".join(offenders))


def test_journal_writes_append_only():
    """The journal's crash-safety story is append-only JSONL: recovery
    may terminate a torn line (an append) and rotation may RENAME the
    live file aside, but nothing ever seeks, truncates, or reopens the
    file in a write-from-scratch mode — a rewrite would turn 'crash
    leaves every committed record intact' into a race."""
    import re

    patterns = [
        (re.compile(r"\.seek\s*\("), "seek"),
        (re.compile(r"\.truncate\s*\("), "truncate"),
        (re.compile(r"open\s*\([^)]*[\"']w\+?b?[\"']"),
         "write-mode open"),
        (re.compile(r"open\s*\([^)]*[\"']r\+"), "read-write open"),
    ]
    path = REPO / "sentinel_tpu" / "telemetry" / "journal.py"
    offenders = []
    for lineno, code in _code_lines(path):
        for pattern, what in patterns:
            if pattern.search(code):
                offenders.append(f"{path.relative_to(REPO)}:{lineno} ({what})")
    assert not offenders, (
        "journal file writes must stay append-only: " + ", ".join(offenders))


@pytest.mark.skipif(shutil.which("ruff") is None,
                    reason="ruff binary not in this image")
def test_ruff_clean():
    proc = subprocess.run(
        ["ruff", "check", "--no-cache", str(REPO)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
