"""The host engine: entry/exit API over the jitted device step.

This is the analog of the reference's ``CtSph`` + ``SphU`` (SURVEY.md §3.1):
it owns the node registry, the compiled rule tensors, the device state, and
the jitted ``entry_step`` / ``exit_step``; each ``entry()`` expands into a
micro-batch row, runs the step, and translates the decision into a pass,
a paced sleep, or a typed ``BlockException``.

Batch widths are drawn from a small fixed ladder so jit caches stay warm
(no dynamic shapes — XLA traces once per width). The synchronous path used
by the public API submits width-1 batches (correctness / low-rate callers);
high-rate callers and the bench use :meth:`check_batch` /
:meth:`complete_batch` directly, and the pipelined engine (M4) will feed
the same step functions from a background cadence loop.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import constants as C
from sentinel_tpu.core import context as ctx_mod
from sentinel_tpu.core.batch import (
    BATCH_WIDTHS,
    Decisions,
    EntryBatch,
    ExitBatch,
    MAX_PARAMS,
    make_entry_batch_np,
    make_exit_batch_np,
)
from sentinel_tpu.core.exceptions import BlockException, exception_for_reason


class DeviceDispatchError(RuntimeError):
    """A device dispatch died (backend failure) AFTER the input
    state may have been donated. The raising site has already dropped the
    engine to a cold state (reference restart stance: rules durable,
    stats ephemeral); catchers decide their own degradation — the sync
    entry path fails open, batch-API callers see the typed error."""
from sentinel_tpu.core.registry import NodeRegistry, ORIGIN_ID_NONE
from sentinel_tpu.metrics import profiling as prof
from sentinel_tpu.metrics.profiling import StepTimer, span, timed_call
from sentinel_tpu.resilience import DeadlineBudget


class _FastPathState:
    """One atomically-swapped snapshot of the host fast-path config:
    entry() reads a single attribute, so a rule push can never expose a
    torn (leases, guarded, unruled) combination to a lock-free reader."""

    __slots__ = ("leases", "guarded", "unruled")

    def __init__(self, leases, guarded, unruled):
        self.leases = leases
        self.guarded = guarded
        self.unruled = unruled

from sentinel_tpu.models import authority as A
from sentinel_tpu.models import degrade as D
from sentinel_tpu.models import flow as F
from sentinel_tpu.models import param_flow as P
from sentinel_tpu.models import system as Y
from sentinel_tpu.ops import step as S
from sentinel_tpu.utils import time_util
from sentinel_tpu.utils.param_hash import hash_param as _hash_param

# Per-family slot-count floors at engine construction (and after a
# reset_slot_floor): flow starts at 1 (compile_flow_rules' historical
# floor); the rest compile to zero slots until first use. One definition
# shared by __init__ and reset_slot_floor so the two can't drift.
INITIAL_SLOT_FLOOR = {"flow": 1, "degrade": 0, "authority": 0, "param": 0}

# Widths of the row window read (row_window): a sampled batch's distinct
# rows, padded up to the next width; more than the last is read in chunks.
ROW_READ_WIDTHS = (8, 64, 512)


class EntryHandle:
    """A live entry (reference: ``CtEntry``). Use as a context manager."""

    __slots__ = (
        "engine", "resource", "context", "cluster_row", "dn_row", "origin_row",
        "entry_in", "count", "created_ms", "error", "exited", "params",
        "leased", "slot_gen",
    )

    def __init__(self, engine, resource, context, cluster_row, dn_row,
                 origin_row, entry_in, count, params, leased=False,
                 now_ms=None):
        self.engine = engine
        self.resource = resource
        self.context = context
        self.cluster_row = cluster_row
        self.dn_row = dn_row
        self.origin_row = origin_row
        self.entry_in = entry_in
        self.count = count
        # Callers on the µs-scale fast path pass the clock they already
        # read; everyone else pays the (cached-tick) read here.
        self.created_ms = (engine.now_ms() if now_ms is None else now_ms)
        self.error = False
        self.exited = False
        self.params = params
        self.leased = leased
        # Slot-mode tenancy stamp (core/slots.py): the generation of the
        # slot this entry committed under, COLD_GEN (-2) for a cold-path
        # entry that must tally its exit host-side, -1 in fixed-capacity
        # mode / for pass-through handles.
        self.slot_gen = -1

    def trace(self, ex: Optional[BaseException] = None) -> None:
        """Record a business exception (reference: ``Tracer.trace``)."""
        if ex is None or not BlockException.is_block_exception(ex):
            self.error = True

    def exit(self, count: Optional[int] = None) -> None:
        if self.exited:
            return
        self.exited = True
        self.engine._do_exit(self, count if count is not None else self.count)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc is not None and not BlockException.is_block_exception(exc):
            self.trace(exc)
        self.exit()
        return False


class SentinelEngine:
    """Owns device state + compiled rules; thread-safe via one lock.

    The device step itself is a pure function, so the lock only serializes
    host-side staging and the state-swap — the TPU analog of the reference's
    lock-free LeapArray updates is that *all* mutation happens inside one
    linearized step stream.
    """

    def __init__(self, capacity: int = 4096, clock=None,
                 journal_path: Optional[str] = None,
                 slot_budget: int = 0):
        # Clock-injection seam (ISSUE 13): every internal wall-clock read
        # goes through now_ms(), so a simulator can drive a REAL engine on
        # a program-advanced clock (sentinel_tpu/simulator/replay.py) with
        # no global freeze. None = the process clock (time_util, which
        # tests may freeze globally); a callable = this engine's private
        # timebase. The device step already takes ``now`` as an explicit
        # argument — this seam closes the host-side reads.
        self._clock = clock
        # Slot-table admission (core/slots.py — ROADMAP 1): slot_budget
        # > 0 (or csp.sentinel.slots.budget) bounds the DEVICE tensor to
        # ``budget`` rows and maps the live hot resource set into them
        # dynamically, with evict/rehydrate and a loud cold-tail degrade
        # past the budget. 0 = classic fixed-capacity mode, bit-for-bit
        # the pre-slot behavior. In slot mode the registry keeps a much
        # larger capacity for name interning + metadata (it no longer
        # sizes any device tensor); the device capacity IS the budget.
        from sentinel_tpu.core.config import config as _slots_cfg

        if not slot_budget:
            slot_budget = _slots_cfg.slots_budget()
        if slot_budget:
            from sentinel_tpu.core.slots import SlotTable

            self.registry = NodeRegistry(
                _slots_cfg.slots_registry_capacity())
            self.capacity = int(slot_budget)
            self.slots = SlotTable(self, int(slot_budget))
        else:
            self.registry = NodeRegistry(capacity)
            self.capacity = capacity
            self.slots = None
        # Instant-window geometry (reference: IntervalProperty /
        # SampleCountProperty — core:node/). Config-seeded, runtime-tunable
        # via set_window_geometry(); the minute window stays fixed (as
        # upstream's minute log does).
        from sentinel_tpu.core.config import config as _cfg
        from sentinel_tpu.ops import window as W_

        interval = _cfg.get_int("csp.sentinel.statistic.interval.ms",
                                C.SECOND_WINDOW_MS)
        samples = _cfg.get_int("csp.sentinel.statistic.sample.count",
                               C.SECOND_BUCKETS)
        if interval <= 0 or samples <= 0 or interval % samples != 0:
            # Same validation set_window_geometry enforces; a bad config
            # value must not brick boot (sample_count=0 would divide by
            # zero on the first rotate) — fall back to defaults, loudly.
            from sentinel_tpu.log.record_log import record_log

            record_log.warn("invalid csp.sentinel.statistic geometry "
                            "%sms/%s; using defaults", interval, samples)
            interval, samples = C.SECOND_WINDOW_MS, C.SECOND_BUCKETS
        self._spec1 = W_.WindowSpec(interval, samples)
        # Push-property form, like upstream's SampleCountProperty /
        # IntervalProperty (datasource-bindable):
        #   engine.window_geometry_property.update_value(
        #       {"intervalMs": 2000, "sampleCount": 4})
        from sentinel_tpu.core.property import (
            DynamicSentinelProperty, SimplePropertyListener)

        self.window_geometry_property = DynamicSentinelProperty()
        self.window_geometry_property.add_listener(SimplePropertyListener(
            lambda v: self.set_window_geometry(
                v.get("intervalMs"), v.get("sampleCount"))))
        # Prioritized-borrow wait cap (reference: OccupyTimeoutProperty —
        # core:node/). Config-seeded, runtime-tunable; push form:
        #   engine.occupy_timeout_property.update_value(250)
        seed_occupy = _cfg.get_int(
            "csp.sentinel.occupy.timeout.ms", C.DEFAULT_OCCUPY_TIMEOUT_MS)
        if not 0 <= seed_occupy <= interval:
            from sentinel_tpu.log.record_log import record_log

            record_log.warn(
                "invalid csp.sentinel.occupy.timeout.ms %s (window %sms); "
                "using default", seed_occupy, interval)
            seed_occupy = min(C.DEFAULT_OCCUPY_TIMEOUT_MS, interval)
        self._occupy_timeout_ms = seed_occupy
        self.occupy_timeout_property = DynamicSentinelProperty()
        self.occupy_timeout_property.add_listener(SimplePropertyListener(
            lambda v: self.set_occupy_timeout(int(v))))
        # Global kill switch (reference: Constants.ON via the setSwitch /
        # getSwitch command handlers). Off => every entry passes unguarded.
        self.enabled = True
        self.flow_rules = F.FlowRuleManager()
        self.flow_rules.add_listener(lambda: self._on_rules_changed("flow"))
        self.degrade_rules = D.DegradeRuleManager()
        self.degrade_rules.add_listener(lambda: self._mark_dirty("degrade"))
        self.authority_rules = A.AuthorityRuleManager()
        self.authority_rules.add_listener(lambda: self._mark_dirty("authority"))
        self.system_rules = Y.SystemRuleManager()
        self.system_rules.add_listener(lambda: self._mark_dirty("system"))
        self.param_rules = P.ParamFlowRuleManager()
        self.param_rules.add_listener(lambda: self._on_rules_changed("param"))
        # LLM admission (sentinel_tpu/llm/ — ISSUE 17): the TPS family
        # LOWERS onto flow rules (llm/rules.py) — the listener strips
        # previously-derived rules and re-injects, so the device machinery
        # gains no fourth tensor pack. The streaming-reservation ledger is
        # host-side, engine-timebase only, evicted on the spill cadence.
        from sentinel_tpu.llm.rules import TpsRuleManager
        from sentinel_tpu.llm.streams import StreamLedger

        self.tps_rules = TpsRuleManager()
        self.tps_rules.add_listener(self._on_tps_rules_changed)
        self._llm_max_streams: Dict[str, int] = {}
        self._llm_window_budget: Dict[str, float] = {}
        self._llm_default_estimate = _cfg.llm_default_estimate_tokens()
        self.streams = StreamLedger(
            capacity=_cfg.llm_max_streams(),
            idle_evict_ms=_cfg.llm_idle_evict_ms(),
            window_ms=interval)
        self.system_status = Y.SystemStatusListener()
        self._signals_refreshed_ms = 0
        self._sealed_sec = self.now_ms() // 1000 - 1
        # Control-plane audit journal (telemetry/journal.py — ISSUE 14):
        # every rule/SLO/target load, rollout transition, HA role flip,
        # shard-map apply, adaptive decision, and clock swap appends one
        # seq-numbered, causally-linked record. Constructed FIRST among
        # the observability surfaces: the rule managers, rollout, SLO,
        # adaptive, and cluster layers below all write through it (and
        # the SLO/adaptive logs RESTORE from it after a restart when a
        # file backs it). Stamps ride now_ms(), so a simulator replay
        # journals in simulated time. journal_path: None = the
        # csp.sentinel.journal.path config, "" = force memory-only
        # (the simulator's determinism stance — a shared file would
        # leak one replay's records into the next).
        from sentinel_tpu.telemetry.journal import ControlPlaneJournal

        self.journal = ControlPlaneJournal(self.now_ms, path=journal_path)
        # Fleet federation (telemetry/fleet.py): a FleetView collector
        # attached via the `fleet` ops command (None = not watching).
        self.fleet = None
        # Flight-recorder tee (ISSUE 13): callables invoked with each
        # freshly spilled complete second, already rendered to the
        # ``second_to_dict`` JSON shape — the trace writer subscribes
        # here (simulator/trace.py) so live traffic can be captured into
        # a portable replay trace with zero extra device work.
        self._flight_tees: List = []
        # Cluster role (client / embedded server) — host-side maps from
        # resource to its cluster-mode rules' (flowId, fallbackToLocal).
        from sentinel_tpu.cluster.state import ClusterStateManager

        self.cluster = ClusterStateManager()
        # Role flips (ops setClusterMode, HA promotions) journal through
        # the owning engine — and servers the manager starts serve THIS
        # engine's bridge + fleet telemetry; standalone managers leave
        # both None.
        self.cluster.journal = self.journal
        self.cluster.engine = self
        # Staged rollout (sentinel_tpu/rollout/): candidate rulesets
        # evaluated in shadow lanes of the fused step, optionally enforced
        # for a deterministic canary slice. The compiled candidate pack +
        # the traced canary scalars live here; the manager owns lifecycle
        # and guardrails. Constructed AFTER the rule managers (it reads
        # their staged partitions) but BEFORE any listener can fire.
        self._shadow_rules: Optional[S.RulePack] = None
        self._canary_bps: Optional[int] = None
        self._canary_salt = 0
        from sentinel_tpu.rollout.manager import RolloutManager

        self.rollout = RolloutManager(self)
        self._cluster_flow_info: Dict[str, list] = {}
        self._cluster_param_info: Dict[str, list] = {}
        # flowId -> (threshold, windowIntervalMs) of the LOCAL copies of
        # cluster-mode flow rules: the HA client's degraded-quota share
        # base (cluster/ha.py — per-client share of the global threshold
        # while no leader is reachable). Replaced wholesale on rule load.
        self._cluster_thresholds: Dict[int, tuple] = {}
        self._pipeline = None
        # Cumulative pipelined-admission counters across pipeline
        # start/stop generations (the live Pipeline object dies with
        # stop_pipeline; scrapers need monotone counters).
        self._pipeline_totals = {
            "cycles": 0, "batched": 0, "harvests": 0, "failOpenCycles": 0,
            "inflightDepthMax": 0, "poolAllocated": 0, "poolReused": 0,
        }
        # Guards the totals fold + the retiring hand-off so scrapes
        # during stop_pipeline() never see the monotone counters dip
        # (and concurrent stops can never double-fold). Deliberately
        # NOT the engine lock: stats reads must not stall behind a
        # dispatch-held compile.
        self._pipeline_stats_lock = threading.Lock()
        # A pipeline between "unhooked from admission" and "counters
        # folded" — pipeline_stats() keeps reading its live counters.
        self._retiring_pipeline = None
        # Entries that passed UNGUARDED because the pipeline could not
        # produce a verdict (collector death / cycle error). A silent
        # fail-open is an invisible protection outage — count it and log
        # at most once per second (reference's fallback is at least
        # observable through block logs).
        self.fail_open_count = 0
        self._fail_open_logged_ms = 0
        # Resilience accounting (sentinel_tpu/resilience/): how often
        # cluster-mode rules degraded to their local fallback, and the
        # aggregate remote-wait budget one entry() may spend in
        # _cluster_token_check (bounded-latency graceful degradation —
        # the old behavior paid up to request_timeout_s PER cluster rule
        # plus unbounded SHOULD_WAIT sleeps).
        self.cluster_fallback_count = 0
        self.cluster_budget_exhausted_count = 0
        # Overload sheds (ISSUE 6): entries whose cluster check came back
        # OVERLOADED (the token server shed before admission) and were
        # served via the local lease/fallback path instead.
        self.cluster_overload_count = 0
        # Shard mis-routes (ISSUE 12): entries whose cluster check came
        # back WRONG_SLICE un-healed — the client's routing map was
        # stale past what the self-healing walk could absorb (or a
        # plain unsharded client is pointed at a sharded leader); the
        # rule degraded to its local fallback.
        self.cluster_wrong_slice_count = 0
        from sentinel_tpu.core.config import (
            DEFAULT_RESILIENCE_ENTRY_BUDGET_MS, RESILIENCE_ENTRY_BUDGET_MS)

        self.cluster_entry_budget_ms = _cfg.get_int(
            RESILIENCE_ENTRY_BUDGET_MS, DEFAULT_RESILIENCE_ENTRY_BUDGET_MS)
        if self.cluster_entry_budget_ms <= 0:
            from sentinel_tpu.log.record_log import record_log

            record_log.warn("invalid %s=%s; using default %dms",
                            RESILIENCE_ENTRY_BUDGET_MS,
                            self.cluster_entry_budget_ms,
                            DEFAULT_RESILIENCE_ENTRY_BUDGET_MS)
            self.cluster_entry_budget_ms = DEFAULT_RESILIENCE_ENTRY_BUDGET_MS
        # Per-step timing (SURVEY §5): enqueue wall per dispatch + sampled
        # synchronous step wall; surfaced via the `profile` ops command.
        # The sampling cadence is config-tunable (`csp.sentinel.profile.
        # syncEvery`): every Nth dispatch blocks for a true step wall.
        from sentinel_tpu.core.config import (
            DEFAULT_PROFILE_SYNC_EVERY, PROFILE_SYNC_EVERY)

        sync_every = _cfg.get_int(PROFILE_SYNC_EVERY,
                                  DEFAULT_PROFILE_SYNC_EVERY)
        if sync_every <= 0:
            from sentinel_tpu.log.record_log import record_log

            record_log.warn("invalid %s=%s; using default %d",
                            PROFILE_SYNC_EVERY, sync_every,
                            DEFAULT_PROFILE_SYNC_EVERY)
            sync_every = DEFAULT_PROFILE_SYNC_EVERY
        self.step_timer = StepTimer(sync_every=sync_every)
        # Sampled decision traces (sentinel_tpu/telemetry/): every Nth
        # blocked entry pulled off-device asynchronously, served by the
        # `traces` ops command and the dashboard.
        from sentinel_tpu.telemetry.trace_ring import DecisionTraceBuffer

        self.traces = DecisionTraceBuffer(self)
        # Cross-process spans (telemetry/spans.py): every Nth cluster-
        # checked entry carries a trace context over the token-server
        # wire; the stitched spans land here for the `traces` command's
        # span view and the OTLP export.
        from sentinel_tpu.telemetry.spans import SpanCollector

        self.spans = SpanCollector()
        # Flight recorder (telemetry/timeseries.py): device ring length
        # (0 disables the device tensors entirely) + the compacted
        # host-side history the ring spills into on reads.
        from sentinel_tpu.core.config import (
            DEFAULT_TELEMETRY_TIMESERIES_HISTORY,
            DEFAULT_TELEMETRY_TIMESERIES_SECONDS,
            TELEMETRY_TIMESERIES_HISTORY,
            TELEMETRY_TIMESERIES_SECONDS,
        )
        from sentinel_tpu.telemetry.timeseries import TimeseriesHistory

        self.flight_seconds = max(0, _cfg.get_int(
            TELEMETRY_TIMESERIES_SECONDS,
            DEFAULT_TELEMETRY_TIMESERIES_SECONDS))
        self.timeseries = TimeseriesHistory(_cfg.get_int(
            TELEMETRY_TIMESERIES_HISTORY,
            DEFAULT_TELEMETRY_TIMESERIES_HISTORY))
        # SLO engine (sentinel_tpu/slo/): burn-rate objectives + anomaly
        # baselines + health scores, evaluated from the COMPLETE seconds
        # the flight recorder spills — fed by _spill_flight, so the
        # judgement layer rides the existing once-per-second fold and
        # adds zero per-step device work.
        from sentinel_tpu.slo.manager import SloManager

        self.slo = SloManager(self)
        # Wire-to-device latency waterfall (ISSUE 18): per-stage log2
        # histograms over perf_counter stage deltas, sealed once per
        # second by _spill_flight's fold. Constructed AFTER slo — its
        # regression sentry fires through slo.external_transition.
        from sentinel_tpu.telemetry.waterfall import WaterfallRecorder

        self.waterfall = WaterfallRecorder(self)
        # Namespace telescope (ISSUE 19): population sensing over the
        # unbounded (resource, flowId) key space — top-k / CMS / HLL /
        # churn riding the same spill fold. Constructed AFTER slo for
        # the same reason as the waterfall: its cardinality alarm fires
        # through slo.external_transition.
        from sentinel_tpu.telemetry.population import PopulationTracker

        self.population = PopulationTracker(self)
        # Closed-loop adaptive limiting (sentinel_tpu/adaptive/): the
        # acting half of the loop the SLO engine senses for. Constructed
        # AFTER rollout (it registers a lifecycle listener) and slo (its
        # senses read judgement); ticks ride _spill_flight, so the loop
        # adds zero per-step device work and no background thread.
        from sentinel_tpu.adaptive.loop import AdaptiveLoop

        self.adaptive = AdaptiveLoop(self)
        # Governed shard placement (ISSUE 16): senses the fleet plane,
        # proposes minimal-movement map diffs, chaos-certifies them, and
        # applies through the journal-audited HA path. Pure control
        # plane — no background thread; ops drive it via `rebalance`.
        from sentinel_tpu.cluster.rebalance import ShardRebalancer

        self.rebalancer = ShardRebalancer(self)
        # Token-lease fast path (core/lease.py): host-admitted resources +
        # the async stats committer. Rebuilt on every rule push.
        self.lease_enabled = (
            (_cfg.get("csp.sentinel.lease.enabled") or "true").lower()
            != "false")
        # Unruled resources may skip the device check entirely (always
        # pass + async stats commit); flipped off with system rules / SPI.
        self._fastpath = _FastPathState({}, frozenset(), self.lease_enabled)
        self._committer = None
        self._closed = False
        self._lock = threading.RLock()
        # Config-plane lock: serializes rule pushes / geometry retunes /
        # close against EACH OTHER without making them wait on the device
        # dispatch path, which holds ``_lock`` for the full XLA call —
        # including first-dispatch compiles (seconds on CPU, 20-40s on
        # TPU). Before the split, a rule push racing a cold compile
        # appeared to "not take": the manager had the new rules while the
        # lease table served the old thresholds until the compile
        # finished. Lock ORDER is config -> engine; never acquire
        # ``_config_lock`` while holding ``_lock``.
        self._config_lock = threading.RLock()
        self._state: Optional[S.SentinelState] = None
        self._rules: Optional[S.RulePack] = None
        self._named_origins: Dict[str, set] = {}
        self._dirty = {"flow": True, "degrade": True, "authority": True,
                       "system": True, "param": True, "rollout": False}
        # Slot-count ratchet per family: empty families compile to ZERO
        # slots (their per-slot loops vanish — a no-rules step is ~4x
        # cheaper), but 0 -> 1 slots is a tensor-SHAPE change that would
        # retrace the fused step on a rule push. Flooring each compile at
        # the widest slot count ever seen keeps the round-4 guarantee
        # "rule pushes don't recompile" for every push after a family's
        # first use (the first-use retrace is one-time and unavoidable).
        # Flow starts at 1 (compile_flow_rules' historical floor) and
        # ratchets up the same way: a second rule on one resource widens
        # the shape once and it never shrinks back.
        self._slot_floor = dict(INITIAL_SLOT_FLOOR)
        self._rebuild_w1_jits()
        self._flush_jit = jax.jit(S.flush_seconds, donate_argnums=(0,))
        self._w60_read_jit = jax.jit(lambda st_, now, idx: jnp.transpose(
            W_.rotate(st_.w60, now, S.SPEC_60S).counts[idx], (2, 0, 1)))
        # Flight-recorder spill read: gather only the requested ring
        # slots on device, ONE host transfer (full-ring reads would move
        # the whole ~55MB ring per spill).
        self._flight_read_jit = jax.jit(lambda st_, idx: (
            st_.flight.events[idx], st_.flight.attr[idx],
            st_.flight.hist[idx], st_.flight.slot_attr[idx]))
        # SPI boot (reference: Env static init -> InitExecutor.doInit) +
        # device-checker splice: the step re-jits when registrations change.
        from sentinel_tpu.core import spi as spi_mod

        self._spi = spi_mod
        self._spi_version = -1
        self._entry_jit = None
        self._rebuild_entry_jit()
        # Init funcs do NOT run here: an @init_func calling the module API
        # mid-construction would hit a half-assigned singleton. get_engine()
        # fires them once the default engine is installed (the reference's
        # "first SphU.entry triggers doInit" ordering).

    # -- clock seam (ISSUE 13) ---------------------------------------------

    def now_ms(self) -> int:
        """This engine's timebase: the injected clock when one is set
        (simulator replay), else the process clock (which tests freeze
        globally via time_util). Every host-side time read inside the
        engine — and in the adaptive/rollout/SLO layers riding it — goes
        through here, so a replayed engine experiences ONE consistent,
        program-advanced time."""
        clock = self._clock
        return clock() if clock is not None else \
            time_util.current_time_millis()

    def set_clock(self, clock) -> None:
        """Install (or clear, with None) an injected clock, resetting
        the engine's time cursors AND its volatile statistics to the
        new timebase.

        The cursors assume time never moves backward: ``_sealed_sec``
        gates the metric log, ``timeseries.last_stamp_ms`` gates the
        flight-recorder spill, and the signal/log throttles hold
        last-read stamps. Swapping to a timebase earlier than the old
        one would otherwise silently wedge all of them (seconds "already
        sealed/spilled", throttles never expiring) — the latent
        real-time-monotonicity assumption this seam flushes out. Device
        state is dropped cold for the same reason: window bucket
        starts, the staged second, and flight-ring slots all carry
        old-timebase stamps that would interleave wrongly with the new
        one. Rules survive, statistics restart — the reference restart
        stance, rebuilt on the next dispatch (shape-cached jits make
        that a cheap ``make_state``, not a recompile)."""
        with self._config_lock, self._lock:
            self._clock = clock
            now = self.now_ms()
            self._sealed_sec = now // 1000 - 1
            self._signals_refreshed_ms = 0
            self._fail_open_logged_ms = 0
            self._state = None  # stats ephemeral; _ensure_compiled rebuilds
            self.timeseries.clear()
            # Lease mirrors carry last-filled / window stamps of the OLD
            # timebase: a warm-up mirror with a future-stamped sync (or a
            # param bucket that can never refill) would wedge the fast
            # path exactly like the spill cursors above. Drop the table
            # and rebuild COLD — swapping the fast path to empty first
            # keeps _rebuild_leases from carrying the stale mirrors over
            # (its carry-over exists for rule pushes, where the timebase
            # is continuous).
            self._fastpath = _FastPathState({}, frozenset(),
                                            self.lease_enabled)
            self._rebuild_leases()
        # Stamp-bearing subsystem cursors reset OUTSIDE the engine locks
        # (they take their own locks, and the established order is
        # adaptive/slo -> engine, never the inverse): SLO ingest/eval
        # cursors + series/baselines/alerts, and the adaptive loop's
        # abort backoff + envelope cooldown stamps — all absolute times
        # of the old timebase that would wedge judgement or freeze
        # retuning for (simulated) decades after a backward swap.
        self.slo.reset_timebase()
        adaptive = getattr(self, "adaptive", None)
        if adaptive is not None:
            adaptive.reset_timebase()
        rebalancer = getattr(self, "rebalancer", None)
        if rebalancer is not None:
            rebalancer.reset_timebase()
        waterfall = getattr(self, "waterfall", None)
        if waterfall is not None:
            waterfall.reset_timebase()
        population = getattr(self, "population", None)
        if population is not None:
            population.reset_timebase()
        # Audit the swap itself — stamped with the NEW timebase (the
        # old one no longer exists to stamp with). seq stays monotone
        # across the swap even though timestamps may step backward;
        # SEMANTICS.md "Journal causality" names this asymmetry.
        self.journal.record("clockSwap", injected=clock is not None)

    def add_flight_tee(self, fn) -> None:
        """Subscribe ``fn(second_dict)`` to every freshly spilled
        complete flight-recorder second (the trace-capture hook)."""
        self._flight_tees.append(fn)

    def remove_flight_tee(self, fn) -> None:
        try:
            self._flight_tees.remove(fn)
        except ValueError:
            pass

    @property
    def _leases(self):
        return self._fastpath.leases

    @property
    def _guarded_resources(self):
        return self._fastpath.guarded

    @property
    def _unruled_fastpath(self):
        return self._fastpath.unruled

    def _rebuild_leases(self) -> None:
        """Recompute the token-lease table from current rules + geometry.

        Mirrors must NOT reset to zero on a rule push — re-granting quota
        already spent this window would double-admit. Surviving resources
        carry their mirror over; newly-eligible ones seed from the device
        window (their past traffic took the device path, so the window IS
        their usage)."""
        from sentinel_tpu.core.lease import build_lease_table

        if self._closed:
            # close() swapped in the empty fast path; a straggler push
            # must not resurrect lease admission on a closed engine.
            return
        old = self._leases
        if self.lease_enabled:
            new, guarded, unruled_ok = build_lease_table(self)
        else:
            new, guarded, unruled_ok = {}, set(), False
        fresh = []
        for res, lease in new.items():
            prev = old.get(res)
            if prev is not None and prev.buckets == lease.buckets \
                    and prev.bucket_ms == lease.bucket_ms:
                lease.seed(*prev.snapshot())
            else:
                fresh.append(res)
        if fresh:
            self._seed_leases_into(new, fresh)
        self._fastpath = _FastPathState(new, guarded, unruled_ok)

    def _ensure_committer(self):
        committer = self._committer
        if committer is None:
            from sentinel_tpu.core.lease import StatsCommitter, SyncCommitter

            with self._lock:
                if self._closed:
                    # An entry racing close() read the fast path before the
                    # swap; committing inline beats silently resurrecting a
                    # daemon thread (+hooks) on a closed engine.
                    return SyncCommitter(self)
                if self._committer is None:
                    self._committer = StatsCommitter(self).start()
                committer = self._committer
        return committer

    def _flush_committer(self) -> None:
        """Drain pending leased commits so reads are deterministic."""
        committer = self._committer
        if committer is not None:
            committer.flush()

    def _seed_leases_from_state(self, only: Optional[List[str]] = None) -> None:
        """Adopt device windows into the lease mirrors (checkpoint warm
        restart)."""
        targets = [res for res in self._leases
                   if only is None or res in only]
        self._seed_leases_into(self._leases, targets)

    def _seed_leases_into(self, table, targets) -> None:
        """Seed ``targets``' mirrors in ``table`` from the device window
        PLUS any un-flushed committer commits (a previously-unruled
        resource's recent traffic may still sit in the queue; flushing
        here would deadlock against the background flush, which takes the
        engine lock we may already hold — so count, don't flush).

        Row lookup is NON-allocating: a resource with no registry row has
        never served traffic, so there is nothing to seed (and allocating
        here would make a mere rule load consume rows, tripping
        ``restore_checkpoint``'s fresh-engine guard)."""
        targets = [res for res in targets if res in table]
        if not targets:
            return
        with self._lock:
            state = self._state
            if state is not None:
                pass_counts = np.asarray(
                    state.w1.counts[:, C.MetricEvent.PASS, :])
                starts = np.asarray(state.w1.starts)
            rows = {}
            for res in targets:
                row = self._device_row_of(res)
                if row is not None:
                    rows[res] = row
        committer = self._committer
        pending = committer.pending_pass_counts() if committer else {}
        now = self.now_ms()
        for res in targets:
            if res not in rows:
                continue  # never served traffic: mirror stays empty
            lease = table[res]
            if state is not None:
                lease.seed(starts, pass_counts[:, rows[res]])
            # Queued (not yet flushed) commits are real usage too — with no
            # device state yet (nothing ever flushed) they are ALL of it.
            queued = pending.get(rows[res], 0)
            if queued:
                lease.add(queued, now)

    def _rebuild_w1_jits(self):
        """(Re)build the spec1-dependent jits — one construction site shared
        by __init__ and set_window_geometry, so a retuned engine cannot
        drift from boot behavior.

        Jitted read paths: unjitted window rotation dispatches op-by-op and
        measured ~100ms/read at 32k rows; one compiled program is ~1ms (see
        seal_metrics docstring for the 10k-resource numbers). The totals
        read normalizes window sums to per-second QPS (reference
        ``StatisticNode.passQps`` divides by the interval in seconds), the
        same scaling the flow checker applies on-device.
        """
        from sentinel_tpu.ops import window as W_

        spec1 = self._spec1
        qps_scale = jnp.float32(1000.0 / spec1.interval_ms)
        self._exit_jit = jax.jit(
            functools.partial(S.exit_step, spec1=spec1), donate_argnums=(0,))
        self._w1_read_jit = jax.jit(lambda st_, now: (
            W_.all_totals(W_.rotate(st_.w1, now, spec1)).astype(jnp.float32)
            * qps_scale,
            st_.cur_threads))

        # A def, not a jitted functools.partial: XLA names partials
        # ``jit__unknown(...)``, the name the fused steps' device-time
        # reading keys on.
        def row_window_read(w1, cur_threads, now, rows):
            # Gather, then rotate: the rotation's bucket mask and the
            # bucket sum act on each row alone, so a gathered row reads
            # what the whole-window read gives it.
            win = W_.Window(w1.counts[:, :, rows], w1.min_rt[:, rows],
                            w1.starts)
            return (W_.all_totals(W_.rotate(win, now, spec1))
                    .astype(jnp.float32) * qps_scale, cur_threads[rows])

        self._row_read_jit = jax.jit(row_window_read)

    def _rebuild_entry_jit(self):
        # Version BEFORE checkers: a registration racing between the two
        # reads then leaves version != snapshot and the next
        # _ensure_compiled re-runs this (the reverse order would pin a
        # stale checker set forever).
        self._spi_version = self._spi.device_version()
        checkers = self._spi.device_checkers()
        step = functools.partial(
            S.entry_step, extra_checkers=checkers, spec1=self._spec1)
        self._entry_jit = jax.jit(step, donate_argnums=(0,))

    # -- rule compilation --------------------------------------------------

    def _mark_dirty(self, family: str):
        # Config lock, NOT the engine lock: the dirty flag hand-off is a
        # GIL-atomic dict write (_ensure_compiled reads it under the
        # engine lock on next dispatch), and the lease rebuild must not
        # queue behind an in-flight dispatch's compile (see _config_lock).
        with self._config_lock:
            self._dirty[family] = True
            self._sync_rollout_sources()
            self._rebuild_leases()
        self._slots_sync_pins()
        self._journal_rule_load(family)

    def _journal_rule_load(self, family: str) -> None:
        """One ``ruleLoad`` audit record per family load: who pushed
        (the ``acting()`` provenance context — datasource pollers and
        ops commands set it), what is now in force (rule dicts, capped),
        and what caused it (a rollout promotion's ``causing()`` seam).
        Runs OUTSIDE the config lock — the journal fsync must never
        extend the window a rule push holds the config plane."""
        from sentinel_tpu.datasource import converters as CV
        from sentinel_tpu.telemetry.journal import MAX_RULES_PER_RECORD

        mgr, to_dict = {
            "flow": (self.flow_rules, CV.flow_rule_to_dict),
            "degrade": (self.degrade_rules, CV.degrade_rule_to_dict),
            "authority": (self.authority_rules, CV.authority_rule_to_dict),
            "system": (self.system_rules, CV.system_rule_to_dict),
            "param": (self.param_rules, CV.param_rule_to_dict),
            "tps": (self.tps_rules, CV.tps_rule_to_dict),
        }[family]
        rules = list(mgr.get_rules())
        dicts = []
        for r in rules[:MAX_RULES_PER_RECORD]:
            try:
                dicts.append(to_dict(r))
            except Exception:  # noqa: BLE001 — audit must not break loads
                dicts.append({"resource": getattr(r, "resource", None)})
        self.journal.record(
            "ruleLoad", family=family, count=len(rules), rules=dicts,
            rulesTruncated=len(rules) > MAX_RULES_PER_RECORD)

    def _sync_rollout_sources(self) -> None:
        """Rule pushes may carry staged (candidate-tagged) rules, and the
        active candidate's MERGED view depends on the live rules — both
        make the compiled shadow pack stale. Caller holds the config lock."""
        rollout = getattr(self, "rollout", None)
        if rollout is None:
            return
        rollout.refresh_staged()
        if rollout.device_active():
            self._dirty["rollout"] = True

    def _set_canary(self, bps: Optional[int], salt: int) -> None:
        """Canary knobs are TRACED step scalars: tuning the percentage or
        salt never recompiles; only the None<->set flip (enter/leave the
        canary stage) retraces, like any argument-structure change."""
        self._canary_bps = None if bps is None else int(bps)
        self._canary_salt = int(salt)

    def _on_rules_changed(self, family: str):
        """Flow/param loads also rebuild the host-side cluster-rule maps
        eagerly (cheap scans), so the entry() fast path can consult them
        lock-free: the dicts are replaced wholesale, never mutated."""
        with self._config_lock:
            self._dirty[family] = True
            self._sync_rollout_sources()
            self._rebuild_leases()
            if family == "flow":
                rules = self.flow_rules.get_rules()
                self._cluster_flow_info = self._cluster_info(rules)
                self._cluster_thresholds = self._cluster_threshold_map(rules)
                # origin_named is read on entry BEFORE compilation runs, so
                # the named-origin map must be fresh at load time too (same
                # classification helper as the compiler — no drift).
                self._named_origins = F.named_origin_map(rules, self.registry)
            else:
                self._cluster_param_info = self._cluster_info(
                    self.param_rules.get_rules(), with_param_idx=True)
        self._slots_sync_pins()
        self._journal_rule_load(family)

    def _on_tps_rules_changed(self):
        """TPS loads LOWER onto the flow family (llm/rules.py): strip the
        previously-derived rules, re-inject the fresh lowering, keep every
        operator rule (live and staged) untouched. The flow load below
        fires the normal flow listener, so tensors/leases/cluster maps
        rebuild with no TPS-specific compilation path. An operator flow
        push replaces the whole flow list — lowered rules vanish until
        the next TPS load re-lowers (documented contract)."""
        from sentinel_tpu.llm import rules as LR

        tps_live = self.tps_rules.get_rules()
        tps_staged = [r for rs in self.tps_rules.get_staged().values()
                      for r in rs]
        lowered = LR.lower_tps_rules(tps_live) \
            + LR.lower_tps_rules(tps_staged)
        # Replaced wholesale, never mutated — entry()'s stream_open
        # concurrency check reads it lock-free.
        self._llm_max_streams = LR.max_streams_by_resource(tps_live)
        # resource -> tightest per-window token budget: the reservation
        # cap (an up-front reservation can never exceed one window's
        # budget — the rest of a long generation pays live as it
        # streams across later windows).
        budgets: Dict[str, float] = {}
        for r in LR.lower_tps_rules(tps_live):
            cur = budgets.get(r.resource)
            budgets[r.resource] = r.count if cur is None \
                else min(cur, r.count)
        self._llm_window_budget = budgets
        keep = [r for r in self.flow_rules.get_rules()
                if getattr(r, "derived_from", None) != LR.DERIVED_TPS]
        keep += [r for rs in self.flow_rules.get_staged().values()
                 for r in rs
                 if getattr(r, "derived_from", None) != LR.DERIVED_TPS]
        self.flow_rules.load_rules(keep + lowered)
        self._journal_rule_load("tps")

    def _ensure_compiled(self):
        """(Re)build rule tensors + state after a config push (§3.2).

        Each family rebuilds independently: a flow-rule push re-creates
        flow controller state (reference: "WarmUp state re-created!") but
        leaves circuit-breaker state intact, and vice versa. Node stats
        always survive.
        """
        if self._spi_version != self._spi.device_version():
            self._rebuild_entry_jit()  # SPI device checker set changed
        # Dirty flags are cleared BEFORE the corresponding get_rules()
        # read, and the dict object is never rebound: rule pushes set the
        # flag on the config plane WITHOUT the engine lock (_mark_dirty),
        # so clear-after-read would lose a push landing mid-compile (the
        # dispatcher would clear a flag it never compiled for, and the
        # device tensors would enforce stale rules until an unrelated
        # later push). Clear-first at worst costs one redundant recompile.
        if self._state is None:
            for k in self._dirty:
                self._dirty[k] = False
            now = self.now_ms()
            ft, named = F.compile_flow_rules(
                self.flow_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["flow"])
            dt, di = D.compile_degrade_rules(
                self.degrade_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["degrade"])
            pt = P.compile_param_rules(
                self.param_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["param"])
            at = A.compile_authority_rules(
                self.authority_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["authority"])
            self._ratchet_slots(flow=ft, degrade=dt, param=pt, authority=at)
            self._named_origins = {r: set(o) for r, o in named.items()}
            self._rules = S.RulePack(
                flow=ft, degrade=dt, authority=at,
                system=Y.compile_system_rules(self.system_rules.get_rules()),
                param=pt,
            )
            self._state = S.make_state(self.capacity, ft.num_rules, now,
                                       degrade=D.make_degrade_state(dt, di),
                                       param=P.make_param_state(pt.num_rules),
                                       spec1=self._spec1,
                                       flight_seconds=self.flight_seconds)
            self._maybe_start_system_listener()
            self._compile_shadow()
            return
        if not any(self._dirty.values()):
            return
        now = self.now_ms()
        if self._dirty["flow"]:
            self._dirty["flow"] = False
            ft, named = F.compile_flow_rules(
                self.flow_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["flow"])
            self._ratchet_slots(flow=ft)
            self._named_origins = {r: set(o) for r, o in named.items()}
            self._rules = self._rules._replace(flow=ft)
            self._state = self._state._replace(flow=F.make_flow_state(ft.num_rules, now))
        if self._dirty["degrade"]:
            self._dirty["degrade"] = False
            dt, di = D.compile_degrade_rules(
                self.degrade_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["degrade"])
            self._ratchet_slots(degrade=dt)
            self._rules = self._rules._replace(degrade=dt)
            self._state = self._state._replace(degrade=D.make_degrade_state(dt, di))
        if self._dirty["authority"]:
            self._dirty["authority"] = False
            at = A.compile_authority_rules(
                self.authority_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["authority"])
            self._ratchet_slots(authority=at)
            self._rules = self._rules._replace(authority=at)
        if self._dirty["system"]:
            self._dirty["system"] = False
            self._rules = self._rules._replace(
                system=Y.compile_system_rules(self.system_rules.get_rules()))
            self._maybe_start_system_listener()
        if self._dirty["param"]:
            self._dirty["param"] = False
            pt = P.compile_param_rules(
                self.param_rules.get_rules(), self._rule_registry(),
                self.capacity, min_slots=self._slot_floor["param"])
            self._ratchet_slots(param=pt)
            self._rules = self._rules._replace(param=pt)
            self._state = self._state._replace(param=P.make_param_state(pt.num_rules))
        if self._dirty["rollout"]:
            self._dirty["rollout"] = False
            self._compile_shadow()

    def _compile_shadow(self) -> None:
        """(Re)build the candidate pack + a fresh shadow world, or tear
        both down when no candidate holds the device.

        The candidate compiles from the MERGED view (live rules plus the
        candidate's per-resource overrides — rollout/manager.py), with the
        same slot floors as the live pack so the common candidate-close-
        to-live case shares tensor shapes. Installing/removing a shadow is
        a state-STRUCTURE change: one retrace, like a family's first use.
        Like a live rule load, a candidate edit re-creates controller
        state — the shadow world (and its counters) restarts cold; the
        rollout guardrail re-baselines on its next tick.
        """
        self._dirty["rollout"] = False
        rollout = getattr(self, "rollout", None)
        spec = rollout.device_spec() if rollout is not None else None
        if spec is None:
            self._shadow_rules = None
            if self._state is not None and self._state.shadow is not None:
                self._state = self._state._replace(shadow=None)
            return
        ft, _ = F.compile_flow_rules(
            spec["flow"], self._rule_registry(), self.capacity,
            min_slots=self._slot_floor["flow"])
        dt, di = D.compile_degrade_rules(
            spec["degrade"], self._rule_registry(), self.capacity,
            min_slots=self._slot_floor["degrade"])
        at = A.compile_authority_rules(
            spec["authority"], self._rule_registry(), self.capacity,
            min_slots=self._slot_floor["authority"])
        pt = P.compile_param_rules(
            spec["param"], self._rule_registry(), self.capacity,
            min_slots=self._slot_floor["param"])
        self._shadow_rules = S.RulePack(
            flow=ft, degrade=dt, authority=at,
            system=Y.compile_system_rules(spec["system"]), param=pt)
        if self._state is not None:
            self._state = self._state._replace(shadow=S.make_shadow_state(
                self.capacity, self._shadow_rules,
                D.make_degrade_state(dt, di), spec1=self._spec1))

    def _ratchet_slots(self, **tensors) -> None:
        """Raise each family's slot floor to what was just compiled, so
        later pushes (even back to zero rules) keep the same tensor
        shapes and never retrace the fused step.

        The ratchet is monotonic for the process lifetime BY DESIGN: a
        one-time burst of K rules on one resource widens that family's
        per-slot device loop to K forever, trading steady-state step cost
        for the no-retrace guarantee. After a known-transient burst, ops
        can reclaim the cost with ``reset_slot_floor()`` (one retrace) —
        see OPERATIONS.md "retune"."""
        for family, rt in tensors.items():
            self._slot_floor[family] = max(self._slot_floor[family], rt.slots)

    def reset_slot_floor(self) -> Dict[str, int]:
        """Drop every family's slot floor back to its initial value and
        force a recompile, shrinking the per-slot device loops to what
        the CURRENT rules actually need.

        Costs one fused-step retrace on the next dispatch (the exact
        thing the ratchet exists to avoid) — call it deliberately after
        a transient rule burst, not on a schedule. Returns the floor that
        was in effect before the reset (ops visibility)."""
        with self._config_lock:
            old = dict(self._slot_floor)
            self._slot_floor = dict(INITIAL_SLOT_FLOOR)
            for family in INITIAL_SLOT_FLOOR:
                self._dirty[family] = True
            self._rebuild_leases()
        return old

    def _maybe_start_system_listener(self):
        def is_set(v):
            return v is not None and v >= 0

        if any(
            is_set(r.highest_system_load) or is_set(r.highest_cpu_usage)
            for r in self.system_rules.get_rules()
        ):
            self.system_status.start()

    def warmup(self, widths: Optional[Sequence[int]] = None) -> None:
        """Precompile the fused entry/exit steps for every micro-batch
        ladder width under the CURRENT rule shapes, and the row window
        read (:meth:`row_window`) at each of its widths.

        XLA specializes per (batch width, rule-tensor shape); the first
        dispatch of each pair pays a compile (seconds on CPU, 20-40s on
        TPU) while holding the engine lock — so first DEVICE-PATH traffic
        stalls behind the compiler. (Rule pushes do not: they run on the
        config lock and only wait when seeding a newly-eligible resource
        from the device window.) Production boot sequence: load initial
        rules, then ``warmup()``, then serve. No-op batches (all rows -1)
        commit nothing."""
        for width in (widths if widths is not None else BATCH_WIDTHS):
            ebuf = make_entry_batch_np(int(width))  # all rows -1: no-op
            self._run_entry_batch(
                EntryBatch(**{k: jnp.asarray(v) for k, v in ebuf.items()}))
            xbuf = make_exit_batch_np(int(width))
            self._run_exit_batch(
                ExitBatch(**{k: jnp.asarray(v) for k, v in xbuf.items()}))
        for width in ROW_READ_WIDTHS:  # the decision-trace window reads
            self.row_window(np.zeros(width, np.int64))

    def set_occupy_timeout(self, timeout_ms: int) -> None:
        """Retune the prioritized-borrow wait cap at runtime (reference:
        ``OccupyTimeoutProperty``). Capped at one instant window — a
        borrow can never wait past the window it borrows from. A TRACED
        step argument, so tuning is free (no recompile)."""
        timeout_ms = int(timeout_ms)
        with self._lock:
            if timeout_ms < 0 or timeout_ms > self._spec1.interval_ms:
                raise ValueError(
                    f"occupy timeout {timeout_ms}ms must be within "
                    f"[0, {self._spec1.interval_ms}] (one instant window)")
            self._occupy_timeout_ms = timeout_ms

    def set_window_geometry(self, interval_ms: Optional[int] = None,
                            sample_count: Optional[int] = None) -> None:
        """Retune the instant window at runtime (reference:
        ``IntervalProperty`` / ``SampleCountProperty`` — core:node/).

        The 1s-window statistics RESET under the new geometry (upstream
        rebuilds the LeapArray the same way); breakers, param buckets, the
        minute window, and the concurrency gauge survive. Pending occupy
        borrows are dropped — their bucket geometry no longer exists.
        Device shapes are static under jit, so this recompiles the step on
        next use (~one compile, same as a capacity change would).
        """
        from sentinel_tpu.ops import window as W_

        # Pre-retune queued commits belong to the OLD window: land them in
        # it before it is discarded, so neither the reset device window nor
        # the fresh lease mirrors inherit pre-retune usage. (Must happen
        # outside self._lock — the flush dispatch takes it.)
        self._flush_committer()
        with self._config_lock, self._lock:
            cur = self._spec1
            interval_ms = cur.interval_ms if interval_ms is None else int(interval_ms)
            sample_count = cur.buckets if sample_count is None else int(sample_count)
            if interval_ms <= 0 or sample_count <= 0 \
                    or interval_ms % sample_count != 0:
                raise ValueError(
                    f"invalid window geometry: interval {interval_ms}ms must "
                    f"be a positive multiple of sample count {sample_count}")
            new = W_.WindowSpec(interval_ms, sample_count)
            if new == cur:
                return
            self._spec1 = new
            # The borrow-wait cap must stay within one instant window; a
            # shrink below the active cap clamps it (loudly), or borrows
            # would credit buckets that expire before their wait elapses.
            if self._occupy_timeout_ms > new.interval_ms:
                from sentinel_tpu.log.record_log import record_log

                record_log.warn(
                    "occupy timeout %sms clamped to new %sms window",
                    self._occupy_timeout_ms, new.interval_ms)
                self._occupy_timeout_ms = new.interval_ms
            self._rebuild_w1_jits()
            self._rebuild_entry_jit()  # closes over the new spec
            # Reset the device window BEFORE rebuilding leases: the fresh
            # mirrors (new bucket count) must seed from the new-geometry
            # window, not the stale one — seeding old-geometry buckets into
            # new-geometry mirrors corrupts the ring (wrong length) and
            # re-grants/withholds quota the reset already discarded.
            if self._state is not None:
                self._state = self._state._replace(
                    w1=W_.make_window(self.capacity, new),
                    occupied_next=jnp.zeros((self.capacity,), jnp.int32),
                    occupied_stamp=jnp.int64(-1),
                )
            # The shadow world's instant window carries the OLD bucket
            # geometry — rebuild it under the new spec at the next
            # compile (its stats reset with the live window's, same
            # stance as the 1s-window reset above).
            self._dirty["rollout"] = True
            self._rebuild_leases()  # mirrors carry the window geometry

    def close(self) -> None:
        """Stop background workers (pipeline, host OS sampler, cluster role)."""
        # Fast path off FIRST (one atomic swap) so no new entry takes it,
        # then drain and stop the committer; a leased handle exiting after
        # close falls back to the synchronous device path. The flag and the
        # swap happen under the lock _ensure_committer checks them under, so
        # a racing entry either installs its committer before the swap (we
        # stop that one below) or sees _closed and commits inline; stop()
        # runs OUTSIDE the lock — the background flush takes the engine
        # lock, and joining it while holding that lock would deadlock.
        with self._config_lock, self._lock:
            self._closed = True
            self._fastpath = _FastPathState({}, frozenset(), False)
            committer, self._committer = self._committer, None
        if committer is not None:
            committer.stop()
        self.stop_pipeline()
        self.system_status.stop()
        self.cluster.stop()
        self.traces.stop()
        self.slo.stop()
        fleet = self.fleet
        if fleet is not None:
            self.fleet = None
            fleet.stop()
        self.journal.close()

    @staticmethod
    def _cluster_info(rules, with_param_idx: bool = False) -> Dict[str, list]:
        """resource -> [(flowId, fallback[, paramIdx])] for remote-enforced
        (cluster mode + flowId) rules. Pod-psum cluster rules (no flowId)
        stay out: they are enforced by the local/pod check."""
        info: Dict[str, list] = {}
        for r in rules:
            cc = getattr(r, "cluster_config", None) or {}
            if getattr(r, "cluster_mode", False) and cc.get("flowId") is not None:
                entry = (int(cc["flowId"]),
                         bool(cc.get("fallbackToLocalWhenFail", True)))
                if with_param_idx:
                    entry += (int(r.param_idx),)
                info.setdefault(r.resource, []).append(entry)
        return info

    @staticmethod
    def _cluster_threshold_map(rules) -> Dict[int, tuple]:
        """flowId -> (threshold, windowIntervalMs) from the local copies
        of cluster-mode flow rules (the degraded-quota share base) —
        the SAME derivation standalone HA seats use, so every client
        computes the same share (the SEMANTICS.md bound needs that)."""
        from sentinel_tpu.cluster.rules import cluster_thresholds

        return cluster_thresholds(
            r for r in rules if getattr(r, "cluster_mode", False))

    def cluster_degraded_thresholds(self) -> Dict[int, tuple]:
        """Current flowId -> (threshold, intervalMs) map for the HA
        client's DegradedQuota (lock-free: replaced wholesale on load)."""
        return self._cluster_thresholds

    # -- LLM streaming reservations (sentinel_tpu/llm/ — ISSUE 17) ---------

    def _llm_debit(self, resource: str, tokens: int) -> int:
        """Debit ``tokens`` into the model's TPS window through the
        normal entry path, chunked to MAX_ACQUIRE_COUNT (the device
        kernels' exact-count ceiling). QPS PASS debits are
        window-permanent; the immediate exit releases only the
        concurrency channel. On a mid-chunk block the exception carries
        ``llm_debited`` — the tokens already landed — so the caller can
        refund them as expiring credit."""
        remaining = int(tokens)
        debited = 0
        try:
            while remaining > 0:
                chunk = min(remaining, C.MAX_ACQUIRE_COUNT)
                try:
                    handle = self.entry(resource, count=chunk)
                except BlockException as ex:
                    ex.llm_debited = debited
                    raise
                handle.exit()
                debited += chunk
                remaining -= chunk
        finally:
            # Land the leased commits NOW, in this sim second: an
            # injected-clock run (simulator replay) has no on_advance
            # flush hook, so a background flush after clock.advance
            # would stamp these debits into the WRONG window —
            # nondeterministically.
            self._flush_committer()
        return debited

    def stream_open(self, stream_id: str, model: str,
                    estimate_tokens: Optional[int] = None,
                    tenant: str = C.LIMIT_APP_DEFAULT):
        """Open a streaming reservation: acquire the ESTIMATED output
        budget up front as a lease that ticks down as tokens stream
        (``stream_tick``) and reconciles on ``stream_close``. Raises a
        ``BlockException`` subclass when the window (or the
        maxConcurrentStreams cap / ledger capacity) rejects the open;
        any partially-debited estimate is refunded as expiring credit,
        so a rejected open never leaks budget."""
        from sentinel_tpu.core.exceptions import FlowException
        from sentinel_tpu.llm.rules import llm_resource

        resource = llm_resource(model)
        now = self.now_ms()
        estimate = int(self._llm_default_estimate
                       if estimate_tokens is None else estimate_tokens)
        if estimate < 0:
            raise ValueError("estimate_tokens must be >= 0")
        cap = self._llm_max_streams.get(resource)
        if (cap is not None and self.streams.active(resource) >= cap) \
                or self.streams.at_capacity():
            self.streams.open_blocked += 1
            from sentinel_tpu.log.record_log import log_block

            log_block(resource, "FlowException", tenant, estimate, now)
            raise FlowException(resource)
        # The up-front reservation caps at ONE window's token budget: a
        # multi-second generation reserves its first window's worth and
        # pays the rest live as it streams across later windows (the
        # tick's overflow path) — which is also what keeps the abort
        # over-admission bound ≤ one window of tokens (SEMANTICS.md).
        budget = self._llm_window_budget.get(resource)
        reserved = estimate if budget is None \
            else min(estimate, int(budget))
        credit = self.streams.take_credit(resource, reserved, now)
        try:
            debited = self._llm_debit(resource, reserved - int(credit))
        except BlockException as ex:
            # Refund what landed (live chunks + consumed credit): the
            # tokens stay in the PASS window until it rolls, but the
            # credit makes them reusable for that long — no budget leak.
            refund = getattr(ex, "llm_debited", 0) + credit
            self.streams.add_credit(resource, refund, now)
            self.streams.open_blocked += 1
            raise
        return self.streams.open(stream_id, resource, tenant,
                                 estimate, reserved, debited, now)

    def stream_tick(self, stream_id: str, tokens: int) -> float:
        """Reconcile ``tokens`` actually streamed against the
        reservation. Output beyond the estimate debits LIVE (credit
        first), so a runaway generation pays for every token; a block
        on that overflow debit propagates as backpressure (the tokens
        already streamed stay counted). Returns the remaining reserved
        budget."""
        now = self.now_ms()
        covered, overflow = self.streams.tick(stream_id, tokens, now)
        if overflow > 0:
            lease = self.streams.get(stream_id)
            credit = self.streams.take_credit(
                lease.resource, overflow, now)
            try:
                debited = self._llm_debit(
                    lease.resource, int(overflow - int(credit)))
            except BlockException as ex:
                self.streams.record_overflow_debit(
                    getattr(ex, "llm_debited", 0))
                raise
            self.streams.record_overflow_debit(debited)
        lease = self.streams.get(stream_id)
        return lease.remaining if lease is not None else 0.0

    def stream_close(self, stream_id: str, aborted: bool = False) -> float:
        """Close (or abort) a streaming reservation. The unconsumed
        remainder returns as per-resource credit expiring at the window
        roll-off — the over-admission across an abort is bounded by the
        unreconciled estimate for at most one window interval
        (SEMANTICS.md "Streaming-reservation bound"). Returns the
        released remainder."""
        now = self.now_ms()
        lease = self.streams.get(stream_id)
        if lease is None:
            raise KeyError(f"unknown stream {stream_id!r}")
        remainder = self.streams.close(stream_id, now, aborted=aborted)
        if remainder > 0:
            self.streams.add_credit(lease.resource, remainder, now)
        return remainder

    def _refresh_signals(self, now_ms: int) -> None:
        """Fold the latest host OS sample into device state (≤ 1 Hz).

        A clock that stepped BACKWARD (NTP slew, a test re-freezing to an
        earlier epoch, a simulator timebase) must refresh rather than
        wait for real time to catch the stale stamp up — the throttle
        gates only genuinely-recent refreshes."""
        if 0 <= now_ms - self._signals_refreshed_ms < 1000:
            return
        with span(prof.ADMIT_REFRESH_SIGNALS):
            self._signals_refreshed_ms = now_ms
            self._state = self._state._replace(
                sys_signals=jnp.asarray(self.system_status.snapshot()))

    # -- public API --------------------------------------------------------

    def entry(
        self,
        resource: str,
        entry_type: int = C.EntryType.OUT,
        count: int = 1,
        args: Sequence = (),
        prioritized: bool = False,
    ) -> EntryHandle:
        """``SphU.entry``: admit or raise a ``BlockException`` subclass."""
        if count > C.MAX_ACQUIRE_COUNT:
            # The device kernels carry per-request counts through bf16
            # matmul operands, exact only up to 256 (ops/segment.py). The
            # reference's acquireCount is 1 in every shipped call site;
            # reject out-of-range counts loudly instead of silently
            # mis-admitting.
            raise ValueError(
                f"count={count} exceeds MAX_ACQUIRE_COUNT={C.MAX_ACQUIRE_COUNT}")
        ctx = ctx_mod.get_context()
        if ctx is None:
            ctx = ctx_mod.enter_auto()  # pooled per-thread default context
        if ctx.is_null:
            return EntryHandle(self, resource, ctx, -1, -1, -1,
                               entry_type == C.EntryType.IN, count, ())

        if not self.enabled:
            return EntryHandle(self, resource, ctx, -1, -1, -1,
                               entry_type == C.EntryType.IN, count, ())

        if self.slots is not None:
            # Slot mode: admission routes through the bounded hot set
            # (core/slots.py) — hot resources take the normal lease /
            # device machinery at their SLOT row, cold-tail resources
            # degrade loudly to the host lease path; nothing raises at
            # capacity.
            return self._slot_entry(resource, ctx, entry_type, count,
                                    args, prioritized)

        reg = self.registry
        if ctx.entrance_row < 0:
            ctx.entrance_row = reg.entrance_row(ctx.name)
        parent = ctx.cur_entry.dn_row if ctx.cur_entry else ctx.entrance_row
        cluster_row, dn_row, origin_row, origin_id = reg.resolve_entry(
            resource, ctx.name, ctx.origin, parent, int(entry_type))
        entry_in = entry_type == C.EntryType.IN

        if cluster_row < 0:
            # Registry full: pass-through, like the reference's chain cap.
            return EntryHandle(self, resource, ctx, -1, -1, -1, entry_in, count, ())

        params = tuple(_hash_param(a) for a in args[:MAX_PARAMS]) \
            if args else ()

        # SPI host slots (core/spi.py): a slot raising a BlockException
        # rejects the entry; the block is committed to statistics first
        # (the reference's StatisticSlot records custom-slot rejections).
        custom_ex = None
        slots = self._spi.host_slots()
        if slots:
            info = self._spi.EntryInfo(resource=resource, origin=ctx.origin,
                             count=count, entry_type=int(entry_type),
                             prioritized=prioritized, args=tuple(args),
                             context_name=ctx.name)
            for slot in slots:
                try:
                    slot.on_entry(info)
                except BlockException as ex:
                    custom_ex = ex
                    break
                except Exception:
                    # A buggy slot must not leak the auto-created context
                    # (it would shadow the thread's next ContextUtil.enter).
                    ctx_mod.auto_exit_context()
                    raise
        if custom_ex is not None:
            self._submit_entry(
                resource, cluster_row, dn_row, origin_row, origin_id,
                reg.context_id(ctx.name), count, prioritized, entry_in,
                params, skip_cluster=True, pre_blocked=True)
            ctx_mod.auto_exit_context()
            from sentinel_tpu.log.record_log import log_block

            log_block(resource, type(custom_ex).__name__, ctx.origin, count,
                      self.now_ms())
            raise custom_ex

        # Token-lease fast path (core/lease.py): eligible resources admit
        # host-side (device-exact DEFAULT math, serially exact under one
        # lock) and stream their stats to the device asynchronously —
        # sync-path latency drops from one device dispatch to microseconds.
        # (prioritized requests keep the device path: a rejected one may
        # still be granted an occupy-next-window borrow there.)
        fp = self._fastpath  # ONE read: never a torn (leases, guarded, unruled)
        lease = fp.leases.get(resource)
        fast_ok = (not slots and self._pipeline is None
                   and not self._spi.device_checkers())
        if lease is not None and not prioritized and fast_ok:
            now = self.now_ms()
            # admit() returns a BlockReason int (0 = pass): plain leases
            # run the DEFAULT window ring; widened leases (warm-up flow
            # rules, single-param resources — ROADMAP 3c) also mirror the
            # warm-up bucket and the per-value param token buckets, and
            # attribute blocks to the right family.
            block_reason = lease.admit(count, now, params)
            self._ensure_committer().add_entry(
                cluster_row, dn_row, origin_row, entry_in, count,
                block_reason == 0, block_reason)
            if block_reason:
                ctx_mod.auto_exit_context()
                ex = exception_for_reason(block_reason, resource)
                from sentinel_tpu.log.record_log import log_block

                log_block(resource, type(ex).__name__, ctx.origin, count, now)
                raise ex
            handle = EntryHandle(self, resource, ctx, cluster_row, dn_row,
                                 origin_row, entry_in, count, params,
                                 leased=True, now_ms=now)
            ctx.entry_stack.append(handle)
            return handle
        if lease is None and fast_ok and fp.unruled \
                and resource not in fp.guarded:
            # NO rules of any family on this resource (and nothing
            # RELATEs to it): always pass, stats stream via the committer
            # — the dominant real-world case never pays a device dispatch.
            self._ensure_committer().add_entry(
                cluster_row, dn_row, origin_row, entry_in, count, True)
            handle = EntryHandle(self, resource, ctx, cluster_row, dn_row,
                                 origin_row, entry_in, count, params,
                                 leased=True)
            ctx.entry_stack.append(handle)
            return handle

        if lease is not None:
            # Device path on a LEASED resource (prioritized request or the
            # pipeline mode): land pending leased commits first so the
            # device check sees them, and mirror the verdict below so the
            # lease never drifts from the device window.
            self._flush_committer()
        # Cross-process span sampling (telemetry/spans.py): only entries
        # with cluster-mode rules can cross the wire, so only those are
        # sampled — the root "entry" span records the final verdict, the
        # cluster check hangs token_request + server-side spans under it.
        trace_ctx = root_span = None
        if self._cluster_flow_info.get(resource) \
                or self._cluster_param_info.get(resource):
            trace_ctx = self.spans.sample()
        if trace_ctx is not None:
            from sentinel_tpu.telemetry.spans import Span

            root_span = Span("sentinel.entry", trace_ctx,
                             attrs={"resource": resource,
                                    "origin": ctx.origin})
        skip_cluster, pre_blocked = self._cluster_token_check(
            resource, count, prioritized, args, trace=trace_ctx)
        reason, wait_us = self._submit_entry(
            resource, cluster_row, dn_row, origin_row, origin_id,
            reg.context_id(ctx.name), count, prioritized, entry_in, params,
            skip_cluster=skip_cluster, pre_blocked=pre_blocked,
        )
        if root_span is not None:
            root_span.attrs.update(
                reason=int(reason),
                blocked=bool(reason > 0 and reason != C.BlockReason.WAIT),
                preBlocked=bool(pre_blocked))
            self.spans.record(root_span.finish())
        if reason > 0 and reason != C.BlockReason.WAIT:
            # Drop an auto-entered context with no live entries so a fresh
            # ContextUtil.enter on this thread isn't shadowed by it.
            ctx_mod.auto_exit_context()
            ex = exception_for_reason(reason, resource)
            from sentinel_tpu.log.record_log import log_block

            log_block(resource, type(ex).__name__, ctx.origin, count,
                      self.now_ms())
            raise ex
        if wait_us > 0:
            time.sleep(wait_us / 1e6)
        if lease is not None:
            # Occupy grants land in the bucket after the wait — recording
            # post-sleep stamps them there. params keep a widened lease's
            # per-value buckets honest for device-path passes.
            lease.add(count, self.now_ms(), params)

        handle = EntryHandle(self, resource, ctx, cluster_row, dn_row,
                             origin_row, entry_in, count, params)
        ctx.entry_stack.append(handle)
        return handle

    # -- slot-table admission (core/slots.py — ROADMAP 1) ------------------

    def _slot_entry(self, resource: str, ctx, entry_type: int, count: int,
                    args: Sequence, prioritized: bool) -> EntryHandle:
        """entry() in slot mode. Hot resources run the standard lease /
        device machinery at their slot row; cold-tail resources degrade
        LOUDLY: leaseable-ruled -> host-exact lease verdict, everything
        else -> counted pass (unenforced if device-only-ruled). Handles
        carry (slot, generation) so exits can never land on a reused
        slot's successor."""
        from sentinel_tpu.core.slots import COLD_GEN
        from sentinel_tpu.log.record_log import log_block

        slots = self.slots
        entry_in = entry_type == C.EntryType.IN
        params = tuple(_hash_param(a) for a in args[:MAX_PARAMS]) \
            if args else ()
        now = self.now_ms()
        # Intern the name host-side: metadata only (entry/resource type
        # for the metas view, the ops-plane name table) — never a device
        # row. Past registry capacity this degrades loudly (overflow
        # counter) and admission continues: the slot table never needs
        # the registry row to exist.
        self.registry.cluster_row(resource, int(entry_type))
        # The telescope feed drives admit/steal, so it must see EVERY
        # entry at resource grain — cold ones never reach a device batch.
        population = getattr(self, "population", None)
        if population is not None and population.enabled:
            population.observe_pairs(((resource, count),))
        cur = slots.current(resource)
        if cur is None:
            cur = slots.try_admit(resource, now)
        fp = self._fastpath
        lease = fp.leases.get(resource)

        if cur is None:
            # ---- cold tail: no slot, no raise -------------------------
            if lease is not None:
                # Host-exact verdict through the existing lease path —
                # eviction costs stats continuity, never rule fidelity.
                block_reason = lease.admit(count, now, params)
                if block_reason:
                    slots.cold_block(resource, count)
                    slots.note_verdict(resource, -1, COLD_GEN, now // 1000,
                                       "block", block_reason)
                    ctx_mod.auto_exit_context()
                    ex = exception_for_reason(block_reason, resource)
                    log_block(resource, type(ex).__name__, ctx.origin,
                              count, now)
                    raise ex
                slots.cold_pass(resource, count)
            else:
                # Device-only-ruled (guarded) cold resources pass
                # UNENFORCED behind a counter — loud, bounded, and fixed
                # by the pin machinery in steady state; plain unruled
                # cold resources just pass counted.
                unenforced = resource in fp.guarded or not fp.unruled
                slots.cold_pass(resource, count, unenforced=unenforced)
            slots.note_verdict(resource, -1, COLD_GEN, now // 1000,
                               "pass", 0)
            handle = EntryHandle(self, resource, ctx, -1, -1, -1, entry_in,
                                 count, params, now_ms=now)
            handle.slot_gen = COLD_GEN
            ctx.entry_stack.append(handle)
            return handle

        slots.hot_hits_total += 1
        slot, gen = cur
        fast_ok = (not self._spi.host_slots()
                   and not self._spi.device_checkers())
        if lease is not None and not prioritized and fast_ok:
            # ---- leased-hot: host verdict, committer commit -----------
            block_reason = lease.admit(count, now, params)
            # Committer BEFORE gate: its lazy construction takes _lock,
            # and the lock order is _lock -> gate, never the reverse.
            committer = self._ensure_committer()
            with slots.gate:
                cur2 = slots._hot.get(resource)
                if cur2 is not None:
                    # Re-translated under the gate: the enqueue can never
                    # target a slot whose tenancy already changed.
                    committer.add_entry(cur2[0], -1, -1, entry_in, count,
                                        block_reason == 0, block_reason)
                    slot, gen = cur2
            if cur2 is None:
                # Evicted between translation and enqueue: the verdict
                # stands (host-exact), the stats tally cold.
                if block_reason:
                    slots.cold_block(resource, count)
                else:
                    slots.cold_pass(resource, count)
            if block_reason:
                slots.note_verdict(resource, slot if cur2 else -1,
                                   gen if cur2 else COLD_GEN, now // 1000,
                                   "block", block_reason)
                ctx_mod.auto_exit_context()
                ex = exception_for_reason(block_reason, resource)
                log_block(resource, type(ex).__name__, ctx.origin, count,
                          now)
                raise ex
            slots.note_verdict(resource, slot if cur2 else -1,
                               gen if cur2 else COLD_GEN, now // 1000,
                               "pass", 0)
            handle = EntryHandle(self, resource, ctx, cur2[0] if cur2
                                 else -1, -1, -1, entry_in, count, params,
                                 leased=cur2 is not None, now_ms=now)
            handle.slot_gen = gen if cur2 else COLD_GEN
            ctx.entry_stack.append(handle)
            return handle

        # ---- device path at the slot row ------------------------------
        # SPI host slots keep their veto (the reference's custom-slot
        # chain): a BlockException pre-blocks the device commit.
        pre_blocked = False
        custom_ex = None
        spi_slots = self._spi.host_slots()
        if spi_slots:
            info = self._spi.EntryInfo(
                resource=resource, origin=ctx.origin, count=count,
                entry_type=int(entry_type), prioritized=prioritized,
                args=tuple(args), context_name=ctx.name)
            for spi_slot in spi_slots:
                try:
                    spi_slot.on_entry(info)
                except BlockException as ex:
                    custom_ex, pre_blocked = ex, True
                    break
                except Exception:
                    ctx_mod.auto_exit_context()
                    raise
        if lease is not None:
            # Pending leased commits must land before the device check.
            self._flush_committer()
        skip_cluster, cluster_blocked = self._cluster_token_check(
            resource, count, prioritized, args)
        oid = self.registry.origin_id(ctx.origin)
        fields = dict(
            cluster_row=-1, dn_row=-1, origin_row=-1, origin_id=oid,
            origin_named=oid in self._named_origins.get(resource, ()),
            context_id=self.registry.context_id(ctx.name), count=count,
            prioritized=prioritized, entry_in=entry_in,
            skip_cluster=skip_cluster,
            pre_blocked=pre_blocked or cluster_blocked, params=params)
        reason, wait_us, cur2 = self._slot_submit(resource, fields)
        if custom_ex is not None:
            ctx_mod.auto_exit_context()
            log_block(resource, type(custom_ex).__name__, ctx.origin,
                      count, now)
            raise custom_ex
        if cur2 is None:
            # Tenancy changed between translation and dispatch: nothing
            # committed — serve the entry as a counted cold pass.
            slots.cold_pass(resource, count)
            slots.note_verdict(resource, -1, COLD_GEN, now // 1000,
                               "pass", 0)
            handle = EntryHandle(self, resource, ctx, -1, -1, -1, entry_in,
                                 count, params, now_ms=now)
            handle.slot_gen = COLD_GEN
            ctx.entry_stack.append(handle)
            return handle
        slot, gen = cur2
        if reason > 0 and reason != C.BlockReason.WAIT:
            slots.note_verdict(resource, slot, gen, now // 1000, "block",
                               int(reason))
            ctx_mod.auto_exit_context()
            ex = exception_for_reason(reason, resource)
            log_block(resource, type(ex).__name__, ctx.origin, count,
                      self.now_ms())
            raise ex
        if wait_us > 0:
            time.sleep(wait_us / 1e6)
        if lease is not None:
            lease.add(count, self.now_ms(), params)
        slots.note_verdict(resource, slot, gen, now // 1000, "pass", 0)
        handle = EntryHandle(self, resource, ctx, slot, -1, -1, entry_in,
                             count, params, now_ms=now)
        handle.slot_gen = gen
        ctx.entry_stack.append(handle)
        return handle

    def _slot_submit(self, resource: str,
                     fields: Dict) -> Tuple[int, int, Optional[Tuple[int, int]]]:
        """Width-1 device dispatch with in-lock tenancy re-validation:
        the slot row is resolved INSIDE ``_lock`` (steal surgery holds
        it), so a commit can only land under live tenancy. Returns
        (reason, wait_us, (slot, gen) committed under) — (0, 0, None)
        when the resource went cold first (nothing committed)."""
        slots = self.slots
        with self._lock:
            cur = slots.current(resource)
            if cur is None:
                return 0, 0, None
            fields = dict(fields, cluster_row=cur[0])
            buf = make_entry_batch_np(1)
            for k, v in fields.items():
                if k == "params":
                    for i, h in enumerate(v):
                        buf["param_hash"][0, i] = h
                        buf["param_present"][0, i] = True
                else:
                    buf[k][0] = v
            try:
                dec = self._run_entry_batch_locked(EntryBatch(**buf))
            except DeviceDispatchError as ex:
                self._note_fail_open(str(ex))
                return 0, 0, cur
            return int(dec.reason[0]), int(dec.wait_us[0]), cur

    def _slot_exit(self, handle: EntryHandle, count: int) -> None:
        """_do_exit in slot mode. A resource hot NOW (any generation)
        exits at its CURRENT slot — the grafted cur_threads gauge nets
        to zero there; evicted-and-still-cold exits decrement the spill
        record and tally host-side; cold-path entries always tally
        host-side."""
        from sentinel_tpu.core.slots import COLD_GEN

        slots = self.slots
        now = self.now_ms()
        rt = min(max(0, now - handle.created_ms), C.DEFAULT_MAX_RT_MS)
        if handle.slot_gen == COLD_GEN:
            slots.cold_exit(handle.resource, count, rt, handle.error)
            ctx_mod.auto_exit_context()
            return
        committer = self._committer  # one read: close() nulls it
        if handle.leased and committer is not None:
            with slots.gate:
                cur = slots._hot.get(handle.resource)
                if cur is not None:
                    committer.add_exit(cur[0], -1, -1, handle.entry_in,
                                       count, rt, True, handle.error)
            if cur is None:
                slots.evicted_exit(handle.resource, count, rt,
                                   handle.error, now)
            ctx_mod.auto_exit_context()
            return
        with self._lock:
            cur = slots.current(handle.resource)
            if cur is not None:
                buf = make_exit_batch_np(1)
                buf["cluster_row"][0] = cur[0]
                buf["dn_row"][0] = -1
                buf["origin_row"][0] = -1
                buf["entry_in"][0] = handle.entry_in
                buf["count"][0] = count
                buf["rt_ms"][0] = rt
                buf["success"][0] = True
                buf["error"][0] = handle.error
                for i, h in enumerate(handle.params):
                    buf["param_hash"][0, i] = h
                    buf["param_present"][0, i] = True
                try:
                    self._run_exit_batch(ExitBatch(**buf))
                except DeviceDispatchError as ex:
                    self._note_fail_open(str(ex))
        if cur is None:
            slots.evicted_exit(handle.resource, count, rt, handle.error,
                               now)
        ctx_mod.auto_exit_context()

    def _device_metas(self):
        """Row-indexed meta view of the DEVICE tensor: the registry in
        fixed-capacity mode, the slot table's tenancy view in slot mode.
        Every consumer that renders device rows to names reads through
        here, so a reused slot renders as its CURRENT occupant only."""
        slots = getattr(self, "slots", None)
        return self.registry.meta if slots is None else slots.device_metas()

    def _device_resources(self) -> Dict[str, int]:
        """resource -> device row of everything with a live device row."""
        slots = getattr(self, "slots", None)
        return self.registry.resources() if slots is None \
            else slots.resources()

    def _device_row_of(self, resource: str) -> Optional[int]:
        """Current device row for one resource, or None (cold / never
        registered). Delegates to the slot table's single translation
        implementation in slot mode."""
        slots = getattr(self, "slots", None)
        if slots is None:
            return self.registry.get_cluster_row(resource)
        return slots.device_row(resource)

    def _rule_registry(self):
        """What the rule compilers resolve rows through: the registry in
        fixed-capacity mode, the slot table's facade in slot mode (rows
        are slots; a cold ruled resource compiles inert — the pin
        machinery prevents that outside pin overflow)."""
        slots = getattr(self, "slots", None)
        return self.registry if slots is None else slots.rule_registry_view()

    def _slot_pinned_resources(self) -> set:
        """Resources compiled rules target (live + rollout candidate):
        PINNED hot — the rule tensors hold their slot indices, so
        evicting one would apply its rule to the slot's successor."""
        slots = getattr(self, "slots", None)
        if slots is None:
            return set()
        pinned: set = set()

        def _add(rules) -> None:
            for r in rules:
                res = getattr(r, "resource", "")
                if res:
                    pinned.add(res)
                ref = getattr(r, "ref_resource", "")
                if ref:
                    pinned.add(ref)

        _add(self.flow_rules.get_rules())
        _add(self.degrade_rules.get_rules())
        _add(self.param_rules.get_rules())
        _add(self.authority_rules.get_rules())
        rollout = getattr(self, "rollout", None)
        spec = rollout.device_spec() if rollout is not None else None
        if spec:
            for fam in ("flow", "degrade", "authority", "param"):
                _add(spec.get(fam) or ())
        return pinned

    def _slots_sync_pins(self) -> None:
        """Config-plane hook on every rule push: admit (stealing if
        needed) every newly ruled resource BEFORE its rules compile.
        Runs OUTSIDE the config lock's critical section is fine too —
        lock order stays config -> engine -> gate throughout. If pinning
        changed occupancy, every family re-dirties: the pin admits were
        published AFTER any compile the admission surgery itself ran, so
        the next dispatch must recompile against the final mapping."""
        slots = self.slots
        if slots is None:
            return
        before = slots.admits_total
        slots.ensure_pinned(self._slot_pinned_resources(), self.now_ms())
        if slots.admits_total != before:
            with self._config_lock:
                for fam in ("flow", "degrade", "authority", "param"):
                    self._dirty[fam] = True

    def _note_fail_open(self, why: str) -> None:
        """Count + rate-limited log of an unguarded pass-through."""
        self.fail_open_count += 1
        now = self.now_ms()
        if now - self._fail_open_logged_ms >= 1000:
            self._fail_open_logged_ms = now
            import logging

            logging.getLogger("sentinel_tpu").warning(
                "entry passed UNGUARDED (%s); fail_open_count=%d",
                why, self.fail_open_count)

    def _note_cluster_fallback(self, budget_exhausted: bool = False) -> None:
        """A cluster-mode rule degraded to its local fallback this entry."""
        self.cluster_fallback_count += 1
        if budget_exhausted:
            self.cluster_budget_exhausted_count += 1

    def _cluster_token_check(self, resource, count, prioritized, args,
                             trace=None) -> Tuple[bool, bool]:
        """Remote token acquire for cluster-mode rules (``passClusterCheck``).

        Returns (skip_cluster, pre_blocked): with a healthy token client,
        OK/SHOULD_WAIT verdicts mask the cluster rules out of the local
        check; BLOCKED pre-decides the entry; FAIL-class statuses keep the
        local check live when the rule's fallbackToLocalWhenFail is set
        (= ``fallbackToLocalOrPass``). No client/no cluster rules -> local
        (or pod-psum) enforcement as-is.

        Bounded latency: ALL remote work for one entry — request waits
        AND server-hinted SHOULD_WAIT sleeps, across every cluster rule —
        shares one ``cluster_entry_budget_ms`` deadline budget. A slow,
        hung, or partitioned token server costs the data path at most the
        budget, never a socket timeout per rule; rules the budget can't
        reach degrade to the local check. The client's own breaker
        (resilience.HealthGate) makes the steady degraded state
        effectively free: once OPEN, request_token fails fast without
        touching the wire.
        """
        # Lock-free fast path: the info dicts are replaced wholesale on rule
        # load, and the common no-cluster-rules deployment returns here
        # without touching the engine lock.
        flow_info = self._cluster_flow_info.get(resource, ())
        param_info = self._cluster_param_info.get(resource, ())
        if not flow_info and not param_info:
            return False, False
        client = self.cluster.client_if_active()
        if client is None:
            return False, False
        from sentinel_tpu.cluster.constants import TokenResultStatus

        def traced_call(kind, flow_id, fn):
            """Run one remote acquire under a child span when tracing;
            the server-side span (shipped in the response TLV) joins the
            local collector so the stitched trace reads in one place."""
            if trace is None:
                return fn(None)
            from sentinel_tpu.telemetry.spans import Span, TraceContext

            child = trace.child()
            sp = Span("cluster.token_request", child,
                      parent_span_id=trace.span_id,
                      attrs={"flowId": flow_id, "kind": kind})
            tr = fn(child)
            sp.finish()
            sp.attrs["status"] = int(tr.status)
            self.spans.record(sp)
            if tr.server_span is not None:
                srv = tr.server_span
                self.spans.record_remote(
                    TraceContext(trace.trace_id, srv["spanId"]),
                    "cluster.token_service", child.span_id,
                    srv["startMs"], srv["durationUs"],
                    attrs={"flowId": flow_id})
            return tr

        budget = DeadlineBudget(self.cluster_entry_budget_ms)
        # A request launched with less than half the configured budget
        # left is breaker-NEUTRAL on timeout: a healthy server can miss a
        # starved deadline (earlier rules / SHOULD_WAIT sleeps ate it),
        # and such misses must not trip the gate.
        neutral_below_ms = self.cluster_entry_budget_ms / 2
        all_ok = True
        for flow_id, fallback in flow_info:
            remaining_ms = budget.remaining_ms()
            if remaining_ms <= 0:
                if fallback:
                    all_ok = False
                self._note_cluster_fallback(budget_exhausted=True)
                continue
            tr = traced_call("flow", flow_id, lambda t: client.request_token(
                flow_id, count, prioritized, timeout_s=remaining_ms / 1000.0,
                gate_neutral=remaining_ms < neutral_below_ms, trace=t))
            if tr.status == TokenResultStatus.OK:
                continue
            if tr.status == TokenResultStatus.SHOULD_WAIT:
                wait_ms = budget.clamp_wait_ms(tr.wait_ms)
                if wait_ms > 0:
                    time.sleep(wait_ms / 1000.0)
                continue
            if tr.status == TokenResultStatus.BLOCKED:
                return False, True
            if tr.status == TokenResultStatus.OVERLOADED:
                # Server shed this acquire before admission: degrade to
                # the local lease/fallback path IMMEDIATELY — no retry,
                # no sleep (the retry-after hint governs the failover
                # client's target backoff, not the data path: callers
                # get bounded latency, never a queued wait).
                self.cluster_overload_count += 1
                if fallback:
                    all_ok = False
                    self._note_cluster_fallback()
                continue
            if tr.status == TokenResultStatus.WRONG_SLICE:
                # The leader we reached no longer owns this flow's hash
                # slice and the client could not self-heal within this
                # entry (cluster/sharding.py): not a verdict — degrade
                # to the local check like a FAIL, separately counted so
                # a stale-map storm is visible in resilience_stats.
                self.cluster_wrong_slice_count += 1
                if fallback:
                    all_ok = False
                    self._note_cluster_fallback()
                continue
            if fallback:  # FAIL / NO_RULE / TOO_MANY_REQUEST -> local check
                all_ok = False
                self._note_cluster_fallback()
        for flow_id, fallback, param_idx in param_info:
            if param_idx >= len(args):
                continue  # no such argument: the rule does not apply
            remaining_ms = budget.remaining_ms()
            if remaining_ms <= 0:
                if fallback:
                    all_ok = False
                self._note_cluster_fallback(budget_exhausted=True)
                continue
            tr = traced_call(
                "param", flow_id, lambda t: client.request_param_token(
                    flow_id, count, [args[param_idx]],
                    timeout_s=remaining_ms / 1000.0,
                    gate_neutral=remaining_ms < neutral_below_ms, trace=t))
            if tr.status == TokenResultStatus.OK:
                continue
            if tr.status == TokenResultStatus.BLOCKED:
                return False, True
            if tr.status == TokenResultStatus.OVERLOADED:
                self.cluster_overload_count += 1
                if fallback:
                    all_ok = False
                    self._note_cluster_fallback()
                continue
            if tr.status == TokenResultStatus.WRONG_SLICE:
                self.cluster_wrong_slice_count += 1
                if fallback:
                    all_ok = False
                    self._note_cluster_fallback()
                continue
            if fallback:
                all_ok = False
                self._note_cluster_fallback()
        return all_ok, False

    def _submit_entry(self, resource, cluster_row, dn_row, origin_row,
                      origin_id, context_id, count, prioritized, entry_in,
                      params, skip_cluster=False, pre_blocked=False) -> Tuple[int, int]:
        fields = dict(
            cluster_row=cluster_row, dn_row=dn_row, origin_row=origin_row,
            origin_id=origin_id,
            origin_named=origin_id in self._named_origins.get(resource, ()),
            context_id=context_id, count=count, prioritized=prioritized,
            entry_in=entry_in, skip_cluster=skip_cluster,
            pre_blocked=pre_blocked, params=params,
        )
        pipeline = self._pipeline
        if pipeline is not None:
            ticket = pipeline.submit_entry(fields)
            # A submitted ticket is completed exactly once — by a cycle or
            # by stop()'s straggler drain — so NEVER resubmit it (that
            # would double-commit the stats). Only a None ticket (closed
            # before submit) takes the synchronous path.
            if ticket is not None:
                while not ticket.done.wait(timeout=2.0):
                    if pipeline.closed and not ticket.done.wait(timeout=2.0):
                        # Stop() drained everything it could and the ticket
                        # never surfaced (collector died mid-cycle): pass
                        # unguarded rather than risk a double commit.
                        self._note_fail_open("collector died mid-cycle")
                        return 0, 0
                if ticket.reason == -2:  # cycle error: pass-through
                    self._note_fail_open("pipeline cycle error")
                    return 0, 0
                return ticket.reason, ticket.wait_us
        with self._lock:
            buf = make_entry_batch_np(1)
            for k, v in fields.items():
                if k == "params":
                    for i, h in enumerate(v):
                        buf["param_hash"][0, i] = h
                        buf["param_present"][0, i] = True
                else:
                    buf[k][0] = v
            try:
                dec = self._run_entry_batch_locked(EntryBatch(**buf))
            except DeviceDispatchError as ex:  # backend death only
                self._note_fail_open(str(ex))
                return 0, 0  # fail open, like fallbackToLocalOrPass
            return int(dec.reason[0]), int(dec.wait_us[0])

    def _run_entry_batch_locked(self, batch: EntryBatch,
                                now_ms: Optional[int] = None,
                                sample: bool = True) -> Decisions:
        """One entry dispatch, engine lock held: the synchronous path
        and the batch API alike. ``sample=False`` (the batch API, whose
        caller reads the verdicts back itself) records the dispatch in
        the StepTimer without a sampled sync."""
        self._ensure_compiled()
        now = now_ms if now_ms is not None else self.now_ms()
        self._refresh_signals(now)
        try:
            self._state, dec = timed_call(
                self.step_timer, "entry", batch.size, self._entry_jit,
                self._state, self._rules, batch, now, sample=sample,
                occupy_timeout_ms=self._occupy_timeout_ms,
                shadow_rules=self._shadow_rules,
                canary_bps=self._canary_bps,
                canary_salt=self._canary_salt)
        except Exception as ex:  # noqa: BLE001 — dispatch only (donation)
            self._state = None  # buffers possibly consumed: restart cold
            raise DeviceDispatchError(f"entry dispatch failed: {ex!r:.200}") from ex
        # Sampled decision traces: enqueue only (the worker materializes
        # off this thread) — never blocks the step stream.
        with span(prof.ADMIT_DECISION_TRACES):
            self.traces.submit(batch, dec, now)
        with span(prof.ADMIT_POPULATION):
            self._observe_population(batch)
        return dec

    def _run_entry_batch(self, batch: EntryBatch) -> Decisions:
        with self._lock:
            return self._run_entry_batch_locked(batch)

    def _run_exit_batch(self, batch: ExitBatch, now_ms: Optional[int] = None,
                        sample: bool = True) -> None:
        with self._lock:
            self._ensure_compiled()
            now = now_ms if now_ms is not None else self.now_ms()
            try:
                self._state = timed_call(
                    self.step_timer, "exit", batch.size, self._exit_jit,
                    self._state, self._rules, batch, now, sample=sample,
                    shadow_rules=self._shadow_rules)
            except Exception as ex:  # noqa: BLE001
                self._state = None
                raise DeviceDispatchError(
                    f"exit dispatch failed: {ex!r:.200}") from ex

    def harvest_decisions(self, dec: Decisions) -> Tuple[np.ndarray,
                                                         np.ndarray]:
        """Materialize a previously dispatched cycle's verdicts (the
        pipeline's harvest phase). Runs WITHOUT the engine lock — the
        arrays belong to an already-enqueued step, so blocking here never
        stalls a concurrent dispatch. An async compute failure surfaces
        HERE (not at dispatch) under JAX's deferred execution: drop the
        state cold exactly like a dispatch-time failure — the catcher
        fails its tickets open and the next dispatch rebuilds."""
        try:
            return np.asarray(dec.reason), np.asarray(dec.wait_us)
        except Exception as ex:  # noqa: BLE001 — backend death
            with self._lock:
                self._state = None
            raise DeviceDispatchError(
                f"harvest failed: {ex!r:.200}") from ex

    # -- pipelined mode ----------------------------------------------------

    def start_pipeline(self, max_batch: int = 2048,
                       linger_s: Optional[float] = None,
                       inflight_depth: Optional[int] = None) -> "object":
        """Switch to micro-batched admission (``core/pipeline.py``):
        concurrent entries fold into one device step per cycle, with up
        to ``inflight_depth`` cycles overlapped on the device stream.
        ``linger_s``/``inflight_depth`` default to the
        ``csp.sentinel.pipeline.*`` config keys."""
        from sentinel_tpu.core.pipeline import Pipeline

        if self.slots is not None:
            raise RuntimeError(
                "pipelined admission is not supported in slot mode: the "
                "pipeline resolves rows outside the slot-tenancy "
                "re-validation protocol (run slot mode synchronous, or "
                "fixed-capacity mode pipelined)")
        with self._lock:
            if self._pipeline is None:
                self._ensure_compiled()  # compile before the loop starts
                self._pipeline = Pipeline(
                    self, max_batch, linger_s,
                    inflight_depth=inflight_depth).start()
            return self._pipeline

    def stop_pipeline(self) -> None:
        with self._pipeline_stats_lock:
            pipeline, self._pipeline = self._pipeline, None
            if pipeline is None:
                return  # a concurrent stop owns (or already folded) it
            self._retiring_pipeline = pipeline
        pipeline.stop()  # may drain for seconds — counters stay readable
        with self._pipeline_stats_lock:
            s = pipeline.stats()
            t = self._pipeline_totals
            for k in ("cycles", "batched", "harvests", "failOpenCycles",
                      "poolAllocated", "poolReused"):
                t[k] += s[k]
            t["inflightDepthMax"] = max(t["inflightDepthMax"],
                                        s["inflightDepthMax"])
            self._retiring_pipeline = None

    def pipeline_stats(self) -> Dict:
        """One ops view of pipelined admission: monotone cycle/entry
        counters across pipeline generations (a stopping pipeline keeps
        reporting through the retiring hand-off — no counter dip), the
        live in-flight depth, and the queue-wait vs device-wait split
        from the StepTimer. Never touches the engine lock."""
        with self._pipeline_stats_lock:
            t = dict(self._pipeline_totals)
            p = self._pipeline or self._retiring_pipeline
            live = p.stats() if p is not None else None
            active = self._pipeline is not None
        out = {
            "active": active,
            "cycles": t["cycles"] + (live["cycles"] if live else 0),
            "batched": t["batched"] + (live["batched"] if live else 0),
            "harvests": t["harvests"] + (live["harvests"] if live else 0),
            "failOpenCycles": t["failOpenCycles"]
            + (live["failOpenCycles"] if live else 0),
            "inflightDepth": live["inflightDepth"] if live else 0,
            "inflightDepthMax": max(
                t["inflightDepthMax"],
                live["inflightDepthMax"] if live else 0),
            "configuredDepth": live["configuredDepth"] if live else 0,
            "poolAllocated": t["poolAllocated"]
            + (live["poolAllocated"] if live else 0),
            "poolReused": t["poolReused"]
            + (live["poolReused"] if live else 0),
        }
        out.update(self.step_timer.pipeline_snapshot())
        return out

    def _do_exit(self, handle: EntryHandle, count: int) -> None:
        ctx = handle.context
        if ctx.entry_stack and ctx.entry_stack[-1] is handle:
            ctx.entry_stack.pop()
        elif handle in ctx.entry_stack:
            ctx.entry_stack.remove(handle)
        if self.slots is not None and handle.slot_gen != -1:
            # Slot mode: generation-stamped exit accounting (current-slot
            # device exit / spill-record decrement / cold tally).
            self._slot_exit(handle, count)
            return
        if handle.cluster_row < 0:
            ctx_mod.auto_exit_context()
            return
        now = self.now_ms()
        rt = max(0, now - handle.created_ms)
        slots = self._spi.host_slots()
        if slots:
            info = self._spi.EntryInfo(
                resource=handle.resource, origin=ctx.origin, count=count,
                entry_type=(C.EntryType.IN if handle.entry_in
                            else C.EntryType.OUT),
                prioritized=False, args=(), context_name=ctx.name)
            for slot in slots:
                try:
                    slot.on_exit(info, rt, handle.error)
                except Exception as ex:
                    # Exit hooks never break the real exit, but a broken
                    # slot must be observable, not silent.
                    from sentinel_tpu.log.record_log import record_log

                    record_log.warn("SPI slot %r on_exit failed: %r",
                                    type(slot).__name__, ex)
        committer = self._committer  # one read: close() nulls it concurrently
        if handle.leased and committer is not None:
            # Leased entries complete through the async committer too; the
            # device's RT/success/exception stats converge within one flush.
            # (After close() the committer is gone — fall through to the
            # synchronous device commit below rather than resurrecting it.)
            committer.add_exit(
                handle.cluster_row, handle.dn_row, handle.origin_row,
                handle.entry_in, count, min(rt, C.DEFAULT_MAX_RT_MS),
                True, handle.error)
            ctx_mod.auto_exit_context()
            return
        fields = dict(
            cluster_row=handle.cluster_row, dn_row=handle.dn_row,
            origin_row=handle.origin_row, entry_in=handle.entry_in,
            count=count, rt_ms=min(rt, C.DEFAULT_MAX_RT_MS), success=True,
            error=handle.error, params=handle.params,
        )
        pipeline = self._pipeline
        submitted = pipeline is not None and pipeline.submit_exit(fields)
        if not submitted:
            buf = make_exit_batch_np(1)
            for k, v in fields.items():
                if k == "params":
                    for i, h in enumerate(v):
                        buf["param_hash"][0, i] = h
                        buf["param_present"][0, i] = True
                else:
                    buf[k][0] = v
            try:
                self._run_exit_batch(ExitBatch(**buf))
            except DeviceDispatchError as ex:
                # An exit commit is pure statistics; an infrastructure
                # failure here must never break the caller's happy path.
                self._note_fail_open(str(ex))
        ctx_mod.auto_exit_context()

    # -- batch API (bench / pipelined engine / cluster frontends) ---------

    def check_batch(self, batch: EntryBatch, now_ms: Optional[int] = None) -> Decisions:
        with span(prof.ADMIT_CHECK_BATCH), self._lock:
            return self._run_entry_batch_locked(batch, now_ms, sample=False)

    def complete_batch(self, batch: ExitBatch, now_ms: Optional[int] = None) -> None:
        with span(prof.ADMIT_COMPLETE_BATCH):
            self._run_exit_batch(batch, now_ms, sample=False)

    # -- metric log source (ops plane) ------------------------------------

    def seal_metrics(self, now_ms: Optional[int] = None) -> List:
        """Aggregate sealed (fully elapsed) seconds from the minute window.

        Reference: ``MetricTimerListener`` walking every ClusterNode's
        minute-window buckets (SURVEY.md §3.5). Here it is one device slice:
        ``w60.counts[:, sealed_bucket_idx, :]`` for all resources at once.
        Returns ``MetricNode``s (timestamps set) for seconds not yet sealed
        by a previous call; all-idle resource-seconds are skipped.
        """
        from sentinel_tpu.core.registry import KIND_CLUSTER
        from sentinel_tpu.metrics.metric_node import MetricNode

        now = now_ms if now_ms is not None else self.now_ms()
        now_sec = now // 1000
        self._flush_committer()  # leased commits land before sealing
        with self._lock:
            self._ensure_compiled()
            first = max(self._sealed_sec + 1, now_sec - C.MINUTE_BUCKETS + 1)
            seconds = list(range(first, now_sec))
            if not seconds:
                return []
            self._sealed_sec = seconds[-1]
            # Fold any completed staged second into w60 before reading it
            # (the step stages the live second in state.sec — see ops/step).
            self._state = self._flush_jit(self._state, now)
            # Pad the bucket-index vector to a power-of-two ladder so a
            # backlog (k up to MINUTE_BUCKETS after a stall) costs at most
            # log2(60) distinct compiles ever — never a fresh XLA compile
            # inside this lock per new backlog length.
            k = len(seconds)
            k_pad = 1 << (k - 1).bit_length()
            idx_list = [s % C.MINUTE_BUCKETS for s in seconds]
            idx = jnp.asarray(idx_list + [idx_list[0]] * (k_pad - k),
                              jnp.int32)
            # One compiled program: rotate + gather + transpose to
            # [R, k, E] on device, ONE host transfer. (Measured at 10k
            # resources / 32k rows, CPU backend: the previous eager path
            # was ~3.3 s per 1 Hz cycle inside this lock; now ~50 ms —
            # dominated by MetricNode construction for active rows.)
            slices = np.asarray(self._w60_read_jit(
                self._state, jnp.asarray(now, jnp.int64), idx))[:, :k]
            threads = np.asarray(self._state.cur_threads)    # [R]
            metas = self._device_metas()
        # Vectorized active scan: only (row, second) pairs with any
        # pass/block/success/exception produce a MetricNode.
        ev = [C.MetricEvent.PASS, C.MetricEvent.BLOCK,
              C.MetricEvent.SUCCESS, C.MetricEvent.EXCEPTION]
        active_rows, active_k = np.nonzero(slices[:, :, ev].any(axis=2))
        out = []
        for row, k in zip(active_rows.tolist(), active_k.tolist()):
            m = metas[row]
            if m.kind != KIND_CLUSTER:
                continue
            t = slices[row, k]
            succ = int(t[C.MetricEvent.SUCCESS])
            out.append(MetricNode(
                timestamp=seconds[k] * 1000,
                resource=m.resource,
                pass_qps=int(t[C.MetricEvent.PASS]),
                block_qps=int(t[C.MetricEvent.BLOCK]),
                success_qps=succ,
                exception_qps=int(t[C.MetricEvent.EXCEPTION]),
                rt=float(t[C.MetricEvent.RT]) / max(succ, 1),
                occupied_pass_qps=int(t[C.MetricEvent.OCCUPIED_PASS]),
                concurrency=int(threads[row]),
                classification=m.resource_type,
            ))
        # Writers expect (second, registration) order; sort by timestamp.
        out.sort(key=lambda n: n.timestamp)
        return out

    # -- introspection (ops plane) ----------------------------------------

    def resilience_stats(self) -> Dict:
        """One ops view of every degradation channel: fail-open passes,
        cluster-rule local fallbacks, the token client's breaker, and the
        registered health probes (datasource pollers, heartbeat) with
        last-success ages. Lock-free — plain counter/snapshot reads."""
        from sentinel_tpu import resilience

        now = self.now_ms()
        out: Dict = {
            "failOpenCount": self.fail_open_count,
            "clusterFallbackCount": self.cluster_fallback_count,
            "clusterBudgetExhaustedCount": self.cluster_budget_exhausted_count,
            "clusterOverloadCount": self.cluster_overload_count,
            "clusterWrongSliceCount": self.cluster_wrong_slice_count,
            "clusterEntryBudgetMs": self.cluster_entry_budget_ms,
            "tokenClientBreaker": None,
            # Frontend overload (ISSUE 6): the embedded token server's
            # admission-queue depth/bounds and shed counters, None while
            # this instance is not a server.
            "overload": self.cluster.overload_stats(),
            # Wire path (ISSUE 11): the reactor frontend's connection /
            # coalescing / RTT snapshot, None while not a reactor server.
            "wire": self.cluster.wire_stats(),
            # Staged-rollout guardrail beside the degradation channels:
            # active candidate set, stage, and windows-to-abort — one
            # unified picture of everything currently between the live
            # ruleset and what traffic actually experiences.
            "rollout": self.rollout.guardrail_state(),
            # Cluster HA (cluster/ha.py): current role, leadership epoch,
            # failovers, degraded-quota spells — failover state without
            # scraping /metrics.
            "clusterHA": self.cluster.ha_stats(),
            # Closed-loop adaptive limiting (sentinel_tpu/adaptive/):
            # enabled/frozen state, in-flight candidate, and the
            # proposal/promotion/abort counters — what the loop is doing
            # to the rules, beside what the rules are doing to traffic.
            "adaptive": self.adaptive.guardrail_state(),
            "probes": {},
        }
        client = self.cluster.token_client
        gate = getattr(client, "health_gate", None)
        if gate is not None:
            out["tokenClientBreaker"] = gate.snapshot()
        for name, snap in resilience.health_snapshot().items():
            for key in ("lastSuccessMs", "lastCheckMs"):
                v = snap.get(key)
                if isinstance(v, (int, float)) and v > 0:
                    snap[key.replace("Ms", "AgeMs")] = max(0, now - int(v))
            out["probes"][name] = snap
        return out

    def shadow_counts(self) -> Optional[np.ndarray]:
        """Cumulative rollout counters since the candidate was installed:
        ``np.int64[S.NUM_SHADOW_COUNTERS, R]`` (would-pass/would-block per
        family beside the live outcome of the same lanes), or None when no
        candidate holds the device. The rollout manager's guardrail and
        the dashboard diff view read through this."""
        with self._lock:
            self._ensure_compiled()
            st = self._state
            if st is None or st.shadow is None:
                return None
            return np.asarray(st.shadow.counts)

    def breaker_transitions(self) -> Dict:
        """The live breakers' state-change record since their degrade
        rules were last loaded (models/degrade.py ``DegradeState``):
        ``rules``, the valid degrade rules in rule-id order, and per rule
        ``toOpen`` / ``toHalfOpen`` / ``toClosed`` int64 counts of the
        transitions into each state, ``lastOpenMs`` (last CLOSED -> OPEN)
        and ``lastCloseMs`` (last HALF_OPEN -> CLOSED) clock readings, -1
        where none happened. A read of device state: no dispatch."""
        with self._lock:
            self._ensure_compiled()
            ds = self._state.degrade
            rules = [r for r in self.degrade_rules.get_rules() if r.is_valid()]
            transitions = np.asarray(ds.transitions)
            opened = np.asarray(ds.last_open_ms)
            closed = np.asarray(ds.last_close_ms)
        n = len(rules)
        return {"rules": rules,
                "toOpen": transitions[D.TO_OPEN, :n],
                "toHalfOpen": transitions[D.TO_HALF_OPEN, :n],
                "toClosed": transitions[D.TO_CLOSED, :n],
                "lastOpenMs": opened[:n], "lastCloseMs": closed[:n]}

    def telemetry_counts(self) -> Dict[str, np.ndarray]:
        """Cumulative device telemetry since engine start, as numpy:
        ``blockByReason`` int64[NUM_ATTR_REASONS, R] per-(reason family,
        node row) block attribution, ``rtHist`` int64[NUM_RT_BUCKETS, R]
        success-RT histogram, ``totals`` int64[NUM_EVENTS, R] event
        counters. Queued leased commits are flushed first so counter
        reads are deterministic."""
        self._flush_committer()
        with self._lock:
            self._ensure_compiled()
            tele = self._state.telemetry
            sec_counts = np.asarray(self._state.sec.counts)
            block = np.asarray(tele.block_by_reason)
            hist = np.asarray(tele.rt_hist)
            totals = np.asarray(tele.totals)
            block_slot = np.asarray(tele.block_by_slot)
            stage_attr = np.asarray(tele.stage_attr)
            stage_hist = np.asarray(tele.stage_hist)
            stage_slot_bins = np.asarray(tele.stage_slot)
        # Read-side fold of the live staged second (S.telemetry_view
        # semantics, done host-side so reads never dispatch a program):
        # exact at any instant, whatever the fold cadence on device.
        return {
            "blockByReason": block + stage_attr.astype(np.int64),
            "rtHist": hist + stage_hist.astype(np.int64),
            "totals": totals + sec_counts.astype(np.int64),
            "blockBySlot": block_slot + stage_slot_bins.astype(np.int64),
        }

    def telemetry_snapshot(self) -> Dict:
        """JSON-shaped telemetry view (`telemetry` ops command parity
        with the OpenMetrics endpoint): per-resource cumulative counters,
        block attribution by reason family, and RT percentiles estimated
        from the device histogram."""
        from sentinel_tpu.core.registry import KIND_CLUSTER
        from sentinel_tpu.telemetry.attribution import (
            ATTR_REASON_NAMES, histogram_quantile)

        counts = self.telemetry_counts()
        totals = counts["totals"]
        by_reason = counts["blockByReason"]
        rt_hist = counts["rtHist"]
        active = totals.any(axis=0) | by_reason.any(axis=0)
        resources: Dict[str, Dict] = {}
        for row, meta in enumerate(self._device_metas()):
            if meta.kind != KIND_CLUSTER or row >= active.shape[0] \
                    or not active[row]:
                continue
            hist = rt_hist[:, row]
            reasons = {name: int(by_reason[ch, row])
                       for ch, name in enumerate(ATTR_REASON_NAMES)
                       if by_reason[ch, row]}
            resources[meta.resource] = {
                "passTotal": int(totals[C.MetricEvent.PASS, row]),
                "blockTotal": int(totals[C.MetricEvent.BLOCK, row]),
                "successTotal": int(totals[C.MetricEvent.SUCCESS, row]),
                "exceptionTotal": int(totals[C.MetricEvent.EXCEPTION, row]),
                "rtSumMs": int(totals[C.MetricEvent.RT, row]),
                "blockByReason": reasons,
                "rtP50Ms": round(histogram_quantile(hist, 0.50), 2),
                "rtP95Ms": round(histogram_quantile(hist, 0.95), 2),
                "rtP99Ms": round(histogram_quantile(hist, 0.99), 2),
            }
        from sentinel_tpu.telemetry.attribution import slot_bins_to_dict

        slot_out = slot_bins_to_dict(counts["blockBySlot"])
        return {
            "resources": resources,
            "counters": {
                "failOpenCount": self.fail_open_count,
                "clusterFallbackCount": self.cluster_fallback_count,
                "clusterBudgetExhaustedCount":
                    self.cluster_budget_exhausted_count,
            },
            "blockBySlot": slot_out,
            "stepTimer": self.step_timer.snapshot(),
            # Pipelined-admission health (dashboard "Pipeline" line +
            # JSON parity with the sentinel_tpu_pipeline_* gauges).
            "pipeline": self.pipeline_stats(),
            # snapshot(limit=0): the counter fields without the traces.
            "traceSampling": {
                k: v for k, v in self.traces.snapshot(limit=0).items()
                if k != "traces"
            },
            "spanSampling": {
                k: v for k, v in self.spans.snapshot(limit=0).items()
                if k != "spans"
            },
        }

    # -- flight recorder (per-second time series) --------------------------

    def _spill_flight(self, now_ms: Optional[int] = None) -> None:
        """Pull completed seconds off the device ring into the host
        history. Gathers ONLY slots newer than the last spilled stamp
        (one jitted gather, one transfer); no-op when recording is off."""
        from sentinel_tpu.telemetry.timeseries import (
            compact_second,
            second_to_dict,
        )

        now = now_ms if now_ms is not None else self.now_ms()
        fresh = []
        with self._lock:
            self._ensure_compiled()
            if self._state is not None and self._state.flight is not None:
                # Fold any completed staged second into the ring first, so
                # a read right after a second boundary sees that second.
                self._state = self._flush_jit(self._state, now)
                stamps = np.asarray(self._state.flight.stamps)
                last = self.timeseries.last_stamp_ms
                fresh = sorted(
                    (int(s), i) for i, s in enumerate(stamps.tolist())
                    if s >= 0 and s > last)
                if fresh:
                    idx_list = [i for _, i in fresh]
                    # Pad to a power-of-two ladder: a backlog of k new
                    # seconds costs at most log2(ring) distinct compiles
                    # ever (the seal_metrics discipline).
                    k = len(idx_list)
                    k_pad = 1 << (k - 1).bit_length()
                    idx = jnp.asarray(idx_list + [idx_list[0]] * (k_pad - k),
                                      jnp.int32)
                    ev, attr, hist, slot = (
                        np.asarray(x)[:k] for x in
                        self._flight_read_jit(self._state, idx))
        metas = self._device_metas()
        slots_tbl = getattr(self, "slots", None)
        for j, (stamp, _i) in enumerate(fresh):
            rec = compact_second(stamp, ev[j], attr[j], hist[j], slot[j])
            self.timeseries.append(rec)
            if slots_tbl is not None:
                # Pin the tenancy this second spilled under, so history
                # renders forever attribute a reused slot's PAST seconds
                # to the evicted occupant, never the successor.
                slots_tbl.remember_metas(stamp, metas)
            # Judgement rides the spill: each complete second feeds the
            # SLO manager's objective series + anomaly baselines (host
            # arithmetic, outside the engine lock).
            sec_dict = second_to_dict(rec, metas)
            self.slo.ingest(stamp, sec_dict["resources"])
            # Trace capture rides the same render: tees (the flight
            # recorder's trace writer, simulator/trace.py) see every
            # complete second exactly once, in stamp order. A broken tee
            # must not stall the spill (or the step stream behind it).
            for tee in list(self._flight_tees):
                try:
                    tee(sec_dict)
                except Exception:  # noqa: BLE001 — tee bugs can't stall spill
                    from sentinel_tpu.log.record_log import record_log

                    record_log.warn("flight tee %r failed; detaching", tee)
                    self.remove_flight_tee(tee)
        # Burn rules re-evaluate at the newest complete second boundary
        # on EVERY spill (even with no fresh seconds: idle decay must
        # resolve alerts without requiring new traffic).
        self.slo.evaluate(now)
        # The latency waterfall seals its staged seconds on the same
        # fold (AFTER slo.evaluate: its sentry transitions land in the
        # freshly-evaluated store). getattr for the same construction-
        # order reachability reason as adaptive below.
        waterfall = getattr(self, "waterfall", None)
        if waterfall is not None:
            waterfall.roll(now)
        # The namespace telescope folds its staged (key, count) pairs
        # into the population sketches on the same cadence (AFTER slo
        # for the same sentry-transition reason as the waterfall).
        population = getattr(self, "population", None)
        if population is not None:
            population.roll(now)
        # Slot-table rebalance rides the same cadence, AFTER the
        # telescope folded (its top-k ranking drives admit/steal) —
        # 1/s-throttled and freeze-gated inside.
        if slots_tbl is not None:
            slots_tbl.on_spill(now)
        # The adaptive loop rides the same cadence, AFTER judgement is
        # current (its freeze gate and proposal alert-gate read it).
        # Interval-gated + reentry-safe inside; getattr: _spill_flight
        # is reachable from AdaptiveLoop's own tick during construction
        # of later engine fields in exotic subclassing, and from the
        # loop's judgement refresh (which must not recurse).
        adaptive = getattr(self, "adaptive", None)
        if adaptive is not None:
            adaptive.on_spill(now)
        # Streaming-reservation hygiene rides the same cadence: leases
        # whose client vanished mid-generation evict (their remainder
        # returns as expiring credit, the abort contract), and stale
        # credit rolls off with its window.
        streams = getattr(self, "streams", None)
        if streams is not None:
            for lease in streams.evict(now):
                streams.add_credit(lease.resource, lease.remaining, now)

    def _observe_population(self, batch: EntryBatch) -> None:
        """Stage this admission batch's (row, tokens) traffic for the
        namespace telescope — a dict fold on arrays the batch already
        carries host-side, next to the existing ``traces.submit``; the
        A/B guard in tests/test_population.py pins that this adds ZERO
        device dispatches."""
        population = getattr(self, "population", None)
        if population is not None and population.enabled \
                and self.slots is None:
            # Slot mode feeds the telescope at RESOURCE grain inside
            # _slot_entry (cold entries never reach a device batch);
            # observing rows here too would double-count the hot set.
            population.observe_rows(batch.cluster_row, batch.count,
                                    self.registry.meta)

    def population_report(self, slot_budget: int = 1024,
                          now_ms: Optional[int] = None) -> Dict:
        """Admission-readiness projection for a hypothetical slot
        budget (ROADMAP item 1's sizing input): bring the telescope
        current on the fold it rides, then project hot-set hit rate,
        eviction/steal rate, and cold-tail mass from the sketches."""
        self._flush_committer()
        self._spill_flight(now_ms)
        return self.population.report(slot_budget)

    def slo_refresh(self, now_ms: Optional[int] = None) -> None:
        """Bring SLO judgement current: land leased commits, fold + spill
        any completed flight-recorder seconds (which feeds the SLO
        manager), and re-evaluate burn rules at the newest complete
        second boundary (the ``alerts``/``slo`` commands' read path)."""
        self._flush_committer()
        self._spill_flight(now_ms)

    def timeseries_view(self, resource: Optional[str] = None,
                        start_ms: Optional[int] = None,
                        end_ms: Optional[int] = None,
                        limit: Optional[int] = None,
                        offset: int = 0,
                        now_ms: Optional[int] = None) -> Dict:
        """Exact per-second telemetry series at any offset within the
        host retention (`timeseries` ops command / dashboard SSE source).

        Seconds return in CHRONOLOGICAL order; ``offset``/``limit``
        paginate newest-first (offset 0 ends at the most recent complete
        second). ``resource`` filters each second's per-resource map (a
        second with no data for it is dropped)."""
        from sentinel_tpu.telemetry.timeseries import (
            page_newest_first,
            second_to_dict,
        )

        self._flush_committer()  # leased commits land before the fold
        # ``now_ms`` drives the fold boundary: batch-API callers feeding
        # virtual clocks pass the stream's own now so the in-progress
        # second stays staged (exactness = COMPLETE seconds only).
        self._spill_flight(now_ms)
        recs = self.timeseries.query(start_ms, end_ms)
        metas = self._device_metas()
        slots_tbl = getattr(self, "slots", None)
        # Filter + paginate on the compact RECORDS, render only the
        # served page: a periodic caller (the exporter's limit=1, each
        # SSE poll) must not pay a full-history JSON render per read.
        # (In slot mode a resource's row varies per tenancy epoch, so
        # the row pre-filter only drops records where the CURRENT row
        # has no data — rendered seconds filter exactly below.)
        if resource is not None:
            row = self._device_row_of(resource)
            if slots_tbl is None:
                recs = ([r for r in recs if row in r.rows]
                        if row is not None else [])
        total = len(recs)
        recs = page_newest_first(recs, limit, offset)
        if slots_tbl is None:
            seconds = [second_to_dict(r, metas, resource) for r in recs]
        else:
            # Render each second under the tenancy it was RECORDED
            # under (the per-stamp snapshot _spill_flight pinned): a
            # reused slot's old seconds keep the evicted occupant's
            # name — the generation-leak defense for history reads.
            seconds = [
                second_to_dict(
                    r, slots_tbl.recall_metas(r.stamp_ms) or metas,
                    resource)
                for r in recs]
            if resource is not None:
                seconds = [s for s in seconds if s.get("resources")]
        return {
            "seconds": seconds,
            "total": total,
            "retainedSeconds": self.timeseries.retained(),
            "recorderSeconds": self.flight_seconds,
        }

    def explain_trace(self, resource: Optional[str] = None,
                      index: int = 0,
                      now_ms: Optional[int] = None) -> Optional[Dict]:
        """Join one sampled blocked-entry trace with the flight-recorder
        second it occurred in: what the verdict was (reason + rule slot),
        what that resource's traffic looked like THAT second (window
        occupancy, per-reason blocks), and which rules of the blocking
        family were loaded — the "why was this blocked" reconstruction,
        with no step re-run (`explain` ops command)."""
        from sentinel_tpu.datasource import converters as CV

        self.traces.drain()
        traces = self.traces.snapshot()["traces"]
        if resource is not None:
            traces = [t for t in traces if t["resource"] == resource]
        index = max(0, int(index))
        if index >= len(traces):
            return None
        tr = traces[index]
        sec_start = tr["timestamp"] - tr["timestamp"] % 1000
        view = self.timeseries_view(resource=tr["resource"],
                                    start_ms=sec_start,
                                    end_ms=sec_start + 1000,
                                    now_ms=now_ms)
        second = view["seconds"][0] if view["seconds"] else None
        fam_rules = {
            "FLOW": (self.flow_rules, CV.flow_rule_to_dict),
            "DEGRADE": (self.degrade_rules, CV.degrade_rule_to_dict),
            "AUTHORITY": (self.authority_rules, CV.authority_rule_to_dict),
            "PARAM_FLOW": (self.param_rules, CV.param_rule_to_dict),
            "SYSTEM": (self.system_rules, CV.system_rule_to_dict),
        }.get(tr["reason"])
        matched = []
        if fam_rules is not None:
            mgr, to_dict = fam_rules
            matched = [to_dict(r) for r in mgr.get_rules()
                       if getattr(r, "resource", tr["resource"])
                       == tr["resource"]]
        res_second = (second or {}).get("resources", {}).get(
            tr["resource"], {})
        return {
            "trace": tr,
            # The full second the entry fell in (None when it predates
            # retention or recording is disabled).
            "second": second,
            "occupancy": {
                "passThatSecond": res_second.get("pass", 0),
                "blockThatSecond": res_second.get("block", 0),
                "occupiedPassThatSecond": res_second.get("occupiedPass", 0),
                "windowAtTrace": tr.get("window", {}),
            },
            "verdict": {
                "reason": tr["reason"],
                "ruleSlot": tr["ruleSlot"],
                "matchedRules": matched,
            },
        }

    def why_query(self, resource: str,
                  stamp_ms: Optional[int] = None) -> Dict:
        """Forensic "why": join the flight-recorder second at
        ``stamp_ms`` with the journal records in force then — blocking
        rule + its load provenance (actor, seq, causeSeq chain), the
        rollout candidate in force, the shard map in force. The ``why``
        ops command's implementation (telemetry/journal.py)."""
        from sentinel_tpu.telemetry.journal import forensic_why

        return forensic_why(self, resource, stamp_ms)

    def row_stats(self):
        """(per-second QPS totals f32[R, E], threads int[R]) as numpy.

        Totals are normalized by the instant-window interval, so they stay
        per-second rates whatever geometry set_window_geometry picked.
        """
        self._flush_committer()
        with self._lock:
            self._ensure_compiled()
            now = self.now_ms()
            totals, threads = self._w1_read_jit(
                self._state, jnp.asarray(now, jnp.int64))
            return np.asarray(totals), np.asarray(threads)

    def row_window(self, rows):
        """``row_stats()`` for the given device rows alone: (per-second
        QPS totals f32[K, E], threads int[K]) as numpy, in ``rows``'
        order, duplicates included, at this call's clock reading.

        Gathers the rows on the device, in chunks padded to
        :data:`ROW_READ_WIDTHS` (each compiled by :meth:`warmup`). Only
        the dispatch holds the engine lock; the readback waits outside
        it, so a caller's next step is not held behind the transfer (a
        step that donates the state lets a pending read of it finish).
        A row outside ``[0, capacity)`` raises ``IndexError``.
        """
        rows = np.asarray(rows, np.int64).reshape(-1)
        if rows.size and (rows.min() < 0 or rows.max() >= self.capacity):
            raise IndexError(
                f"device rows must lie in [0, {self.capacity})")
        top = ROW_READ_WIDTHS[-1]
        reads = []
        self._flush_committer()
        with self._lock:
            self._ensure_compiled()
            now = jnp.asarray(self.now_ms(), jnp.int64)
            state = self._state
            for lo in range(0, max(rows.size, 1), top):
                chunk = rows[lo:lo + top]
                width = next(w for w in ROW_READ_WIDTHS if w >= chunk.size)
                idx = np.zeros(width, np.int32)  # pad with row 0
                idx[:chunk.size] = chunk
                reads.append((chunk.size, self._row_read_jit(
                    state.w1, state.cur_threads, now, idx)))
        return (np.concatenate([np.asarray(t)[:n] for n, (t, _) in reads]),
                np.concatenate([np.asarray(h)[:n] for n, (_, h) in reads]))

    def tree_dict(self) -> Dict:
        """Call tree rooted at machine-root (command API ``jsonTree``/``tree``).

        Reference: ``FetchJsonTreeCommandHandler`` walking ``Constants.ROOT``.
        """
        from sentinel_tpu.core.registry import ROOT_ROW

        totals, threads = self.row_stats()
        metas = self._device_metas()

        def render(row: int) -> Dict:
            m = metas[row]
            t = totals[row]
            succ = float(t[C.MetricEvent.SUCCESS])
            return {
                "id": m.row,
                "resource": m.resource,
                "threadNum": int(threads[row]),
                "passQps": float(t[C.MetricEvent.PASS]),
                "blockQps": float(t[C.MetricEvent.BLOCK]),
                "totalQps": float(t[C.MetricEvent.PASS]) + float(t[C.MetricEvent.BLOCK]),
                "successQps": succ,
                "exceptionQps": float(t[C.MetricEvent.EXCEPTION]),
                # scale cancels in the ratio: RT and SUCCESS carry the same
                # per-second normalization
                "averageRt": float(t[C.MetricEvent.RT]) / succ if succ > 0 else 0.0,
                "children": [render(c) for c in m.children],
            }

        return render(ROOT_ROW)

    def node_snapshot(self) -> Dict[str, Dict[str, float]]:
        """Per-resource live stats (command-API ``cnode`` source)."""
        self._flush_committer()
        with self._lock:
            self._ensure_compiled()
            now = self.now_ms()
            totals, threads = self._w1_read_jit(
                self._state, jnp.asarray(now, jnp.int64))
            totals = np.asarray(totals)
            threads = np.asarray(threads)
        out = {}
        for res, row in self._device_resources().items():
            t = totals[row]
            succ = float(t[C.MetricEvent.SUCCESS])
            out[res] = {
                "passQps": float(t[C.MetricEvent.PASS]),
                "blockQps": float(t[C.MetricEvent.BLOCK]),
                "successQps": succ,
                "exceptionQps": float(t[C.MetricEvent.EXCEPTION]),
                "avgRt": float(t[C.MetricEvent.RT]) / succ if succ > 0 else 0.0,
                "curThreadNum": int(threads[row]),
            }
        return out



