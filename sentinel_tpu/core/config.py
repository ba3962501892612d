"""Layered static configuration (reference: ``core:config/SentinelConfig.java``
+ ``SentinelConfigLoader.java`` — SURVEY.md §5 "Config / flag system").

Reference precedence: JVM ``-Dcsp.sentinel.*`` system properties override a
``sentinel.properties`` file (classpath or ``csp.sentinel.config.file``).
Python-native equivalent: environment variables (both the literal dotted key
and the ``CSP_SENTINEL_*`` upper-snake form) override a properties file named
by ``$CSP_SENTINEL_CONFIG_FILE`` (default ``./sentinel.properties``), which
overrides programmatic ``set_config`` defaults.

Well-known keys keep the reference's exact dotted names so existing ops
tooling / documentation transfers directly.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

# Well-known keys (reference: SentinelConfig constants).
APP_NAME = "project.name"
APP_TYPE = "csp.sentinel.app.type"
CHARSET = "csp.sentinel.charset"
SINGLE_METRIC_FILE_SIZE = "csp.sentinel.metric.file.single.size"
TOTAL_METRIC_FILE_COUNT = "csp.sentinel.metric.file.total.count"
COLD_FACTOR = "csp.sentinel.flow.cold.factor"
STATISTIC_MAX_RT = "csp.sentinel.statistic.max.rt"
SPI_CLASSLOADER = "csp.sentinel.spi.classloader"
LOG_DIR = "csp.sentinel.log.dir"
LOG_USE_PID = "csp.sentinel.log.use.pid"
CONFIG_FILE_ENV = "CSP_SENTINEL_CONFIG_FILE"
DASHBOARD_SERVER = "csp.sentinel.dashboard.server"
API_PORT = "csp.sentinel.api.port"
HEARTBEAT_INTERVAL_MS = "csp.sentinel.heartbeat.interval.ms"
HEARTBEAT_CLIENT_IP = "csp.sentinel.heartbeat.client.ip"
# Shared secret for /registry/machine (dashboard-side keys follow the
# sentinel.dashboard.* naming auth.py established); ONE constant so the
# sender and the gate cannot drift onto different keys.
HEARTBEAT_TOKEN = "sentinel.dashboard.heartbeat.token"
# Resilience layer (sentinel_tpu/resilience/ — no reference twin; the
# reference's own remote clients hard-code their retry cadences).
# Per-component retry overrides follow the pattern
# ``csp.sentinel.resilience.<component>.retry.*`` with components
# ``cluster.client`` / ``datasource`` / ``heartbeat``.
RESILIENCE_SEED = "csp.sentinel.resilience.seed"
RESILIENCE_BREAKER_FAILURES = "csp.sentinel.resilience.breaker.failure.threshold"
RESILIENCE_BREAKER_OPEN_MS = "csp.sentinel.resilience.breaker.open.ms"
RESILIENCE_BREAKER_PROBES = "csp.sentinel.resilience.breaker.half.open.probes"
RESILIENCE_ENTRY_BUDGET_MS = "csp.sentinel.resilience.cluster.entry.budget.ms"
# Cluster token-server HA (sentinel_tpu/cluster/ha.py — upstream analog:
# embedded-mode ClusterStateManager; the keys follow the reference's
# dotted naming). Every key here MUST be read through the accessors
# below and documented in docs/OPERATIONS.md (pinned by test_lint).
CLUSTER_HA_MACHINE_ID = "csp.sentinel.cluster.ha.machine.id"
CLUSTER_HA_FAILOVER_DEADLINE_MS = "csp.sentinel.cluster.ha.failover.deadline.ms"
CLUSTER_HA_RECONNECT_MS = "csp.sentinel.cluster.ha.reconnect.ms"
CLUSTER_HA_DEGRADED_DIVISOR = "csp.sentinel.cluster.ha.degraded.divisor"
CLUSTER_HA_CHECKPOINT_PATH = "csp.sentinel.cluster.ha.checkpoint.path"
CLUSTER_HA_CHECKPOINT_PERIOD_MS = "csp.sentinel.cluster.ha.checkpoint.period.ms"
CLUSTER_SHARD_SLICES = "csp.sentinel.cluster.shard.slices"
CLUSTER_SHARD_HANDOFF_PATH = "csp.sentinel.cluster.shard.handoff.path"
# Telemetry layer (sentinel_tpu/telemetry/ — no reference twin).
# profile.syncEvery: every Nth device dispatch blocks for a true
# synchronous step wall (StepTimer sampling cadence; the rest record
# enqueue wall only, keeping the steady-state stream async).
PROFILE_SYNC_EVERY = "csp.sentinel.profile.syncEvery"
# trace.sampleEvery: every Nth BLOCKED entry is retained as a decision
# trace (0 disables); trace.capacity bounds the host-side ring.
TELEMETRY_TRACE_SAMPLE_EVERY = "csp.sentinel.telemetry.trace.sampleEvery"
TELEMETRY_TRACE_CAPACITY = "csp.sentinel.telemetry.trace.capacity"
# timeseries.seconds: device-resident flight-recorder ring length in
# seconds (0 disables recording entirely — no ring tensors on device);
# timeseries.history.seconds bounds the compacted host-side spill.
TELEMETRY_TIMESERIES_SECONDS = "csp.sentinel.telemetry.timeseries.seconds"
TELEMETRY_TIMESERIES_HISTORY = \
    "csp.sentinel.telemetry.timeseries.history.seconds"
# spans.sampleEvery: every Nth cluster-checked entry carries a W3C-style
# trace context across the token-server wire (0 disables); spans.capacity
# bounds the host-side span ring on each side.
TELEMETRY_SPANS_SAMPLE_EVERY = "csp.sentinel.telemetry.spans.sampleEvery"
TELEMETRY_SPANS_CAPACITY = "csp.sentinel.telemetry.spans.capacity"
# Overload protection for the serving frontends (cluster server.py TLV
# frontend, envoy_rls, command plane — no reference twin: the reference's
# Netty server rides the JVM's unbounded executor queues). Every key MUST
# be read through the accessors below and documented in
# docs/OPERATIONS.md "Overload & backpressure" (pinned by test_lint).
OVERLOAD_QUEUE_MAX_GROUPS = "csp.sentinel.overload.queue.max.groups"
OVERLOAD_QUEUE_WATERMARK_PCT = "csp.sentinel.overload.queue.watermark.pct"
OVERLOAD_DEADLINE_MS = "csp.sentinel.overload.deadline.ms"
OVERLOAD_RETRY_AFTER_MS = "csp.sentinel.overload.retry.after.ms"
OVERLOAD_CONN_MAX_BURST = "csp.sentinel.overload.conn.max.burst"
OVERLOAD_IDLE_TIMEOUT_S = "csp.sentinel.overload.idle.timeout.s"
OVERLOAD_RLS_MAX_CONCURRENT = "csp.sentinel.overload.rls.max.concurrent"
OVERLOAD_CLIENT_BACKOFF_MS = "csp.sentinel.overload.client.backoff.ms"
# SLO engine + alerting (sentinel_tpu/slo/ — no reference twin: the
# reference surfaces raw stats and leaves judgement to external
# monitoring). Every key here MUST be read through the accessors below
# and documented in docs/OPERATIONS.md "SLOs & alerting" (pinned by
# test_lint). csp.sentinel.slo.* tunes evaluation; csp.sentinel.alert.*
# tunes the alert store + webhook fan-out.
# Pipelined admission (core/pipeline.py — no reference twin: the
# reference has no device to overlap with). Every key here MUST be read
# through the accessors below and documented in docs/OPERATIONS.md
# "Pipelined admission tuning" (pinned by test_lint).
# inflight.depth: entry cycles allowed in flight on the device stream at
# once (1 = the old synchronous ping-pong, 2 = double buffering);
# linger.us: how long a cycle waits to fold late-arriving concurrent
# callers in; pool.widths: comma-separated ladder widths to pre-allocate
# staging buffers for (empty = every ladder width up to max_batch).
PIPELINE_INFLIGHT_DEPTH = "csp.sentinel.pipeline.inflight.depth"
PIPELINE_LINGER_US = "csp.sentinel.pipeline.linger.us"
PIPELINE_POOL_WIDTHS = "csp.sentinel.pipeline.pool.widths"
# Closed-loop adaptive limiting (sentinel_tpu/adaptive/ — no reference
# twin: the reference's rules are static until pushed). Every key MUST
# be read through the accessors below and documented in
# docs/OPERATIONS.md "Adaptive limiting" (pinned by test_lint).
# enabled: autonomous actuation is OPT-IN — the loop senses nothing and
# proposes nothing until this is true (or `adaptive op=enable`).
ADAPTIVE_ENABLED = "csp.sentinel.adaptive.enabled"
ADAPTIVE_INTERVAL_SECONDS = "csp.sentinel.adaptive.interval.seconds"
ADAPTIVE_STEP_PCT = "csp.sentinel.adaptive.step.pct"
ADAPTIVE_INCREASE_PCT = "csp.sentinel.adaptive.increase.pct"
ADAPTIVE_DECREASE_PCT = "csp.sentinel.adaptive.decrease.pct"
ADAPTIVE_HYSTERESIS_PCT = "csp.sentinel.adaptive.hysteresis.pct"
ADAPTIVE_COOLDOWN_SECONDS = "csp.sentinel.adaptive.cooldown.seconds"
ADAPTIVE_FREEZE_STALE_SECONDS = "csp.sentinel.adaptive.freeze.stale.seconds"
ADAPTIVE_ABORT_BACKOFF_SECONDS = "csp.sentinel.adaptive.abort.backoff.seconds"
ADAPTIVE_SHADOW_SECONDS = "csp.sentinel.adaptive.shadow.seconds"
ADAPTIVE_CANARY_SECONDS = "csp.sentinel.adaptive.canary.seconds"
ADAPTIVE_CANARY_BPS = "csp.sentinel.adaptive.canary.bps"
ADAPTIVE_HISTORY_CAPACITY = "csp.sentinel.adaptive.history.capacity"
# Wire-path ingestion (cluster/reactor.py — no reference twin: the
# reference rides Netty's event loop; this is the Python-native analog).
# Every key here MUST be read through the accessors below and documented
# in docs/OPERATIONS.md "Wire-path tuning" (pinned by test_lint).
# reactor.enabled: the selectors-based multiplexing frontend (false =
# legacy thread-per-connection socketserver, kept for wire-compat drills);
# coalesce.max.batch: max requests folded into one fused-step group;
# inflight.depth: fused wire batches allowed on the device stream at once
# (the PR 8 dispatch/harvest split applied to the token path);
# outbuf.max.bytes: per-connection reply backlog bound — past it the
# connection stops being read and freshly parsed requests shed OVERLOADED;
# read.chunk.bytes: recv size per readable socket per loop cycle;
# workers: compute worker pool for non-FLOW frames (ENTRY/EXIT/PARAM).
WIRE_REACTOR_ENABLED = "csp.sentinel.wire.reactor.enabled"
WIRE_COALESCE_MAX_BATCH = "csp.sentinel.wire.coalesce.max.batch"
WIRE_INFLIGHT_DEPTH = "csp.sentinel.wire.inflight.depth"
WIRE_OUTBUF_MAX_BYTES = "csp.sentinel.wire.outbuf.max.bytes"
WIRE_READ_CHUNK_BYTES = "csp.sentinel.wire.read.chunk.bytes"
WIRE_WORKERS = "csp.sentinel.wire.workers"
WIRE_RLS_BATCHED = "csp.sentinel.wire.rls.batched"
# Latency waterfall (sentinel_tpu/telemetry/waterfall.py — ISSUE 18).
# Every key MUST be read through the accessors below and documented in
# docs/OPERATIONS.md "Latency waterfall & saturation probe" (pinned by
# test_lint). enabled: per-request stage stamping on the wire path;
# history.seconds: sealed per-second records retained for the
# `waterfall` command; exemplar.every: sampling cadence among TRACED
# requests (outliers are always candidates); sentry.*: the per-stage
# budget regression sentry riding the SLO burn windows.
WATERFALL_ENABLED = "csp.sentinel.waterfall.enabled"
WATERFALL_HISTORY_SECONDS = "csp.sentinel.waterfall.history.seconds"
WATERFALL_EXEMPLAR_EVERY = "csp.sentinel.waterfall.exemplar.every"
WATERFALL_SENTRY_ENABLED = "csp.sentinel.waterfall.sentry.enabled"
WATERFALL_SENTRY_MIN_EVENTS = "csp.sentinel.waterfall.sentry.min.events"
# Namespace telescope (sentinel_tpu/telemetry/population.py — ISSUE
# 19). Every key MUST be read through the accessors below and
# documented in docs/OPERATIONS.md "Namespace telescope & admission
# readiness" (pinned by test_lint). enabled: population sensing on the
# spill fold; topk: Space-Saving summary size (error floor total/k);
# cms.*: count-min geometry (cold-tail error (e/width)*total at
# confidence 1-e^-depth); hll.precision: global cardinality registers
# (2^p, stderr 1.04/sqrt(2^p)); slice.precision: the cheaper per-slice
# and per-window register sets; window.seconds: churn-window length;
# churn.history: sealed windows retained; baseline.*: the EWMA
# cardinality-growth alarm (z-score vs prior baseline).
POPULATION_ENABLED = "csp.sentinel.population.enabled"
POPULATION_TOPK = "csp.sentinel.population.topk"
POPULATION_CMS_DEPTH = "csp.sentinel.population.cms.depth"
POPULATION_CMS_WIDTH = "csp.sentinel.population.cms.width"
POPULATION_HLL_PRECISION = "csp.sentinel.population.hll.precision"
POPULATION_SLICE_PRECISION = "csp.sentinel.population.slice.precision"
POPULATION_WINDOW_SECONDS = "csp.sentinel.population.window.seconds"
POPULATION_CHURN_HISTORY = "csp.sentinel.population.churn.history"
POPULATION_BASELINE_ALPHA = "csp.sentinel.population.baseline.alpha"
POPULATION_BASELINE_ZSCORE = "csp.sentinel.population.baseline.zscore"
# Dynamic slot-table admission (core/slots.py — ROADMAP item 1). Every
# key here MUST be read through the accessors below and documented in
# docs/OPERATIONS.md "Slot-table admission" (pinned by test_lint).
# budget: device slot-table size (0 = off: registry rows == device rows,
# the pre-slot engine); registry.capacity: the host name-table size in
# slot mode (the namespace the engine can serve, hot + cold);
# max.steals: steal ceiling per rebalance cycle (anti-thrash);
# hysteresis.pct: a challenger must beat the victim's observed rate by
# this margin before a steal; spill.max: spilled-row records retained
# host-side (LRU past it — a dropped record rehydrates cold, counted);
# stale.seconds: telescope staleness horizon for the freeze gate.
SLOTS_BUDGET = "csp.sentinel.slots.budget"
SLOTS_REGISTRY_CAPACITY = "csp.sentinel.slots.registry.capacity"
SLOTS_MAX_STEALS = "csp.sentinel.slots.max.steals"
SLOTS_HYSTERESIS_PCT = "csp.sentinel.slots.hysteresis.pct"
SLOTS_SPILL_MAX = "csp.sentinel.slots.spill.max"
SLOTS_STALE_SECONDS = "csp.sentinel.slots.stale.seconds"
# Trace-replay simulator (sentinel_tpu/simulator/ — no reference twin:
# the reference has no offline evaluation story). Every key here MUST be
# read through the accessors below and documented in docs/OPERATIONS.md
# "Trace capture & replay" (pinned by test_lint).
# epoch.ms: the simulated timebase origin for traces that carry none —
# deliberately far from the wall clock so an accidental ambient clock
# read in a replayed path produces instantly-wrong seconds;
# max.batch: widest fused-step ladder width one simulated second's
# demand is chunked into; drill.max.seconds: cap on the `sim op=run`
# command's synchronous drill replays (offline suites use the library).
SIM_EPOCH_MS = "csp.sentinel.sim.epoch.ms"
SIM_MAX_BATCH = "csp.sentinel.sim.max.batch"
SIM_DRILL_MAX_SECONDS = "csp.sentinel.sim.drill.max.seconds"
# Chaos campaign engine (sentinel_tpu/chaos/ — no reference twin: the
# reference has no fault-schedule search story). Every key MUST be read
# through the accessors below and documented in docs/OPERATIONS.md
# "Chaos campaign" (pinned by test_lint).
# epoch.ms: the campaign timebase origin — like the simulator's,
# deliberately far from any plausible wall clock (TWO days past 0, so
# chaos and sim stamps are also distinguishable from each other);
# episodes: default campaign length; seconds.per.episode: driven
# seconds per episode; max.faults: schedule-size cap per episode;
# max.episodes: bound on the synchronous `chaos op=run` ops command.
CHAOS_EPOCH_MS = "csp.sentinel.chaos.epoch.ms"
CHAOS_EPISODES = "csp.sentinel.chaos.episodes"
CHAOS_SECONDS_PER_EPISODE = "csp.sentinel.chaos.seconds.per.episode"
CHAOS_MAX_FAULTS = "csp.sentinel.chaos.max.faults"
CHAOS_MAX_EPISODES = "csp.sentinel.chaos.max.episodes"
# Control-plane audit journal (telemetry/journal.py — no reference
# twin: the reference's rule pushes leave no durable record). Every key
# MUST be read through the accessors below and documented in
# docs/OPERATIONS.md "Fleet observability & forensics" (pinned by
# test_lint). path: empty = in-memory tail only (no file); capacity:
# bounded in-memory tail the `journal` command serves; rotate.bytes:
# fsync'd segment rotation threshold for the JSONL file.
JOURNAL_PATH = "csp.sentinel.journal.path"
JOURNAL_CAPACITY = "csp.sentinel.journal.capacity"
JOURNAL_ROTATE_BYTES = "csp.sentinel.journal.rotate.bytes"
# Fleet telemetry federation (telemetry/fleet.py — the mesh-wide half
# of the observability plane). Every key MUST be read through the
# accessors below and documented in docs/OPERATIONS.md "Fleet
# observability & forensics" (pinned by test_lint). history.seconds:
# fleet-wide per-second records the collector retains; stale.ms: how
# old a leader's newest complete second may be before it reports
# stale; max.seconds: complete seconds one fleetTelemetry reply page
# carries (the cursor loops for more).
FLEET_HISTORY_SECONDS = "csp.sentinel.fleet.history.seconds"
FLEET_STALE_MS = "csp.sentinel.fleet.stale.ms"
FLEET_MAX_SECONDS = "csp.sentinel.fleet.max.seconds"
# Self-driving shard rebalancer (cluster/rebalance.py — ISSUE 16).
# Every key MUST be read through the accessors below and documented in
# docs/OPERATIONS.md "Self-driving rebalancing" (pinned by test_lint).
# max.slices.per.epoch: hard movement cap per applied plan;
# cooldown.ms: per-slice quiet period stamped at apply (direction
# flips wait 2x); skew.deadband.pct: relative leader-load spread below
# which no plan is proposed; stale.ms: fleet-series age past which the
# rebalancer freezes; abort.backoff.ms: quiet period after a vetoed
# certification; certify.seconds: driven seconds per certification
# episode; window.seconds: fleet-series fold window for sensing.
REBALANCE_MAX_SLICES = "csp.sentinel.rebalance.max.slices.per.epoch"
REBALANCE_COOLDOWN_MS = "csp.sentinel.rebalance.cooldown.ms"
REBALANCE_DEADBAND_PCT = "csp.sentinel.rebalance.skew.deadband.pct"
REBALANCE_STALE_MS = "csp.sentinel.rebalance.stale.ms"
REBALANCE_BACKOFF_MS = "csp.sentinel.rebalance.abort.backoff.ms"
REBALANCE_CERTIFY_SECONDS = "csp.sentinel.rebalance.certify.seconds"
REBALANCE_WINDOW_SECONDS = "csp.sentinel.rebalance.window.seconds"
# LLM admission (sentinel_tpu/llm/ — ISSUE 17). Every key MUST be read
# through the accessors below and documented in docs/OPERATIONS.md
# "LLM admission & streaming reservations" (pinned by test_lint).
# max.streams: streaming-reservation ledger capacity (opens beyond it
# block — bounded host state, never an unbounded dict);
# idle.evict.ms: a lease untouched this long is an abandoned generation
# and evicts on the spill cadence (remainder returns as credit);
# default.estimate.tokens: the up-front reservation when the caller
# gives no estimate (a typical completion's output budget).
LLM_MAX_STREAMS = "csp.sentinel.llm.max.streams"
LLM_IDLE_EVICT_MS = "csp.sentinel.llm.idle.evict.ms"
LLM_DEFAULT_ESTIMATE_TOKENS = "csp.sentinel.llm.default.estimate.tokens"
SLO_BASELINE_ALPHA = "csp.sentinel.slo.baseline.alpha"
SLO_BASELINE_ZSCORE = "csp.sentinel.slo.baseline.zscore"
SLO_BASELINE_WARMUP_SECONDS = "csp.sentinel.slo.baseline.warmup.seconds"
SLO_BASELINE_MIN_EVENTS = "csp.sentinel.slo.baseline.min.events"
SLO_ROLLOUT_ABORT = "csp.sentinel.slo.rollout.abort"
ALERT_HISTORY_CAPACITY = "csp.sentinel.alert.history.capacity"
ALERT_WEBHOOK_URLS = "csp.sentinel.alert.webhook.urls"
ALERT_WEBHOOK_TIMEOUT_MS = "csp.sentinel.alert.webhook.timeout.ms"
ALERT_WEBHOOK_RETRIES = "csp.sentinel.alert.webhook.retries"

DEFAULT_CHARSET = "utf-8"
DEFAULT_SINGLE_METRIC_FILE_SIZE = 50 * 1024 * 1024
DEFAULT_TOTAL_METRIC_FILE_COUNT = 6
DEFAULT_COLD_FACTOR = 3
DEFAULT_STATISTIC_MAX_RT = 4900
DEFAULT_API_PORT = 8719
DEFAULT_HEARTBEAT_INTERVAL_MS = 10_000
DEFAULT_APP_NAME = "sentinel-tpu-app"
DEFAULT_RESILIENCE_BREAKER_FAILURES = 3
DEFAULT_RESILIENCE_BREAKER_OPEN_MS = 5_000
DEFAULT_RESILIENCE_BREAKER_PROBES = 1
# Aggregate remote-wait bound per entry(): well under the 2s request
# timeout, so a degraded token server costs the data path a bounded,
# configured amount — never a socket timeout per cluster rule.
DEFAULT_RESILIENCE_ENTRY_BUDGET_MS = 500
# Failover must complete well inside the data path's patience (the 2s
# request timeout): the client walks its server list and, past this
# deadline with no leader reachable, enters degraded-quota mode.
DEFAULT_CLUSTER_HA_FAILOVER_DEADLINE_MS = 3_000
# Inner reconnect cadence of the failover client — snappier than the
# plain client's 2s so a standby promotion lands inside the deadline.
DEFAULT_CLUSTER_HA_RECONNECT_MS = 250
# Degraded-quota share divisor when the cluster map lists no clients:
# 1 = the full global threshold locally (single-client deployments).
# Fleets MUST list clients in the map (or set this) for the
# sum-of-shares <= global-threshold bound to hold (docs/SEMANTICS.md).
DEFAULT_CLUSTER_HA_DEGRADED_DIVISOR = 1
DEFAULT_CLUSTER_HA_CHECKPOINT_PERIOD_MS = 5_000
# Sharded multi-leader ring size (cluster/sharding.py): slices per
# cluster when a shard map doesn't say otherwise. FIXED for a cluster's
# lifetime — ownership rebalances, the ring never resizes (resizing
# would remap every flow's slice and void the per-slice fencing bound).
DEFAULT_CLUSTER_SHARD_SLICES = 64
DEFAULT_PROFILE_SYNC_EVERY = 64
DEFAULT_TELEMETRY_TRACE_SAMPLE_EVERY = 64
DEFAULT_TELEMETRY_TRACE_CAPACITY = 256
# ~128 s on device (≈ int32 ring of [S, E+A+H, R] rows-minor slices);
# at the default 4096-row capacity that is ~55 MB of device memory —
# size it down (or to 0) on memory-tight deployments, up for longer
# on-device lookback (docs/OPERATIONS.md "Tracing & flight recorder").
DEFAULT_TELEMETRY_TIMESERIES_SECONDS = 128
DEFAULT_TELEMETRY_TIMESERIES_HISTORY = 1024
DEFAULT_TELEMETRY_SPANS_SAMPLE_EVERY = 64
DEFAULT_TELEMETRY_SPANS_CAPACITY = 256
# Overload defaults. The queue bound is in GROUPS (one pipelined client
# burst = one group); at the 1024-request per-connection burst cap that
# is a worst case of ~524k queued requests — the point is bounding queue
# WAIT (each group drains in one linger tick), not memory. The watermark
# sheds before the hard bound so admission degrades gradually; the
# deadline matches the default client request timeout (2s) — a group
# older than that is dead weight the client already gave up on.
DEFAULT_OVERLOAD_QUEUE_MAX_GROUPS = 512
DEFAULT_OVERLOAD_QUEUE_WATERMARK_PCT = 80
DEFAULT_OVERLOAD_DEADLINE_MS = 2_000
DEFAULT_OVERLOAD_RETRY_AFTER_MS = 100
DEFAULT_OVERLOAD_CONN_MAX_BURST = 1024
DEFAULT_OVERLOAD_IDLE_TIMEOUT_S = 300
DEFAULT_OVERLOAD_RLS_MAX_CONCURRENT = 64
DEFAULT_OVERLOAD_CLIENT_BACKOFF_MS = 250
# Pipeline defaults. Depth 2 = classic double buffering: stage N+1 and
# harvest N-1 while N computes; deeper only helps when the device step
# is much longer than host staging. 100µs linger
# matches the historical collector default.
DEFAULT_PIPELINE_INFLIGHT_DEPTH = 2
DEFAULT_PIPELINE_LINGER_US = 100
# Wire-path defaults. Coalesce cap 1024 matches the conn burst cap (one
# fused step per reactor cycle, padded on the jit ladder); depth 2 =
# classic double buffering on the token acquire stream; 1 MiB outbuf is
# ~60k flow replies — a consumer that far behind is dead, not slow.
DEFAULT_WIRE_COALESCE_MAX_BATCH = 1024
DEFAULT_WIRE_INFLIGHT_DEPTH = 2
DEFAULT_WIRE_OUTBUF_MAX_BYTES = 1_048_576
DEFAULT_WIRE_READ_CHUNK_BYTES = 131_072
DEFAULT_WIRE_WORKERS = 4
# Waterfall defaults. 10 minutes of sealed seconds covers the widest
# sentry burn window (300s) with drill headroom; exemplar cadence 8
# keeps exemplar work off the common path while a busy second still
# lands several; 50 events/s floors the sentry the same way burn-rate
# objectives floor theirs (a trickle can't page).
DEFAULT_WATERFALL_HISTORY_SECONDS = 600
DEFAULT_WATERFALL_EXEMPLAR_EVERY = 8
DEFAULT_WATERFALL_SENTRY_MIN_EVENTS = 50
# Namespace-telescope defaults. k=64 keeps the top-k ring exact for
# Zipf hot sets while a full fleet page stays well under the 64 KB
# entity budget; CMS 4x512 bounds cold-tail error to ~0.53% of total
# at 98% confidence; HLL p=11 (2 KB) gives 2.3% cardinality stderr,
# p=7 (128 B) per slice/window gives 9% — churn and placement signals,
# not billing; 10 s windows x 360 retained = one hour of churn series;
# the baseline alarm uses the SLO anomaly defaults (alpha 0.2, z 4).
DEFAULT_POPULATION_TOPK = 64
DEFAULT_POPULATION_CMS_DEPTH = 4
DEFAULT_POPULATION_CMS_WIDTH = 512
DEFAULT_POPULATION_HLL_PRECISION = 11
DEFAULT_POPULATION_SLICE_PRECISION = 7
DEFAULT_POPULATION_WINDOW_SECONDS = 10
DEFAULT_POPULATION_CHURN_HISTORY = 360
DEFAULT_POPULATION_BASELINE_ALPHA = 0.2
DEFAULT_POPULATION_BASELINE_ZSCORE = 4.0
# Slot-table defaults. budget 0 keeps the slot table OFF unless asked
# for (the unbounded engine is the compatibility default); the 16384
# registry ceiling matches the fixed-tensor cap the slot table exists
# to outgrow — in slot mode that many NAMES fit host-side while only
# `budget` rows are device-resident; 8 steals/cycle bounds eviction
# churn to 8 Hz at the 1 Hz fold; 20% hysteresis keeps rank jitter in
# the telescope's error bars from thrashing slots; 4096 spill records
# ≈ a few MB of host window rows; a telescope silent for 30 s is a
# stale feed — steals freeze rather than act on dead rankings.
DEFAULT_SLOTS_BUDGET = 0
DEFAULT_SLOTS_REGISTRY_CAPACITY = 16384
DEFAULT_SLOTS_MAX_STEALS = 8
DEFAULT_SLOTS_HYSTERESIS_PCT = 20.0
DEFAULT_SLOTS_SPILL_MAX = 4096
DEFAULT_SLOTS_STALE_SECONDS = 30
# Simulator defaults. One day past epoch 0 keeps simulated stamps far
# from any plausible wall clock (the replay-honesty canary); 512 keeps
# the per-second chunking on a mid-ladder width (fewer distinct XLA
# shapes per replay); 300 bounds the ops-command drill.
DEFAULT_SIM_EPOCH_MS = 86_400_000
DEFAULT_SIM_MAX_BATCH = 512
DEFAULT_SIM_DRILL_MAX_SECONDS = 300
# Chaos defaults. Two days past epoch 0 keeps campaign stamps far from
# the wall clock AND from the simulator's one-day origin; 25 episodes
# is the ops-command default (the bench phase runs 200); 12 driven
# seconds covers crash -> degraded -> rebalance -> recovery inside one
# episode; 6 faults bounds schedule size (ddmin cost is schedule-bound).
DEFAULT_CHAOS_EPOCH_MS = 172_800_000
DEFAULT_CHAOS_EPISODES = 25
DEFAULT_CHAOS_SECONDS_PER_EPISODE = 12
DEFAULT_CHAOS_MAX_FAULTS = 6
DEFAULT_CHAOS_MAX_EPISODES = 50
# SLO defaults. alpha=0.2 ≈ a ~5-second effective memory on the EWMA
# baseline mean (fast enough to track diurnal drift, slow enough that a
# one-second spike cannot hide itself); z>=4 on a per-second signal
# keeps the false-positive rate negligible; 30 warmup seconds of traffic
# before a resource's baseline may vote.
DEFAULT_SLO_BASELINE_ALPHA = 0.2
DEFAULT_SLO_BASELINE_ZSCORE = 4.0
DEFAULT_SLO_BASELINE_WARMUP_SECONDS = 30
DEFAULT_SLO_BASELINE_MIN_EVENTS = 10
DEFAULT_ALERT_HISTORY_CAPACITY = 256
DEFAULT_ALERT_WEBHOOK_TIMEOUT_MS = 2_000
DEFAULT_ALERT_WEBHOOK_RETRIES = 3
# Adaptive-limiting defaults. The loop evaluates once per interval on
# the once-per-second fold ride; one actuation moves a threshold at
# most step.pct of its current value; cooldown keeps a promoted change
# untouchable long enough for the flight recorder to show its effect
# (and the flip guard holds 2x that across the target — the
# no-oscillation invariant, docs/SEMANTICS.md "Actuation safety
# envelope"); freeze.stale.seconds is how old the newest complete
# recorded second may be before the loop refuses to trust its senses;
# abort.backoff.seconds is the quiet period after ANY auto-abort.
DEFAULT_ADAPTIVE_INTERVAL_SECONDS = 5
DEFAULT_ADAPTIVE_STEP_PCT = 0.25
DEFAULT_ADAPTIVE_INCREASE_PCT = 0.10
DEFAULT_ADAPTIVE_DECREASE_PCT = 0.30
DEFAULT_ADAPTIVE_HYSTERESIS_PCT = 0.10
DEFAULT_ADAPTIVE_COOLDOWN_SECONDS = 30
DEFAULT_ADAPTIVE_FREEZE_STALE_SECONDS = 5
DEFAULT_ADAPTIVE_ABORT_BACKOFF_SECONDS = 120
DEFAULT_ADAPTIVE_SHADOW_SECONDS = 5
DEFAULT_ADAPTIVE_CANARY_SECONDS = 5
DEFAULT_ADAPTIVE_CANARY_BPS = 1_000
DEFAULT_ADAPTIVE_HISTORY_CAPACITY = 256
# Journal defaults. The in-memory tail bounds what the `journal`
# command serves without file reads; 4 MiB per segment keeps three
# rotated segments (~12 MiB) of control-plane history — mutations are
# rare, so that is weeks of causality at production rates.
DEFAULT_JOURNAL_CAPACITY = 512
DEFAULT_JOURNAL_ROTATE_BYTES = 4 * 1024 * 1024
# Fleet defaults. 512 retained fleet seconds ≈ 8.5 minutes of exact
# mesh-wide series; a leader 5s behind the collector clock is stale
# (the spill cadence is 1 Hz — 5 missed spills means the leader, not
# the schedule); 16 seconds per reply page keeps the payload well
# under the u16 frame bound at realistic resource counts.
DEFAULT_FLEET_HISTORY_SECONDS = 512
DEFAULT_FLEET_STALE_MS = 5_000
DEFAULT_FLEET_MAX_SECONDS = 16
# Rebalancer defaults. 4 slices/epoch keeps any one plan's blast
# radius under 1/16th of the default 64-slice ring; the 60s per-slice
# cooldown means a slice's post-move load shows up in the fleet series
# before it may be re-judged (the adaptive loop's discipline applied
# to placement); 25% relative spread is the noise floor observed on
# the loopback mesh; certification replays 8 driven seconds — past
# the 1.5s failover deadline plus handoff, under the chaos cadence.
DEFAULT_REBALANCE_MAX_SLICES = 4
DEFAULT_REBALANCE_COOLDOWN_MS = 60_000
DEFAULT_REBALANCE_DEADBAND_PCT = 0.25
DEFAULT_REBALANCE_STALE_MS = 10_000
DEFAULT_REBALANCE_BACKOFF_MS = 120_000
DEFAULT_REBALANCE_CERTIFY_SECONDS = 8
DEFAULT_REBALANCE_WINDOW_SECONDS = 30
# LLM-admission defaults. 4096 concurrent reservations bounds ledger
# memory (~100 KiB) far above any single-host serving fan-out; 30s idle
# means a generation that streamed nothing for 30 seconds lost its
# client (SSE keep-alives tick far faster); 128 tokens is a typical
# completion budget when the caller estimates nothing.
DEFAULT_LLM_MAX_STREAMS = 4096
DEFAULT_LLM_IDLE_EVICT_MS = 30_000
DEFAULT_LLM_DEFAULT_ESTIMATE_TOKENS = 128


def _env_key(key: str) -> str:
    return key.upper().replace(".", "_").replace("-", "_")


def _parse_properties(text: str) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith(("#", "!")):
            continue
        for sep in ("=", ":"):
            if sep in line:
                k, _, v = line.partition(sep)
                out[k.strip()] = v.strip()
                break
    return out


class SentinelConfig:
    """Process-wide key/value config with the reference's precedence."""

    def __init__(self):
        self._lock = threading.RLock()
        self._config: Dict[str, str] = {}
        self._loaded = False

    def _ensure_loaded(self):
        if self._loaded:
            return
        with self._lock:
            if self._loaded:
                return
            self._loaded = True
            defaults = {
                CHARSET: DEFAULT_CHARSET,
                SINGLE_METRIC_FILE_SIZE: str(DEFAULT_SINGLE_METRIC_FILE_SIZE),
                TOTAL_METRIC_FILE_COUNT: str(DEFAULT_TOTAL_METRIC_FILE_COUNT),
                COLD_FACTOR: str(DEFAULT_COLD_FACTOR),
                STATISTIC_MAX_RT: str(DEFAULT_STATISTIC_MAX_RT),
                API_PORT: str(DEFAULT_API_PORT),
                HEARTBEAT_INTERVAL_MS: str(DEFAULT_HEARTBEAT_INTERVAL_MS),
            }
            for k, v in defaults.items():
                self._config.setdefault(k, v)
            path = os.environ.get(CONFIG_FILE_ENV, "sentinel.properties")
            try:
                with open(path, "r", encoding="utf-8") as f:
                    self._config.update(_parse_properties(f.read()))
            except OSError:
                pass
            # Env overrides: literal dotted key or CSP_SENTINEL_* form.
            for key in list(self._config) + [APP_NAME, DASHBOARD_SERVER, LOG_DIR]:
                for env in (key, _env_key(key)):
                    if env in os.environ:
                        self._config[key] = os.environ[env]

    def get(self, key: str, default: Optional[str] = None) -> Optional[str]:
        self._ensure_loaded()
        with self._lock:
            for env in (key, _env_key(key)):
                if env in os.environ:
                    return os.environ[env]
            return self._config.get(key, default)

    def set(self, key: str, value: str) -> None:
        self._ensure_loaded()
        with self._lock:
            self._config[key] = str(value)

    def get_int(self, key: str, default: int) -> int:
        v = self.get(key)
        try:
            return int(v) if v is not None else default
        except ValueError:
            return default

    def get_float(self, key: str, default: float) -> float:
        v = self.get(key)
        try:
            return float(v) if v is not None else default
        except ValueError:
            return default

    # -- well-known accessors ---------------------------------------------

    def app_name(self) -> str:
        return self.get(APP_NAME) or DEFAULT_APP_NAME

    def app_type(self) -> int:
        return self.get_int(APP_TYPE, 0)

    def charset(self) -> str:
        return self.get(CHARSET) or DEFAULT_CHARSET

    def single_metric_file_size(self) -> int:
        return self.get_int(SINGLE_METRIC_FILE_SIZE, DEFAULT_SINGLE_METRIC_FILE_SIZE)

    def total_metric_file_count(self) -> int:
        return self.get_int(TOTAL_METRIC_FILE_COUNT, DEFAULT_TOTAL_METRIC_FILE_COUNT)

    def statistic_max_rt(self) -> int:
        return self.get_int(STATISTIC_MAX_RT, DEFAULT_STATISTIC_MAX_RT)

    def api_port(self) -> int:
        return self.get_int(API_PORT, DEFAULT_API_PORT)

    def dashboard_server(self) -> Optional[str]:
        return self.get(DASHBOARD_SERVER)

    def heartbeat_interval_ms(self) -> int:
        return self.get_int(HEARTBEAT_INTERVAL_MS, DEFAULT_HEARTBEAT_INTERVAL_MS)

    # Cluster HA accessors (the ONLY sanctioned readers of the
    # csp.sentinel.cluster.ha.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def cluster_ha_machine_id(self) -> Optional[str]:
        return self.get(CLUSTER_HA_MACHINE_ID)

    def cluster_ha_failover_deadline_ms(self) -> int:
        v = self.get_int(CLUSTER_HA_FAILOVER_DEADLINE_MS,
                         DEFAULT_CLUSTER_HA_FAILOVER_DEADLINE_MS)
        return v if v > 0 else DEFAULT_CLUSTER_HA_FAILOVER_DEADLINE_MS

    def cluster_ha_reconnect_ms(self) -> int:
        v = self.get_int(CLUSTER_HA_RECONNECT_MS,
                         DEFAULT_CLUSTER_HA_RECONNECT_MS)
        return v if v > 0 else DEFAULT_CLUSTER_HA_RECONNECT_MS

    def cluster_ha_degraded_divisor(self) -> int:
        v = self.get_int(CLUSTER_HA_DEGRADED_DIVISOR,
                         DEFAULT_CLUSTER_HA_DEGRADED_DIVISOR)
        return v if v > 0 else DEFAULT_CLUSTER_HA_DEGRADED_DIVISOR

    def cluster_ha_checkpoint_path(self) -> Optional[str]:
        return self.get(CLUSTER_HA_CHECKPOINT_PATH)

    def cluster_ha_checkpoint_period_ms(self) -> int:
        v = self.get_int(CLUSTER_HA_CHECKPOINT_PERIOD_MS,
                         DEFAULT_CLUSTER_HA_CHECKPOINT_PERIOD_MS)
        return v if v > 0 else DEFAULT_CLUSTER_HA_CHECKPOINT_PERIOD_MS

    # Sharded-cluster accessors (the ONLY sanctioned readers of the
    # csp.sentinel.cluster.shard.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def cluster_shard_slices(self) -> int:
        v = self.get_int(CLUSTER_SHARD_SLICES, DEFAULT_CLUSTER_SHARD_SLICES)
        return v if v > 0 else DEFAULT_CLUSTER_SHARD_SLICES

    def cluster_shard_handoff_path(self) -> Optional[str]:
        return self.get(CLUSTER_SHARD_HANDOFF_PATH)

    # Overload accessors (the ONLY sanctioned readers of the
    # csp.sentinel.overload.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def overload_queue_max_groups(self) -> int:
        v = self.get_int(OVERLOAD_QUEUE_MAX_GROUPS,
                         DEFAULT_OVERLOAD_QUEUE_MAX_GROUPS)
        return v if v > 0 else DEFAULT_OVERLOAD_QUEUE_MAX_GROUPS

    def overload_queue_watermark_pct(self) -> int:
        v = self.get_int(OVERLOAD_QUEUE_WATERMARK_PCT,
                         DEFAULT_OVERLOAD_QUEUE_WATERMARK_PCT)
        return min(v, 100) if v > 0 else DEFAULT_OVERLOAD_QUEUE_WATERMARK_PCT

    def overload_deadline_ms(self) -> int:
        v = self.get_int(OVERLOAD_DEADLINE_MS, DEFAULT_OVERLOAD_DEADLINE_MS)
        return v if v > 0 else DEFAULT_OVERLOAD_DEADLINE_MS

    def overload_retry_after_ms(self) -> int:
        v = self.get_int(OVERLOAD_RETRY_AFTER_MS,
                         DEFAULT_OVERLOAD_RETRY_AFTER_MS)
        return v if v > 0 else DEFAULT_OVERLOAD_RETRY_AFTER_MS

    def overload_conn_max_burst(self) -> int:
        v = self.get_int(OVERLOAD_CONN_MAX_BURST,
                         DEFAULT_OVERLOAD_CONN_MAX_BURST)
        return v if v > 0 else DEFAULT_OVERLOAD_CONN_MAX_BURST

    def overload_idle_timeout_s(self) -> int:
        v = self.get_int(OVERLOAD_IDLE_TIMEOUT_S,
                         DEFAULT_OVERLOAD_IDLE_TIMEOUT_S)
        return v if v > 0 else DEFAULT_OVERLOAD_IDLE_TIMEOUT_S

    def overload_rls_max_concurrent(self) -> int:
        v = self.get_int(OVERLOAD_RLS_MAX_CONCURRENT,
                         DEFAULT_OVERLOAD_RLS_MAX_CONCURRENT)
        return v if v > 0 else DEFAULT_OVERLOAD_RLS_MAX_CONCURRENT

    def overload_client_backoff_ms(self) -> int:
        v = self.get_int(OVERLOAD_CLIENT_BACKOFF_MS,
                         DEFAULT_OVERLOAD_CLIENT_BACKOFF_MS)
        return v if v > 0 else DEFAULT_OVERLOAD_CLIENT_BACKOFF_MS

    # Pipeline accessors (the ONLY sanctioned readers of the
    # csp.sentinel.pipeline.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def pipeline_inflight_depth(self) -> int:
        v = self.get_int(PIPELINE_INFLIGHT_DEPTH,
                         DEFAULT_PIPELINE_INFLIGHT_DEPTH)
        return v if v > 0 else DEFAULT_PIPELINE_INFLIGHT_DEPTH

    def pipeline_linger_us(self) -> int:
        v = self.get_int(PIPELINE_LINGER_US, DEFAULT_PIPELINE_LINGER_US)
        return v if v >= 0 else DEFAULT_PIPELINE_LINGER_US

    def pipeline_pool_widths(self) -> tuple:
        """Parsed ladder widths to pre-allocate staging buffers for;
        () = caller default (every ladder width up to its max batch).
        Malformed entries are dropped rather than killing boot."""
        raw = self.get(PIPELINE_POOL_WIDTHS) or ""
        out = []
        for part in raw.split(","):
            part = part.strip()
            if not part:
                continue
            try:
                w = int(part)
            except ValueError:
                continue
            if w > 0:
                out.append(w)
        return tuple(out)

    # Wire-path accessors (the ONLY sanctioned readers of the
    # csp.sentinel.wire.* keys — test_lint forbids reading the literals
    # anywhere else in the package).

    def wire_reactor_enabled(self) -> bool:
        return (self.get(WIRE_REACTOR_ENABLED) or "true").lower() != "false"

    def wire_coalesce_max_batch(self) -> int:
        v = self.get_int(WIRE_COALESCE_MAX_BATCH,
                         DEFAULT_WIRE_COALESCE_MAX_BATCH)
        return v if v > 0 else DEFAULT_WIRE_COALESCE_MAX_BATCH

    def wire_inflight_depth(self) -> int:
        v = self.get_int(WIRE_INFLIGHT_DEPTH, DEFAULT_WIRE_INFLIGHT_DEPTH)
        return v if v > 0 else DEFAULT_WIRE_INFLIGHT_DEPTH

    def wire_outbuf_max_bytes(self) -> int:
        v = self.get_int(WIRE_OUTBUF_MAX_BYTES,
                         DEFAULT_WIRE_OUTBUF_MAX_BYTES)
        return v if v > 0 else DEFAULT_WIRE_OUTBUF_MAX_BYTES

    def wire_read_chunk_bytes(self) -> int:
        v = self.get_int(WIRE_READ_CHUNK_BYTES,
                         DEFAULT_WIRE_READ_CHUNK_BYTES)
        return v if v > 0 else DEFAULT_WIRE_READ_CHUNK_BYTES

    def wire_workers(self) -> int:
        v = self.get_int(WIRE_WORKERS, DEFAULT_WIRE_WORKERS)
        return v if v > 0 else DEFAULT_WIRE_WORKERS

    def wire_rls_batched(self) -> bool:
        return (self.get(WIRE_RLS_BATCHED) or "false").lower() == "true"

    # Waterfall accessors (the ONLY sanctioned readers of the
    # csp.sentinel.waterfall.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def waterfall_enabled(self) -> bool:
        return (self.get(WATERFALL_ENABLED) or "true").lower() != "false"

    def waterfall_history_seconds(self) -> int:
        v = self.get_int(WATERFALL_HISTORY_SECONDS,
                         DEFAULT_WATERFALL_HISTORY_SECONDS)
        return v if v > 0 else DEFAULT_WATERFALL_HISTORY_SECONDS

    def waterfall_exemplar_every(self) -> int:
        v = self.get_int(WATERFALL_EXEMPLAR_EVERY,
                         DEFAULT_WATERFALL_EXEMPLAR_EVERY)
        return v if v > 0 else DEFAULT_WATERFALL_EXEMPLAR_EVERY

    def waterfall_sentry_enabled(self) -> bool:
        return (self.get(WATERFALL_SENTRY_ENABLED)
                or "true").lower() != "false"

    def waterfall_sentry_min_events(self) -> int:
        v = self.get_int(WATERFALL_SENTRY_MIN_EVENTS,
                         DEFAULT_WATERFALL_SENTRY_MIN_EVENTS)
        return v if v > 0 else DEFAULT_WATERFALL_SENTRY_MIN_EVENTS

    # Namespace-telescope accessors (the ONLY sanctioned readers of the
    # csp.sentinel.population.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def population_enabled(self) -> bool:
        return (self.get(POPULATION_ENABLED) or "true").lower() != "false"

    def population_topk(self) -> int:
        v = self.get_int(POPULATION_TOPK, DEFAULT_POPULATION_TOPK)
        return v if v > 0 else DEFAULT_POPULATION_TOPK

    def population_cms_depth(self) -> int:
        v = self.get_int(POPULATION_CMS_DEPTH, DEFAULT_POPULATION_CMS_DEPTH)
        return v if v > 0 else DEFAULT_POPULATION_CMS_DEPTH

    def population_cms_width(self) -> int:
        v = self.get_int(POPULATION_CMS_WIDTH, DEFAULT_POPULATION_CMS_WIDTH)
        return v if v >= 8 else DEFAULT_POPULATION_CMS_WIDTH

    def population_hll_precision(self) -> int:
        v = self.get_int(POPULATION_HLL_PRECISION,
                         DEFAULT_POPULATION_HLL_PRECISION)
        return v if 4 <= v <= 16 else DEFAULT_POPULATION_HLL_PRECISION

    def population_slice_precision(self) -> int:
        v = self.get_int(POPULATION_SLICE_PRECISION,
                         DEFAULT_POPULATION_SLICE_PRECISION)
        return v if 4 <= v <= 16 else DEFAULT_POPULATION_SLICE_PRECISION

    def population_window_seconds(self) -> int:
        v = self.get_int(POPULATION_WINDOW_SECONDS,
                         DEFAULT_POPULATION_WINDOW_SECONDS)
        return v if v > 0 else DEFAULT_POPULATION_WINDOW_SECONDS

    def population_churn_history(self) -> int:
        v = self.get_int(POPULATION_CHURN_HISTORY,
                         DEFAULT_POPULATION_CHURN_HISTORY)
        return v if v > 0 else DEFAULT_POPULATION_CHURN_HISTORY

    def population_baseline_alpha(self) -> float:
        v = self.get_float(POPULATION_BASELINE_ALPHA,
                           DEFAULT_POPULATION_BASELINE_ALPHA)
        return v if 0.0 < v <= 1.0 else DEFAULT_POPULATION_BASELINE_ALPHA

    def population_baseline_zscore(self) -> float:
        v = self.get_float(POPULATION_BASELINE_ZSCORE,
                           DEFAULT_POPULATION_BASELINE_ZSCORE)
        return v if v > 0.0 else DEFAULT_POPULATION_BASELINE_ZSCORE

    # Slot-table admission (core/slots.py — ROADMAP item 1). These are
    # the ONLY sanctioned readers of the csp.sentinel.slots.* keys.

    def slots_budget(self) -> int:
        v = self.get_int(SLOTS_BUDGET, DEFAULT_SLOTS_BUDGET)
        return v if v >= 0 else DEFAULT_SLOTS_BUDGET

    def slots_registry_capacity(self) -> int:
        v = self.get_int(SLOTS_REGISTRY_CAPACITY,
                         DEFAULT_SLOTS_REGISTRY_CAPACITY)
        return v if v > 0 else DEFAULT_SLOTS_REGISTRY_CAPACITY

    def slots_max_steals(self) -> int:
        v = self.get_int(SLOTS_MAX_STEALS, DEFAULT_SLOTS_MAX_STEALS)
        return v if v > 0 else DEFAULT_SLOTS_MAX_STEALS

    def slots_hysteresis_pct(self) -> float:
        v = self.get_float(SLOTS_HYSTERESIS_PCT,
                           DEFAULT_SLOTS_HYSTERESIS_PCT)
        return v if v >= 0.0 else DEFAULT_SLOTS_HYSTERESIS_PCT

    def slots_spill_max(self) -> int:
        v = self.get_int(SLOTS_SPILL_MAX, DEFAULT_SLOTS_SPILL_MAX)
        return v if v > 0 else DEFAULT_SLOTS_SPILL_MAX

    def slots_stale_seconds(self) -> int:
        v = self.get_int(SLOTS_STALE_SECONDS, DEFAULT_SLOTS_STALE_SECONDS)
        return v if v > 0 else DEFAULT_SLOTS_STALE_SECONDS

    # Simulator accessors (the ONLY sanctioned readers of the
    # csp.sentinel.sim.* keys — test_lint forbids reading the literals
    # anywhere else in the package).

    def sim_epoch_ms(self) -> int:
        v = self.get_int(SIM_EPOCH_MS, DEFAULT_SIM_EPOCH_MS)
        return v if v > 0 else DEFAULT_SIM_EPOCH_MS

    def sim_max_batch(self) -> int:
        v = self.get_int(SIM_MAX_BATCH, DEFAULT_SIM_MAX_BATCH)
        return v if v > 0 else DEFAULT_SIM_MAX_BATCH

    def sim_drill_max_seconds(self) -> int:
        v = self.get_int(SIM_DRILL_MAX_SECONDS,
                         DEFAULT_SIM_DRILL_MAX_SECONDS)
        return v if v > 0 else DEFAULT_SIM_DRILL_MAX_SECONDS

    # Chaos-campaign accessors (the ONLY sanctioned readers of the
    # csp.sentinel.chaos.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def chaos_epoch_ms(self) -> int:
        v = self.get_int(CHAOS_EPOCH_MS, DEFAULT_CHAOS_EPOCH_MS)
        return v if v > 0 else DEFAULT_CHAOS_EPOCH_MS

    def chaos_episodes(self) -> int:
        v = self.get_int(CHAOS_EPISODES, DEFAULT_CHAOS_EPISODES)
        return v if v > 0 else DEFAULT_CHAOS_EPISODES

    def chaos_seconds_per_episode(self) -> int:
        v = self.get_int(CHAOS_SECONDS_PER_EPISODE,
                         DEFAULT_CHAOS_SECONDS_PER_EPISODE)
        return v if v > 0 else DEFAULT_CHAOS_SECONDS_PER_EPISODE

    def chaos_max_faults(self) -> int:
        v = self.get_int(CHAOS_MAX_FAULTS, DEFAULT_CHAOS_MAX_FAULTS)
        return v if v > 0 else DEFAULT_CHAOS_MAX_FAULTS

    def chaos_max_episodes(self) -> int:
        v = self.get_int(CHAOS_MAX_EPISODES, DEFAULT_CHAOS_MAX_EPISODES)
        return v if v > 0 else DEFAULT_CHAOS_MAX_EPISODES

    # LLM-admission accessors (the ONLY sanctioned readers of the
    # csp.sentinel.llm.* keys — test_lint forbids reading the literals
    # anywhere else in the package).

    def llm_max_streams(self) -> int:
        v = self.get_int(LLM_MAX_STREAMS, DEFAULT_LLM_MAX_STREAMS)
        return v if v > 0 else DEFAULT_LLM_MAX_STREAMS

    def llm_idle_evict_ms(self) -> int:
        v = self.get_int(LLM_IDLE_EVICT_MS, DEFAULT_LLM_IDLE_EVICT_MS)
        return v if v > 0 else DEFAULT_LLM_IDLE_EVICT_MS

    def llm_default_estimate_tokens(self) -> int:
        v = self.get_int(LLM_DEFAULT_ESTIMATE_TOKENS,
                         DEFAULT_LLM_DEFAULT_ESTIMATE_TOKENS)
        return v if v > 0 else DEFAULT_LLM_DEFAULT_ESTIMATE_TOKENS

    # SLO / alerting accessors (the ONLY sanctioned readers of the
    # csp.sentinel.slo.* and csp.sentinel.alert.* keys — test_lint
    # forbids reading the literals anywhere else in the package).

    def slo_baseline_alpha(self) -> float:
        v = self.get_float(SLO_BASELINE_ALPHA, DEFAULT_SLO_BASELINE_ALPHA)
        return v if 0.0 < v < 1.0 else DEFAULT_SLO_BASELINE_ALPHA

    def slo_baseline_zscore(self) -> float:
        v = self.get_float(SLO_BASELINE_ZSCORE, DEFAULT_SLO_BASELINE_ZSCORE)
        return v if v > 0 else DEFAULT_SLO_BASELINE_ZSCORE

    def slo_baseline_warmup_seconds(self) -> int:
        v = self.get_int(SLO_BASELINE_WARMUP_SECONDS,
                         DEFAULT_SLO_BASELINE_WARMUP_SECONDS)
        return v if v >= 0 else DEFAULT_SLO_BASELINE_WARMUP_SECONDS

    def slo_baseline_min_events(self) -> int:
        v = self.get_int(SLO_BASELINE_MIN_EVENTS,
                         DEFAULT_SLO_BASELINE_MIN_EVENTS)
        return v if v >= 0 else DEFAULT_SLO_BASELINE_MIN_EVENTS

    def slo_rollout_abort(self) -> bool:
        return (self.get(SLO_ROLLOUT_ABORT) or "true").lower() != "false"

    def alert_history_capacity(self) -> int:
        v = self.get_int(ALERT_HISTORY_CAPACITY,
                         DEFAULT_ALERT_HISTORY_CAPACITY)
        return v if v > 0 else DEFAULT_ALERT_HISTORY_CAPACITY

    def alert_webhook_urls(self) -> list:
        raw = self.get(ALERT_WEBHOOK_URLS) or ""
        return [u.strip() for u in raw.split(",") if u.strip()]

    def alert_webhook_timeout_ms(self) -> int:
        v = self.get_int(ALERT_WEBHOOK_TIMEOUT_MS,
                         DEFAULT_ALERT_WEBHOOK_TIMEOUT_MS)
        return v if v > 0 else DEFAULT_ALERT_WEBHOOK_TIMEOUT_MS

    def alert_webhook_retries(self) -> int:
        v = self.get_int(ALERT_WEBHOOK_RETRIES,
                         DEFAULT_ALERT_WEBHOOK_RETRIES)
        return v if v >= 0 else DEFAULT_ALERT_WEBHOOK_RETRIES

    # Adaptive-limiting accessors (the ONLY sanctioned readers of the
    # csp.sentinel.adaptive.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def adaptive_enabled(self) -> bool:
        return (self.get(ADAPTIVE_ENABLED) or "false").lower() == "true"

    def adaptive_interval_seconds(self) -> int:
        v = self.get_int(ADAPTIVE_INTERVAL_SECONDS,
                         DEFAULT_ADAPTIVE_INTERVAL_SECONDS)
        return v if v > 0 else DEFAULT_ADAPTIVE_INTERVAL_SECONDS

    def adaptive_step_pct(self) -> float:
        v = self.get_float(ADAPTIVE_STEP_PCT, DEFAULT_ADAPTIVE_STEP_PCT)
        return v if 0.0 < v <= 1.0 else DEFAULT_ADAPTIVE_STEP_PCT

    def adaptive_increase_pct(self) -> float:
        v = self.get_float(ADAPTIVE_INCREASE_PCT,
                           DEFAULT_ADAPTIVE_INCREASE_PCT)
        return v if v > 0.0 else DEFAULT_ADAPTIVE_INCREASE_PCT

    def adaptive_decrease_pct(self) -> float:
        v = self.get_float(ADAPTIVE_DECREASE_PCT,
                           DEFAULT_ADAPTIVE_DECREASE_PCT)
        return v if 0.0 < v < 1.0 else DEFAULT_ADAPTIVE_DECREASE_PCT

    def adaptive_hysteresis_pct(self) -> float:
        v = self.get_float(ADAPTIVE_HYSTERESIS_PCT,
                           DEFAULT_ADAPTIVE_HYSTERESIS_PCT)
        return v if v >= 0.0 else DEFAULT_ADAPTIVE_HYSTERESIS_PCT

    def adaptive_cooldown_seconds(self) -> int:
        v = self.get_int(ADAPTIVE_COOLDOWN_SECONDS,
                         DEFAULT_ADAPTIVE_COOLDOWN_SECONDS)
        return v if v >= 0 else DEFAULT_ADAPTIVE_COOLDOWN_SECONDS

    def adaptive_freeze_stale_seconds(self) -> int:
        v = self.get_int(ADAPTIVE_FREEZE_STALE_SECONDS,
                         DEFAULT_ADAPTIVE_FREEZE_STALE_SECONDS)
        return v if v > 0 else DEFAULT_ADAPTIVE_FREEZE_STALE_SECONDS

    def adaptive_abort_backoff_seconds(self) -> int:
        v = self.get_int(ADAPTIVE_ABORT_BACKOFF_SECONDS,
                         DEFAULT_ADAPTIVE_ABORT_BACKOFF_SECONDS)
        return v if v >= 0 else DEFAULT_ADAPTIVE_ABORT_BACKOFF_SECONDS

    def adaptive_shadow_seconds(self) -> int:
        v = self.get_int(ADAPTIVE_SHADOW_SECONDS,
                         DEFAULT_ADAPTIVE_SHADOW_SECONDS)
        return v if v >= 0 else DEFAULT_ADAPTIVE_SHADOW_SECONDS

    def adaptive_canary_seconds(self) -> int:
        v = self.get_int(ADAPTIVE_CANARY_SECONDS,
                         DEFAULT_ADAPTIVE_CANARY_SECONDS)
        return v if v >= 0 else DEFAULT_ADAPTIVE_CANARY_SECONDS

    def adaptive_canary_bps(self) -> int:
        v = self.get_int(ADAPTIVE_CANARY_BPS, DEFAULT_ADAPTIVE_CANARY_BPS)
        return v if 0 < v <= 10_000 else DEFAULT_ADAPTIVE_CANARY_BPS

    def adaptive_history_capacity(self) -> int:
        v = self.get_int(ADAPTIVE_HISTORY_CAPACITY,
                         DEFAULT_ADAPTIVE_HISTORY_CAPACITY)
        return v if v > 0 else DEFAULT_ADAPTIVE_HISTORY_CAPACITY

    # Journal / fleet accessors (the ONLY sanctioned readers of the
    # csp.sentinel.journal.* and csp.sentinel.fleet.* keys — test_lint
    # forbids reading the literals anywhere else in the package).

    def journal_path(self) -> Optional[str]:
        v = self.get(JOURNAL_PATH)
        return v if v else None

    def journal_capacity(self) -> int:
        v = self.get_int(JOURNAL_CAPACITY, DEFAULT_JOURNAL_CAPACITY)
        return v if v > 0 else DEFAULT_JOURNAL_CAPACITY

    def journal_rotate_bytes(self) -> int:
        v = self.get_int(JOURNAL_ROTATE_BYTES, DEFAULT_JOURNAL_ROTATE_BYTES)
        return v if v > 0 else DEFAULT_JOURNAL_ROTATE_BYTES

    def fleet_history_seconds(self) -> int:
        v = self.get_int(FLEET_HISTORY_SECONDS, DEFAULT_FLEET_HISTORY_SECONDS)
        return v if v > 0 else DEFAULT_FLEET_HISTORY_SECONDS

    def fleet_stale_ms(self) -> int:
        v = self.get_int(FLEET_STALE_MS, DEFAULT_FLEET_STALE_MS)
        return v if v > 0 else DEFAULT_FLEET_STALE_MS

    def fleet_max_seconds(self) -> int:
        v = self.get_int(FLEET_MAX_SECONDS, DEFAULT_FLEET_MAX_SECONDS)
        return v if v > 0 else DEFAULT_FLEET_MAX_SECONDS

    # Rebalancer accessors (the ONLY sanctioned readers of the
    # csp.sentinel.rebalance.* keys — test_lint forbids reading the
    # literals anywhere else in the package).

    def rebalance_max_slices_per_epoch(self) -> int:
        v = self.get_int(REBALANCE_MAX_SLICES, DEFAULT_REBALANCE_MAX_SLICES)
        return v if v > 0 else DEFAULT_REBALANCE_MAX_SLICES

    def rebalance_cooldown_ms(self) -> int:
        v = self.get_int(REBALANCE_COOLDOWN_MS, DEFAULT_REBALANCE_COOLDOWN_MS)
        return v if v > 0 else DEFAULT_REBALANCE_COOLDOWN_MS

    def rebalance_skew_deadband_pct(self) -> float:
        v = self.get_float(REBALANCE_DEADBAND_PCT,
                           DEFAULT_REBALANCE_DEADBAND_PCT)
        return v if 0.0 < v <= 10.0 else DEFAULT_REBALANCE_DEADBAND_PCT

    def rebalance_stale_ms(self) -> int:
        v = self.get_int(REBALANCE_STALE_MS, DEFAULT_REBALANCE_STALE_MS)
        return v if v > 0 else DEFAULT_REBALANCE_STALE_MS

    def rebalance_abort_backoff_ms(self) -> int:
        v = self.get_int(REBALANCE_BACKOFF_MS, DEFAULT_REBALANCE_BACKOFF_MS)
        return v if v >= 0 else DEFAULT_REBALANCE_BACKOFF_MS

    def rebalance_certify_seconds(self) -> int:
        v = self.get_int(REBALANCE_CERTIFY_SECONDS,
                         DEFAULT_REBALANCE_CERTIFY_SECONDS)
        return v if v > 1 else DEFAULT_REBALANCE_CERTIFY_SECONDS

    def rebalance_window_seconds(self) -> int:
        v = self.get_int(REBALANCE_WINDOW_SECONDS,
                         DEFAULT_REBALANCE_WINDOW_SECONDS)
        return v if v > 0 else DEFAULT_REBALANCE_WINDOW_SECONDS

    def log_dir(self) -> str:
        d = self.get(LOG_DIR)
        if d:
            return d
        return os.path.join(os.path.expanduser("~"), "logs", "csp")

    def reset_for_tests(self) -> None:
        with self._lock:
            self._config.clear()
            self._loaded = False


config = SentinelConfig()
