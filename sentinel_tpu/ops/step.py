"""The fused admission/commit step — sentinel-tpu's "forward pass".

This is the TPU-native analog of the reference's slot-chain walk
(SURVEY.md §3.1): one jitted pure function
``(state, rules, batch, now) -> (state', decisions)`` that

  1. rotates the shared sliding windows to ``now`` (lazy bucket reset,
     branchless — ``ops/window.py``),
  2. runs the rule slots (authority → system → param → flow → degrade, same
     order as the reference chain; M0 wires flow, the rest join in M1),
  3. commits statistics exactly like ``StatisticSlot``: thread-count + pass
     on admit, block counts on reject — *after* the rule verdicts, which is
     the reference's crucial control-flow inversion ("statistics slot wraps
     the rule slots").

Every entry commits to up to four node rows (DefaultNode, ClusterNode,
origin StatisticNode, global ENTRY_NODE for inbound traffic), matching the
reference's node fan-out.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.batch import Decisions, EntryBatch, ExitBatch
from sentinel_tpu.core.registry import ENTRY_ROW
from sentinel_tpu.models import authority as A
from sentinel_tpu.models import degrade as D
from sentinel_tpu.models import flow as F
from sentinel_tpu.models import param_flow as P
from sentinel_tpu.models import system as Y
from sentinel_tpu.ops import segment as seg
from sentinel_tpu.ops import window as W
from sentinel_tpu.telemetry.attribution import (
    NUM_ATTR_REASONS,
    NUM_RT_BUCKETS,
    NUM_SLOT_BINS,
    REASON_CHANNEL_TABLE,
    rt_bucket_index,
    slot_bin_index,
)

SPEC_1S = W.WindowSpec(C.SECOND_WINDOW_MS, C.SECOND_BUCKETS)
SPEC_60S = W.WindowSpec(C.MINUTE_WINDOW_MS, C.MINUTE_BUCKETS)

# Shadow-lane counter channels (sentinel_tpu/rollout/): cumulative per
# ClusterNode row since the candidate set was installed. WOULD_* rows are
# the candidate ("shadow world") verdicts; LIVE_* mirror the live commit
# so a rollout guardrail can diff the two worlds from ONE tensor read
# with no sampling skew between them.
SH_WOULD_PASS = 0
SH_WOULD_BLOCK = 1
SH_WB_AUTHORITY = 2
SH_WB_SYSTEM = 3
SH_WB_PARAM = 4
SH_WB_FLOW = 5
SH_WB_DEGRADE = 6
SH_LIVE_PASS = 7
SH_LIVE_BLOCK = 8
NUM_SHADOW_COUNTERS = 9


class SecondAccum(NamedTuple):
    """Staging buffer for the current second's statistics.

    Scattering every micro-batch directly into the minute window means a
    functional update of a [60, E, R] tensor (24MB at R=16k) per step —
    measured as the single largest cost of the fused step (XLA materializes
    the copy). Instead every commit lands in this dense [E, R] accumulator
    (one small-target scatter) and is folded into ``w60`` exactly once per
    second, when the second rolls over. Readers that need the live current
    second (system BBR, metric sealing) read ``counts`` directly.
    """

    counts: jax.Array  # int32[E, R] event deltas of the second at `stamp`
    min_rt: jax.Array  # int32[R] min RT observed this second
    stamp: jax.Array   # int64[] bucket-start ms of the second; -1 = unset


class ShadowState(NamedTuple):
    """The candidate ruleset's parallel world (sentinel_tpu/rollout/).

    A staged candidate ruleset is evaluated in extra non-enforcing lanes
    of the SAME fused step. Exactness requires the shadow flow/param
    checks to admit against what the candidate WOULD have passed — not
    the live window, which under real enforcement would not contain the
    candidate-blocked traffic — so the shadow world carries its own
    instant window plus per-rule controller state for every stateful
    family. Live state that the shadow world cannot diverge (thread
    gauges, RT/exception outcomes, host OS signals — all driven by which
    requests actually RAN) is read from the live tensors; the exactness
    domain this buys is documented in docs/SEMANTICS.md.
    """

    w1: W.Window          # shadow instant window (candidate-passed traffic)
    flow: F.FlowState     # candidate warm-up / leaky-bucket state
    param: P.ParamFlowState
    degrade: D.DegradeState  # candidate breakers, fed by LIVE completions
    counts: jax.Array     # int64[NUM_SHADOW_COUNTERS, R] cumulative


class FlightRecorder(NamedTuple):
    """Device-resident per-second telemetry ring (the "flight recorder").

    One slot per wall-clock second, indexed ``(second_start_ms // 1000)
    % ring``: each slot holds that second's EXACT deltas — the same
    tensors the ``_roll_second`` fold already stages (``sec.counts``,
    the attribution/histogram/slot staging), snapshotted with one
    in-place dynamic-slice write per tensor AT the fold, i.e. at most
    once per second and zero new per-step work. ``stamps`` carries each
    slot's second-start ms (-1 = never written); a reader validates the
    stamp before trusting a slot, so ring wrap-around and idle seconds
    (nothing staged -> nothing folded -> stale slot) are self-describing
    rather than silently wrong. Host-side spill + longer bounded history
    live in telemetry/timeseries.py; rows-minor layout like every other
    stats tensor (ops/window.py docstring).
    """

    stamps: jax.Array      # int64[RING] second-start ms per slot; -1 unset
    events: jax.Array      # int32[RING, NUM_EVENTS, R] per-second deltas
    attr: jax.Array        # int32[RING, NUM_ATTR_REASONS, R]
    hist: jax.Array        # int32[RING, NUM_RT_BUCKETS, R]
    slot_attr: jax.Array   # int32[RING, NUM_ATTR_REASONS, NUM_SLOT_BINS]


def make_flight_recorder(num_rows: int, seconds: int) -> FlightRecorder:
    return FlightRecorder(
        stamps=jnp.full((seconds,), -1, jnp.int64),
        events=jnp.zeros((seconds, C.NUM_EVENTS, num_rows), jnp.int32),
        attr=jnp.zeros((seconds, NUM_ATTR_REASONS, num_rows), jnp.int32),
        hist=jnp.zeros((seconds, NUM_RT_BUCKETS, num_rows), jnp.int32),
        slot_attr=jnp.zeros((seconds, NUM_ATTR_REASONS, NUM_SLOT_BINS),
                            jnp.int32),
    )


class TelemetryState(NamedTuple):
    """Cumulative device-resident telemetry (sentinel_tpu/telemetry/).

    The attribution/histogram deltas commit as ONE in-place single-column
    scatter each into the int32 STAGING tensors, and ``totals`` is
    derived from the second accumulator the stat commit already stages —
    never a second sweep. The wide int64 cumulative tensors fold once
    per second on the ``_roll_second`` ride (the SecondAccum trick —
    updating them per step, or riding the shared bincount as extra value
    columns, each measured ~7-13% on the tier-1 bench step; staged
    scatters are inside measurement noise). Read-side,
    :func:`telemetry_view` adds the live staging back, so counter reads
    are exact at any instant. Counters are cumulative since engine start
    (Prometheus counter semantics; a restart is an ordinary reset).
    """

    # Blocked counts per (reason family, node row); channel order is
    # telemetry.attribution.ATTR_REASON_VALUES. Oracle-exact: the step's
    # reason codes follow the sequential chain's first-blocking order.
    block_by_reason: jax.Array  # int64[NUM_ATTR_REASONS, R]
    # Success-completion RT histogram per node row, log2 bucket edges
    # (telemetry.attribution.RT_BUCKET_EDGES_MS + overflow).
    rt_hist: jax.Array          # int64[NUM_RT_BUCKETS, R]
    # Cumulative MetricEvent totals per node row (the instant/minute
    # windows forget; exporters need monotonic counters). Folded from
    # ``sec.counts`` — which already carries every commit, including
    # occupy grants — so it costs nothing per step.
    totals: jax.Array           # int64[NUM_EVENTS, R]
    # Cumulative blocked counts per (reason family, rule-slot bin) —
    # engine-global, not per-resource (the per-resource split is
    # ``block_by_reason``; the slot axis answers "WHICH rule of that
    # family", telemetry/attribution.py SLOT_BIN_LABELS).
    block_by_slot: jax.Array    # int64[NUM_ATTR_REASONS, NUM_SLOT_BINS]
    # Current-second staging (the only per-step telemetry writes).
    stage_attr: jax.Array       # int32[NUM_ATTR_REASONS, R]
    stage_hist: jax.Array       # int32[NUM_RT_BUCKETS, R]
    stage_slot: jax.Array       # int32[NUM_ATTR_REASONS, NUM_SLOT_BINS]


def make_telemetry_state(num_rows: int) -> TelemetryState:
    return TelemetryState(
        block_by_reason=jnp.zeros((NUM_ATTR_REASONS, num_rows), jnp.int64),
        rt_hist=jnp.zeros((NUM_RT_BUCKETS, num_rows), jnp.int64),
        totals=jnp.zeros((C.NUM_EVENTS, num_rows), jnp.int64),
        block_by_slot=jnp.zeros((NUM_ATTR_REASONS, NUM_SLOT_BINS), jnp.int64),
        stage_attr=jnp.zeros((NUM_ATTR_REASONS, num_rows), jnp.int32),
        stage_hist=jnp.zeros((NUM_RT_BUCKETS, num_rows), jnp.int32),
        stage_slot=jnp.zeros((NUM_ATTR_REASONS, NUM_SLOT_BINS), jnp.int32),
    )


def telemetry_view(state: "SentinelState") -> TelemetryState:
    """Read-side exact telemetry: cumulative plus the live staged second
    (staging zeroed in the returned view — it has been folded in). Works
    on pod states too (leading device axis broadcasts elementwise)."""
    tele = state.telemetry
    return TelemetryState(
        block_by_reason=tele.block_by_reason
        + tele.stage_attr.astype(jnp.int64),
        rt_hist=tele.rt_hist + tele.stage_hist.astype(jnp.int64),
        totals=tele.totals + state.sec.counts.astype(jnp.int64),
        block_by_slot=tele.block_by_slot + tele.stage_slot.astype(jnp.int64),
        stage_attr=jnp.zeros_like(tele.stage_attr),
        stage_hist=jnp.zeros_like(tele.stage_hist),
        stage_slot=jnp.zeros_like(tele.stage_slot),
    )


class SentinelState(NamedTuple):
    """All mutable device state. One pytree, donated every step."""

    w1: W.Window          # 1s / 2-bucket window over all node rows
    w60: W.Window         # 60s / 60-bucket window (metric log source)
    cur_threads: jax.Array  # int32[R] live concurrency gauge per row
    flow: F.FlowState
    degrade: D.DegradeState
    param: P.ParamFlowState
    sys_signals: jax.Array  # f32[2] host-sampled [load1, cpu_usage]
    sec: SecondAccum      # current-second staging for the minute window
    # Prioritized occupy-next-window borrows (reference:
    # OccupiableBucketLeapArray's borrowArray): counts granted against the
    # NEXT w1 bucket, folded into it as PASS when that bucket becomes
    # current. ``occupied_stamp`` is the w1 bucket-start the borrows were
    # granted in (-1 = none); a jump of more than one bucket deprecates them,
    # exactly like a borrow bucket the ring never rotates into.
    occupied_next: jax.Array   # int32[R] pending borrow counts per node row
    occupied_stamp: jax.Array  # int64[] bucket-start of the granting bucket
    # Decision attribution + RT histograms + cumulative totals
    # (sentinel_tpu/telemetry/) — always present; per-step cost is one
    # in-place staging scatter per direction (see TelemetryState).
    telemetry: TelemetryState
    # Staged-rollout shadow world, present only while a candidate ruleset
    # is installed (None otherwise — installing/removing one is a pytree
    # STRUCTURE change, i.e. exactly one retrace, like a rule-shape change).
    shadow: Optional[ShadowState] = None
    # Per-second flight-recorder ring (telemetry/timeseries.py), present
    # when the engine enables time-series retention (None = disabled, the
    # default for bare make_state callers; same structure-change stance
    # as ``shadow``). Written only at the ``_roll_second`` fold.
    flight: Optional[FlightRecorder] = None


class RulePack(NamedTuple):
    """All compiled rule tensors (host-rebuilt wholesale on config push)."""

    flow: F.FlowRuleTensors
    degrade: D.DegradeRuleTensors
    authority: A.AuthorityRuleTensors
    system: Y.SystemRuleTensors
    param: P.ParamRuleTensors


def make_state(num_rows: int, flow_rules: int, now_ms: int,
               degrade: D.DegradeState = None,
               param: P.ParamFlowState = None,
               spec1: W.WindowSpec = SPEC_1S,
               flight_seconds: int = 0) -> SentinelState:
    if degrade is None:
        dt, di = D.compile_degrade_rules([], None, num_rows)
        degrade = D.make_degrade_state(dt, di)
    if param is None:
        param = P.make_param_state(0)
    return SentinelState(
        w1=W.make_window(num_rows, spec1),
        w60=W.make_window(num_rows, SPEC_60S),
        cur_threads=jnp.zeros((num_rows,), jnp.int32),
        flow=F.make_flow_state(flow_rules, now_ms),
        degrade=degrade,
        param=param,
        sys_signals=jnp.full((Y.NUM_SIGNALS,), -1.0, jnp.float32),
        sec=SecondAccum(
            counts=jnp.zeros((C.NUM_EVENTS, num_rows), jnp.int32),
            min_rt=jnp.full((num_rows,), W.MIN_RT_EMPTY, jnp.int32),
            stamp=jnp.int64(-1),
        ),
        occupied_next=jnp.zeros((num_rows,), jnp.int32),
        occupied_stamp=jnp.int64(-1),
        telemetry=make_telemetry_state(num_rows),
        flight=(make_flight_recorder(num_rows, flight_seconds)
                if flight_seconds > 0 else None),
    )


def make_shadow_state(num_rows: int, shadow_rules: RulePack,
                      degrade_state: D.DegradeState,
                      spec1: W.WindowSpec = SPEC_1S) -> ShadowState:
    """Fresh shadow world for a just-installed candidate ruleset.

    Controller state starts cold, exactly like a live rule load
    (§3.2 "WarmUp state re-created!"), and the shadow window starts
    empty — the candidate world begins accumulating its own passed
    traffic from install time.
    """
    return ShadowState(
        w1=W.make_window(num_rows, spec1),
        flow=F.make_flow_state(shadow_rules.flow.num_rules, 0),
        param=P.make_param_state(shadow_rules.param.num_rules),
        degrade=degrade_state,
        counts=jnp.zeros((NUM_SHADOW_COUNTERS, num_rows), jnp.int64),
    )


def _roll_second(
    w60: W.Window, sec: SecondAccum, telemetry: TelemetryState,
    flight: Optional[FlightRecorder], now_ms: jax.Array
) -> Tuple[W.Window, SecondAccum, TelemetryState, Optional[FlightRecorder]]:
    """Fold the staged second into the minute window if the second rolled.

    The fold rotates only the stamped bucket (lazy reset, exactly
    ``LeapArray.currentWindow`` semantics) and lands the whole [E, R] delta
    with one dense add — at most once per second instead of per step.
    The cumulative telemetry counters fold on the same ride (and from the
    same pre-reset ``sec.counts``), so the wide int64 tensors are touched
    once per second, not per step. The flight recorder (when present)
    snapshots the SAME pre-reset staging tensors into its per-second ring
    slot on the same ride — one in-place dynamic-slice write per tensor,
    at most once per second, zero new per-step work.
    """
    sec_start = now_ms.astype(jnp.int64) - now_ms.astype(jnp.int64) % SPEC_60S.bucket_ms
    need = (sec.stamp >= 0) & (sec.stamp != sec_start)

    def fold(w):
        wf = W.rotate_current(w, sec.stamp, SPEC_60S)
        idx = W.current_index(sec.stamp, SPEC_60S)
        counts = wf.counts.at[idx].add(sec.counts)
        min_rt = wf.min_rt.at[idx].set(jnp.minimum(wf.min_rt[idx], sec.min_rt))
        return W.Window(counts, min_rt, wf.starts)

    tele0 = telemetry

    def fold_tele(t):
        return TelemetryState(
            block_by_reason=t.block_by_reason + t.stage_attr.astype(jnp.int64),
            rt_hist=t.rt_hist + t.stage_hist.astype(jnp.int64),
            totals=t.totals + sec.counts.astype(jnp.int64),
            block_by_slot=t.block_by_slot + t.stage_slot.astype(jnp.int64),
            stage_attr=jnp.zeros_like(t.stage_attr),
            stage_hist=jnp.zeros_like(t.stage_hist),
            stage_slot=jnp.zeros_like(t.stage_slot),
        )

    def fold_flight(f):
        # Slot for the COMPLETED second (sec.stamp, not sec_start): ring
        # index = absolute second number mod ring length, so any reader
        # can address an offset directly and validate against ``stamps``.
        idx = (sec.stamp // SPEC_60S.bucket_ms) % f.stamps.shape[0]
        return FlightRecorder(
            stamps=f.stamps.at[idx].set(sec.stamp),
            events=f.events.at[idx].set(sec.counts),
            attr=f.attr.at[idx].set(tele0.stage_attr),
            hist=f.hist.at[idx].set(tele0.stage_hist),
            slot_attr=f.slot_attr.at[idx].set(tele0.stage_slot),
        )

    w60 = jax.lax.cond(need, fold, lambda w: w, w60)
    telemetry = jax.lax.cond(need, fold_tele, lambda t: t, telemetry)
    if flight is not None:
        flight = jax.lax.cond(need, fold_flight, lambda f: f, flight)
    return w60, SecondAccum(
        counts=jnp.where(need, 0, sec.counts),
        min_rt=jnp.where(need, W.MIN_RT_EMPTY, sec.min_rt),
        stamp=sec_start,
    ), telemetry, flight


def flush_seconds(state: SentinelState, now_ms: jax.Array) -> SentinelState:
    """Host-boundary flush: fold any completed staged second into ``w60``
    (and the cumulative telemetry counters).

    Called by the engine before reading the minute window (metric sealing).
    A stamp equal to the current second stays staged — that second is not
    sealed yet anyway (telemetry readers add live staging back through
    :func:`telemetry_view`).
    """
    now_ms = jnp.asarray(now_ms, jnp.int64)
    w60, sec, telemetry, flight = _roll_second(
        state.w60, state.sec, state.telemetry, state.flight, now_ms)
    return state._replace(w60=w60, sec=sec, telemetry=telemetry,
                          flight=flight)


def _target_rows(cluster_row, dn_row, origin_row, entry_in):
    """[N, 4] node rows each request commits to (−1 entries are dropped)."""
    entry_row = jnp.where(entry_in, ENTRY_ROW, -1)
    return jnp.stack([dn_row, cluster_row, origin_row, entry_row], axis=1)


def _event_delta(rows4: jax.Array, pairs, num_rows: int,
                 extra_cols=()) -> Tuple[jax.Array, jax.Array]:
    """All (event, values4) commits as one dense int32[E, R] delta.

    ``pairs``: list of (MetricEvent, values4, wide) with values4 shaped like
    ``rows4``. Computed as a one-hot matmul bincount (``ops/segment.py``) —
    TPU scatters serialize per update and measured ~0.4ms per commit at 64k
    updates; the MXU form is microseconds. ``wide=True`` values (RT sums,
    up to 2^16) are split into byte limbs so the bf16 operands stay exact.

    ``extra_cols``: further [N, 4] value sets (e.g. the thread-gauge
    deltas) folded into the SAME bincount call — the one-hot operands are
    the expensive part and are shared. Returns ``(delta, extras)`` with
    ``extras`` float32[len(extra_cols), R].
    """
    rows_flat = rows4.reshape(-1)
    cols = []
    for _, v, wide in pairs:
        vf = v.reshape(-1)
        if wide:
            vf = jnp.clip(vf, 0, 65535)
            cols += [vf % 256, vf // 256]
        else:
            cols.append(vf)
    n_event_cols = len(cols)
    cols += [v.reshape(-1) for v in extra_cols]
    out = seg.bincount_matmul(
        rows_flat, jnp.stack(cols, axis=1), num_rows
    )  # [C, R] float32, exact
    delta = jnp.zeros((C.NUM_EVENTS, num_rows), jnp.int32)
    i = 0
    for ev, _, wide in pairs:
        if wide:
            combined = out[i] + 256.0 * out[i + 1]
            i += 2
        else:
            combined = out[i]
            i += 1
        delta = delta.at[ev].set(combined.astype(jnp.int32))
    return delta, out[n_event_cols:]


def _apply_delta(w1: W.Window, sec: SecondAccum, delta: jax.Array, now_ms,
                 spec1: W.WindowSpec) -> Tuple[W.Window, SecondAccum]:
    """Fold a dense [E, R] delta into w1's current bucket + the second acc."""
    idx1 = W.current_index(now_ms, spec1)
    w1 = w1._replace(counts=w1.counts.at[idx1].add(delta))
    return w1, sec._replace(counts=sec.counts + delta)


def _shadow_entry_eval(
    state: SentinelState,
    shadow_rules: RulePack,
    batch: EntryBatch,
    now_ms: jax.Array,
    w1_live: W.Window,
    w60_live: W.Window,
    sec_counts: jax.Array,
    spec1: W.WindowSpec,
    occupy_timeout_ms,
    shadow_extra_pass=None,
    shadow_extra_cms=None,
):
    """Run the candidate ruleset's slot cascade in non-enforcing lanes.

    Same slot order as the live chain (authority → system → param → flow
    → degrade). Stateful families admit against the SHADOW world (its own
    window + controller state); thread gauges, OS signals and the live
    windows feeding the system check come from the live tensors (shadow
    cannot know which requests would have completed — SEMANTICS.md
    "Shadow-lane exactness"). Occupy borrows are not simulated: a
    prioritized request the candidate would reject counts as would-block.

    Returns ``(s_blocked, s_reason, s_wait_us, new_shadow_substate_parts,
    rotated_shadow_w1, per-family block masks, s_slot)``.
    """
    sh = state.shadow
    lanes = batch.cluster_row >= 0  # every real lane, pre-decided or not
    sh_w1 = W.rotate(sh.w1, now_ms, spec1)

    s_reason = jnp.where(lanes, C.BlockReason.PASS, -1).astype(jnp.int32)
    s_slot = jnp.full_like(s_reason, -1)
    s_av = A.check_authority(shadow_rules.authority, batch, lanes)
    s_auth = s_av.blocked
    s_reason = jnp.where(lanes & s_auth, C.BlockReason.AUTHORITY, s_reason)
    s_slot = jnp.where(lanes & s_auth, s_av.slot, s_slot)
    s_blocked = s_auth

    cand = lanes & (~s_blocked)
    # w60/sec must be the step's ROLLED pair (the same tensors the live
    # system check reads): at a second boundary the pre-roll w60 plus the
    # reset accumulator would miss the just-completed second entirely.
    s_sys = Y.check_system(shadow_rules.system, state.sys_signals, w1_live,
                           w60_live, sec_counts, state.cur_threads, batch,
                           cand, now_ms, spec1=spec1)
    s_reason = jnp.where(cand & s_sys, C.BlockReason.SYSTEM, s_reason)
    s_slot = jnp.where(cand & s_sys, 0, s_slot)
    s_blocked = s_blocked | s_sys

    cand = lanes & (~s_blocked)
    s_pv = P.check_param_flow(shadow_rules.param, sh.param, batch, now_ms,
                              cand, extra_cms=shadow_extra_cms)
    s_reason = jnp.where(cand & s_pv.blocked, C.BlockReason.PARAM_FLOW,
                         s_reason)
    s_slot = jnp.where(cand & s_pv.blocked, s_pv.slot, s_slot)
    s_blocked = s_blocked | s_pv.blocked

    s_fv = F.check_flow(shadow_rules.flow, sh.flow, sh_w1, state.cur_threads,
                        batch, now_ms, s_blocked | (~lanes),
                        extra_pass=shadow_extra_pass, spec=spec1,
                        occupy_timeout_ms=occupy_timeout_ms)
    s_flow = lanes & (~s_blocked) & s_fv.blocked
    s_reason = jnp.where(s_flow, C.BlockReason.FLOW, s_reason)
    s_slot = jnp.where(s_flow, s_fv.slot, s_slot)
    s_blocked = s_blocked | s_fv.blocked

    cand = lanes & (~s_blocked)
    s_dv = D.check_degrade(shadow_rules.degrade, sh.degrade, batch, now_ms,
                           cand)
    s_degr = cand & s_dv.blocked
    s_reason = jnp.where(s_degr, C.BlockReason.DEGRADE, s_reason)
    s_slot = jnp.where(s_degr, s_dv.slot, s_slot)
    s_blocked = s_blocked | s_dv.blocked

    s_wait_us = jnp.where(lanes & (~s_blocked),
                          jnp.maximum(s_fv.wait_us, s_pv.wait_us), 0)
    fam_blocks = (s_auth & lanes, s_sys, s_pv.blocked & lanes, s_flow, s_degr)
    return (s_blocked & lanes, s_reason, s_wait_us,
            (s_fv.state, s_pv.state, s_dv.state), sh_w1, fam_blocks, s_slot)


@jax.named_scope("entry_step")
def entry_step(
    state: SentinelState,
    rules: RulePack,
    batch: EntryBatch,
    now_ms: jax.Array,
    extra_pass=None,
    extra_next=None,
    extra_cms=None,
    extra_checkers: tuple = (),
    extra_pass_global=None,
    extra_next_global=None,
    spec1: W.WindowSpec = SPEC_1S,
    occupy_timeout_ms: int = C.DEFAULT_OCCUPY_TIMEOUT_MS,
    shadow_rules: Optional[RulePack] = None,
    canary_bps=None,
    canary_salt=None,
    shadow_extra_pass=None,
    shadow_extra_cms=None,
) -> Tuple[SentinelState, Decisions]:
    """One admission step. ``extra_pass`` / ``extra_next`` (int32[R]) /
    ``extra_cms`` (f32[PR, D, W] param sketch), all optional, are the
    other devices' contributions for cluster-mode rules — supplied by the
    pod-parallel wrapper (``parallel/cluster.py``) from a ``psum``.

    ``extra_checkers``: SPI-registered pure device checkers (core/spi.py),
    spliced between the param-flow and flow slots — the reference's
    SlotChainBuilder splice point. Static (closed over at jit time).

    ``shadow_rules`` (with ``state.shadow`` present) evaluates a staged
    candidate ruleset in extra non-enforcing lanes of this same step
    (sentinel_tpu/rollout/): would-verdicts accumulate in
    ``state.shadow.counts`` with zero effect on live decisions — unless
    ``canary_bps`` is set, in which case lanes whose deterministic
    (origin, context) hash falls inside the canary slice are ENFORCED by
    the candidate verdict instead of the live one. ``canary_bps`` /
    ``canary_salt`` are traced scalars (tuning them never retraces);
    ``shadow_extra_pass`` / ``shadow_extra_cms`` are the pod-psum'd
    cross-device shadow contributions, mirroring ``extra_pass`` /
    ``extra_cms``."""
    now_ms = jnp.asarray(now_ms, jnp.int64)
    with jax.named_scope("roll"):
        w1 = W.rotate(state.w1, now_ms, spec1)
        # Minute-window commits are staged in the [E, R] second accumulator
        # and folded at most once per second; readers (BBR check below, host
        # metric sealing) combine w60 + the live accumulator themselves.
        w60, sec, tele, flight = _roll_second(state.w60, state.sec,
                                              state.telemetry, state.flight,
                                              now_ms)

        # Land pending occupy borrows: once the bucket after the granting one
        # is current, its borrowed counts become real PASS there (reference:
        # OccupiableBucketLeapArray.resetWindowTo transfers the borrow
        # bucket). A jump of 2+ buckets means the target bucket already
        # expired — the borrows are dropped, like a borrow bucket the ring
        # rotated past.
        idx1 = W.current_index(now_ms, spec1)
        cur_start = now_ms - now_ms % spec1.bucket_ms
        moved = (state.occupied_stamp >= 0) & (cur_start != state.occupied_stamp)
        land = moved & (cur_start == state.occupied_stamp + spec1.bucket_ms)
        w1 = w1._replace(counts=w1.counts.at[idx1, C.MetricEvent.PASS].add(
            jnp.where(land, state.occupied_next, 0)))
        occupied_next = jnp.where(moved, 0, state.occupied_next)

    valid = batch.cluster_row >= 0
    reason = jnp.where(valid, C.BlockReason.PASS, -1).astype(jnp.int32)
    # First-blocking rule slot beside the reason (decision attribution —
    # telemetry/attribution.py): -1 until a slotted family blocks.
    rule_slot = jnp.full_like(reason, -1)
    # Remote token-server rejections arrive pre-decided: record the block
    # (StatisticSlot catches the cluster FlowException the same way) and
    # skip every local slot. Their rule identity lives on the token
    # server — rule_slot stays -1 ("remote/unknown").
    blocked = valid & batch.pre_blocked
    # pre_reason carries the rejecting family (host lease blocks name
    # PARAM_FLOW vs FLOW; remote verdicts stay FLOW) so block
    # attribution lands in the right channel.
    reason = jnp.where(blocked, batch.pre_reason, reason)
    # Host-leased admissions (core/lease.py) arrive pre-PASSED: commit
    # their statistics, skip every slot. Their counts join the window via
    # this step's commit, so slot-checked peers in the SAME batch see them
    # with one-batch staleness — the documented micro-batch delta class.
    pre_ok = valid & batch.pre_passed & (~blocked)
    decided = blocked | pre_ok

    # --- rule slots (order mirrors the reference chain: authority →
    # system → param-flow → flow → degrade) --------------------------------
    with jax.named_scope("authority"):
        av = A.check_authority(rules.authority, batch, valid & (~decided))
        auth_blocked = av.blocked
        reason = jnp.where(valid & (~decided) & auth_blocked, C.BlockReason.AUTHORITY, reason)
        rule_slot = jnp.where(valid & (~decided) & auth_blocked, av.slot, rule_slot)
        blocked = blocked | auth_blocked
        decided = decided | blocked

    with jax.named_scope("system"):
        cand = valid & (~decided)
        sys_blocked = Y.check_system(rules.system, state.sys_signals, w1, w60,
                                     sec.counts, state.cur_threads, batch, cand,
                                     now_ms, spec1=spec1)
        reason = jnp.where(cand & sys_blocked, C.BlockReason.SYSTEM, reason)
        # System rules are one global set, not per-resource slots: slot 0.
        rule_slot = jnp.where(cand & sys_blocked, 0, rule_slot)
        blocked = blocked | sys_blocked
        decided = decided | blocked

    with jax.named_scope("param"):
        cand = valid & (~decided)
        pv = P.check_param_flow(rules.param, state.param, batch, now_ms, cand,
                                extra_cms=extra_cms)
        reason = jnp.where(cand & pv.blocked, C.BlockReason.PARAM_FLOW, reason)
        rule_slot = jnp.where(cand & pv.blocked, pv.slot, rule_slot)
        blocked = blocked | pv.blocked
        decided = decided | blocked

    with jax.named_scope("custom"):
        for chk_idx, chk in enumerate(extra_checkers):
            cand = valid & (~decided)
            custom_blocked = cand & chk(state._replace(w1=w1), rules, batch,
                                        now_ms, cand)
            reason = jnp.where(custom_blocked, C.BlockReason.CUSTOM, reason)
            # CUSTOM attribution: the splice position of the blocking checker.
            rule_slot = jnp.where(custom_blocked, chk_idx, rule_slot)
            blocked = blocked | custom_blocked
            decided = decided | blocked

    with jax.named_scope("flow"):
        fv = F.check_flow(rules.flow, state.flow, w1, state.cur_threads, batch, now_ms, decided,
                          extra_pass=extra_pass, occupied_next=occupied_next,
                          extra_next=extra_next,
                          extra_pass_global=extra_pass_global,
                          extra_next_global=extra_next_global, spec=spec1,
                          occupy_timeout_ms=occupy_timeout_ms)
        reason = jnp.where(valid & (~decided) & fv.blocked, C.BlockReason.FLOW, reason)
        rule_slot = jnp.where(valid & (~decided) & fv.blocked, fv.slot, rule_slot)
        blocked = blocked | fv.blocked
        decided = decided | blocked

    # Occupy grants leave the chain before the degrade slot (reference:
    # PriorityWaitException propagates out of FlowSlot).
    with jax.named_scope("degrade"):
        granted = valid & (~decided) & fv.occupied
        dv = D.check_degrade(rules.degrade, state.degrade, batch, now_ms,
                             valid & (~decided) & (~granted))
        reason = jnp.where(valid & (~decided) & dv.blocked, C.BlockReason.DEGRADE, reason)
        rule_slot = jnp.where(valid & (~decided) & dv.blocked, dv.slot, rule_slot)
        blocked = blocked | dv.blocked

    # --- shadow lanes (sentinel_tpu/rollout/) -----------------------------
    # Candidate-world verdicts ride the same step; canary lanes swap their
    # ENFORCED verdict to the candidate's BEFORE the stat commit, so the
    # live windows record what actually happened to them.
    shadow_new = state.shadow
    s_eval = None
    wait_pick = jnp.maximum(fv.wait_us, pv.wait_us)
    with jax.named_scope("shadow"):
        if shadow_rules is not None and state.shadow is not None:
            from sentinel_tpu.rollout.canary import device_in_canary

            s_eval = _shadow_entry_eval(
                state, shadow_rules, batch, now_ms, w1, w60, sec.counts, spec1,
                occupy_timeout_ms, shadow_extra_pass=shadow_extra_pass,
                shadow_extra_cms=shadow_extra_cms)
            (s_blocked, s_reason, s_wait_us, s_states, sh_w1, s_fam,
             s_slot) = s_eval
            if canary_bps is not None:
                # Canary enforcement: deterministic (origin, context) hash
                # selects a stable slice of traffic the candidate governs.
                # Pre-decided lanes (remote token verdicts, lease commits)
                # and occupy-granted lanes stay live-governed — their
                # decision was already made elsewhere.
                mix = (valid & (~batch.pre_blocked) & (~batch.pre_passed)
                       & (~granted)
                       & device_in_canary(
                           batch.origin_id, batch.context_id,
                           0 if canary_salt is None else canary_salt,
                           canary_bps))
                blocked = jnp.where(mix, s_blocked, blocked)
                reason = jnp.where(mix, s_reason, reason)
                rule_slot = jnp.where(mix, s_slot, rule_slot)
                wait_pick = jnp.where(mix, s_wait_us, wait_pick)

    # --- StatisticSlot commit --------------------------------------------
    with jax.named_scope("commit"):
        rows4 = _target_rows(batch.cluster_row, batch.dn_row, batch.origin_row, batch.entry_in)
        admit = valid & (~blocked)
        # Granted occupies don't commit PASS now: their pass lands in the bucket
        # they borrowed (the fold above, next step). The minute staging gets
        # PASS + OCCUPIED_PASS immediately on the rule-selected row (reference:
        # StatisticNode.addOccupiedPass hits the minute counter at grant time).
        pass_counts = jnp.where(admit & (~granted), batch.count, 0)
        block_counts = jnp.where(valid & blocked, batch.count, 0)
        pass4 = jnp.broadcast_to(pass_counts[:, None], rows4.shape)
        block4 = jnp.broadcast_to(block_counts[:, None], rows4.shape)

        thread_inc = jnp.broadcast_to(jnp.where(admit, 1, 0)[:, None], rows4.shape)
        extra_cols = [thread_inc]
        sh_base = len(extra_cols)
        if s_eval is not None:
            # Every shadow commit — the shadow window's PASS plus all the
            # would-verdict counter channels — rides the live commit's
            # bincount as extra value columns: the one-hot operands (the
            # expensive part on TPU) are shared, no second sweep. The LIVE
            # counter channels need no columns at all — they are exactly
            # delta[PASS] / delta[BLOCK].
            s_pass_counts = jnp.where(valid & (~s_blocked), batch.count, 0)
            s_block_counts = jnp.where(valid & s_blocked, batch.count, 0)
            for col in (s_pass_counts, s_block_counts,
                        *(jnp.where(m, batch.count, 0) for m in s_fam)):
                extra_cols.append(jnp.broadcast_to(col[:, None], rows4.shape))
        delta, extras = _event_delta(
            rows4, [(C.MetricEvent.PASS, pass4, False),
                    (C.MetricEvent.BLOCK, block4, False)], w1.num_rows,
            extra_cols=extra_cols)
        w1, sec = _apply_delta(w1, sec, delta, now_ms, spec1)
        occupied_next = occupied_next + fv.occ_add
        occupied_stamp = cur_start
        sec = sec._replace(counts=sec.counts
                           .at[C.MetricEvent.PASS].add(fv.occ_add)
                           .at[C.MetricEvent.OCCUPIED_PASS].add(fv.occ_add))

        cur_threads = state.cur_threads + extras[0].astype(jnp.int32)

    # Telemetry commit: ONE single-column scatter-add of the blocked
    # lanes into the staged per-(reason, ClusterNode) counters. In-place
    # on the donated staging tensor — measured cheaper than riding the
    # shared bincount as 6 extra value columns, whose operand/target
    # widening cost ~13% of the bench step; a width-N single-column
    # scatter is noise on both backends (CPU scatter-add; TPU ~7ns/
    # update × N). ``reason`` here is post-canary-mix, so attribution
    # always matches what the live windows recorded for the lane.
    # ``totals`` needs NO write at all — it folds from ``sec.counts`` at
    # second-roll, and the second staging already carries every commit
    # including occupy grants (the ``occ_add`` adds below —
    # StatisticNode.addOccupiedPass semantics).
    with jax.named_scope("telemetry"):
        attr_ch = jnp.asarray(REASON_CHANNEL_TABLE)[
            jnp.clip(reason, 0, REASON_CHANNEL_TABLE.shape[0] - 1)]
        attr_on = valid & blocked & (attr_ch >= 0)
        attr_rows = W.oob(jnp.where(attr_on, batch.cluster_row, -1), w1.num_rows)
        # The (reason, rule-slot) staging shares the same mask: one more tiny
        # scatter into a [A, SLOT_BINS] tensor (remote/pre-decided verdicts
        # carry slot -1 and land in the "unknown" bin).
        slot_bins = jnp.where(attr_on, slot_bin_index(rule_slot), NUM_SLOT_BINS)
        tele = tele._replace(
            stage_attr=tele.stage_attr.at[
                jnp.maximum(attr_ch, 0), attr_rows].add(
                jnp.where(attr_on, batch.count, 0), mode="drop"),
            stage_slot=tele.stage_slot.at[
                jnp.maximum(attr_ch, 0), slot_bins].add(
                jnp.where(attr_on, batch.count, 0), mode="drop"))

    with jax.named_scope("shadow"):
        if s_eval is not None:
            sh_w1 = sh_w1._replace(counts=sh_w1.counts.at[
                idx1, C.MetricEvent.PASS].add(extras[sh_base].astype(jnp.int32)))
            counts = state.shadow.counts
            for ch, vec in (
                    (SH_WOULD_PASS, extras[sh_base]),
                    (SH_WOULD_BLOCK, extras[sh_base + 1]),
                    (SH_WB_AUTHORITY, extras[sh_base + 2]),
                    (SH_WB_SYSTEM, extras[sh_base + 3]),
                    (SH_WB_PARAM, extras[sh_base + 4]),
                    (SH_WB_FLOW, extras[sh_base + 5]),
                    (SH_WB_DEGRADE, extras[sh_base + 6]),
                    (SH_LIVE_PASS, delta[C.MetricEvent.PASS]),
                    (SH_LIVE_BLOCK, delta[C.MetricEvent.BLOCK])):
                counts = counts.at[ch].add(vec.astype(jnp.int64))
            shadow_new = ShadowState(
                w1=sh_w1, flow=s_states[0], param=s_states[1],
                degrade=s_states[2], counts=counts)

    wait_us = jnp.where(admit, wait_pick, 0)

    new_state = SentinelState(w1=w1, w60=w60, cur_threads=cur_threads,
                              flow=fv.state, degrade=dv.state, param=pv.state,
                              sys_signals=state.sys_signals, sec=sec,
                              occupied_next=occupied_next,
                              occupied_stamp=occupied_stamp,
                              telemetry=tele,
                              shadow=shadow_new,
                              flight=flight)
    return new_state, Decisions(reason=reason, wait_us=wait_us,
                                rule_slot=rule_slot)


@jax.named_scope("exit_step")
def exit_step(
    state: SentinelState,
    rules: RulePack,
    batch: ExitBatch,
    now_ms: jax.Array,
    spec1: W.WindowSpec = SPEC_1S,
    shadow_rules: Optional[RulePack] = None,
) -> SentinelState:
    """Completion commit: RT + success/exception, thread decrement.

    Mirrors ``StatisticSlot.exit`` + ``Tracer`` exception accounting
    (SURVEY.md §3.1 "LeapArray write #2"). With a staged candidate set
    installed (``shadow_rules`` + ``state.shadow``), live completions
    also feed the candidate's breakers and THREAD-grade param gauges —
    the shadow world shares the live RT/exception stream, since which
    requests completed (and how) is decided by what actually ran
    (SEMANTICS.md "Shadow-lane exactness").
    """
    now_ms = jnp.asarray(now_ms, jnp.int64)
    with jax.named_scope("roll"):
        w1 = W.rotate(state.w1, now_ms, spec1)
        w60, sec, tele, flight = _roll_second(state.w60, state.sec,
                                              state.telemetry, state.flight,
                                              now_ms)

    valid = batch.cluster_row >= 0
    with jax.named_scope("commit"):
        rows4 = _target_rows(batch.cluster_row, batch.dn_row, batch.origin_row, batch.entry_in)

        succ = jnp.where(valid & batch.success, batch.count, 0)
        exc = jnp.where(valid & batch.error, batch.count, 0)
        rt = jnp.where(valid & batch.success, batch.rt_ms, 0)
        succ4 = jnp.broadcast_to(succ[:, None], rows4.shape)
        exc4 = jnp.broadcast_to(exc[:, None], rows4.shape)
        rt4 = jnp.broadcast_to(rt[:, None], rows4.shape)

        thread_dec = jnp.broadcast_to(jnp.where(valid, -1, 0)[:, None], rows4.shape)
        delta, extras = _event_delta(
            rows4, [(C.MetricEvent.SUCCESS, succ4, False),
                    (C.MetricEvent.EXCEPTION, exc4, False),
                    (C.MetricEvent.RT, rt4, True)], w1.num_rows,
            extra_cols=[thread_dec])
        w1, sec = _apply_delta(w1, sec, delta, now_ms, spec1)
    # Device-side log-bucketed RT histogram (telemetry/attribution.py):
    # one single-column scatter-add of success completions into the
    # staged per-(bucket, ClusterNode) counters — per-resource latency
    # percentiles replace relying on the avg-only RT/SUCCESS ratio for
    # tail visibility. In-place on the donated staging tensor (see the
    # entry commit's attribution note for why this beats extra bincount
    # columns); the int64 histogram folds at second-roll, and totals
    # ride sec.counts — no per-step write to the wide tensors.
    with jax.named_scope("telemetry"):
        bidx = rt_bucket_index(batch.rt_ms)
        succ_mask = valid & batch.success
        hist_rows = W.oob(jnp.where(succ_mask, batch.cluster_row, -1),
                          w1.num_rows)
        # Weight 1 per COMPLETION, not per acquire token: the RT sum records
        # each completion's rt once (reference Tracer semantics), and the
        # OpenMetrics histogram contract requires _bucket/_count/_sum to
        # describe the same observation stream.
        telemetry = tele._replace(stage_hist=tele.stage_hist.at[
            bidx, hist_rows].add(jnp.where(succ_mask, 1, 0), mode="drop"))

    with jax.named_scope("commit"):
        # min-RT: stage one dense [R] min then fold into the current buckets.
        num_rows = w1.num_rows
        rt_obs = jnp.where((valid & batch.success)[:, None], rt4, W.MIN_RT_EMPTY)
        mstage = jnp.full((num_rows,), W.MIN_RT_EMPTY, jnp.int32).at[
            W.oob(rows4.reshape(-1), num_rows)
        ].min(rt_obs.reshape(-1).astype(jnp.int32), mode="drop")
        idx1 = W.current_index(now_ms, spec1)
        w1 = w1._replace(min_rt=w1.min_rt.at[idx1].set(
            jnp.minimum(w1.min_rt[idx1], mstage)))
        sec = sec._replace(min_rt=jnp.minimum(sec.min_rt, mstage))

        # Clamp at zero: in a correct stream exits never outnumber entries,
        # so a negative row only arises from unmatched exits after a cold
        # state drop (DeviceDispatchError recovery) — without the clamp those
        # stale handles would bias THREAD-grade admission permanently.
        cur_threads = jnp.maximum(
            state.cur_threads + extras[0].astype(jnp.int32), 0)

    with jax.named_scope("degrade"):
        degrade = D.feed_degrade(rules.degrade, state.degrade, batch, now_ms)
    with jax.named_scope("param"):
        param = P.feed_param_exit(rules.param, state.param, batch)

    shadow = state.shadow
    if shadow_rules is not None and shadow is not None:
        with jax.named_scope("shadow"):
            shadow = shadow._replace(
                degrade=D.feed_degrade(shadow_rules.degrade, shadow.degrade,
                                       batch, now_ms),
                param=P.feed_param_exit(shadow_rules.param, shadow.param,
                                        batch),
            )

    return state._replace(w1=w1, w60=w60, cur_threads=cur_threads,
                          degrade=degrade, param=param, sec=sec,
                          telemetry=telemetry, shadow=shadow, flight=flight)
