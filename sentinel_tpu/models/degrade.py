"""Circuit breaking (degrade rules) as a vectorized state machine.

Reference surface (SURVEY.md §2.1 "DegradeSlot + circuit breaker", 1.8
semantics): per-rule ``CircuitBreaker`` with CLOSED → OPEN → HALF_OPEN
states over a private ``statIntervalMs`` sliding window —
``ResponseTimeCircuitBreaker`` (slow-call ratio: rt > count ⇒ slow; open
when slowRatio ≥ slowRatioThreshold) and ``ExceptionCircuitBreaker``
(error ratio / error count). Checked at **entry** (``tryPass``), fed at
**exit** (``onRequestComplete`` with the completed request's RT + error).

TPU-native design: every breaker is one row of
  * ``state  int32[DR]``      CLOSED=0 / OPEN=1 / HALF_OPEN=2
  * ``next_retry_ms int64[DR]``
  * a :class:`~sentinel_tpu.ops.window.RowWindow` ``[DR, 1, 3]`` (one
    tumbling ``statIntervalMs`` bucket per rule — the reference breaker
    LeapArray uses sampleCount 1 — with TOTAL/ERROR/SLOW channels),
and all transitions are ``where``-selects over the whole rule axis.

Entry semantics: CLOSED passes; OPEN passes a single probe per rule once
``next_retry_ms`` elapses (the batch's *first* arrival wins — segmented
first-occurrence flag), flipping the rule to HALF_OPEN; HALF_OPEN blocks.
Exit semantics: completions feed the window; a completion while HALF_OPEN
decides the probe verdict (bad ⇒ re-OPEN with a fresh retry window, good ⇒
CLOSED with stats reset) — including completions of requests admitted
before the flip, matching the reference's observer behavior; CLOSED rules
re-evaluate their threshold and may trip OPEN. With several completions of
one HALF_OPEN rule in a batch, any bad outcome wins (the serial reference's
final state depends on arrival order; documented delta).

Trip comparison: upstream judges ``slowCount * 1.0d / totalCount >
slowRatioThreshold`` (and the error ratio against ``count``) in Java
doubles. The device never divides: each ratio threshold compiles on the
host to the least fraction ``num / den`` (``den`` ≤ the largest window
count) that a double quotient of window counts exceeds the threshold at,
so ``bad * den >= num * total`` in int64 gives upstream's verdict for every
pair of counts the window can hold, ties included
(:func:`ratio_trip_fraction`, :func:`ratio_trips`).

State-change record: each rule counts its transitions into OPEN,
HALF_OPEN and CLOSED (``transitions``), and keeps the clock reading of its
last trip (CLOSED → OPEN) and of its last close (HALF_OPEN → CLOSED) — the
vectorised ``CircuitBreakerStateChangeObserver``. A probe reverted because
a later slot blocked its request counts as HALF_OPEN → OPEN, as upstream's
terminate hook notifies it. The record restarts with the rules' state when
degrade rules are reloaded.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from fractions import Fraction
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.rule_manager import RuleManager
from sentinel_tpu.core.batch import EntryBatch, ExitBatch
from sentinel_tpu.core.registry import NodeRegistry
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops.segment import first_in_segment
from sentinel_tpu.utils.shapes import round_up as _round_up

# RowWindow channels
CH_TOTAL = 0
CH_ERROR = 1
CH_SLOW = 2
NUM_CH = 3

BREAKER_BUCKETS = 1  # tumbling statIntervalMs bucket (reference sampleCount=1)

# Rows of DegradeState.transitions: the state a transition entered (the
# record's updates stack their masks in this order).
TO_OPEN = 0
TO_HALF_OPEN = 1
TO_CLOSED = 2
NUM_TRANSITIONS = 3
# Transient state of a probe reverted within check_degrade (never stored).
_REVERTED = 3

# The largest count a breaker's int32 window holds: every ratio the device
# judges is k / n with 0 <= k <= n <= MAX_WINDOW_COUNT.
MAX_WINDOW_COUNT = (1 << 31) - 1


@dataclass
class DegradeRule:
    """Reference: ``DegradeRule.java`` (1.8 field set)."""

    resource: str
    count: float                      # RT grade: max allowed rt (ms); ratio/count grades: threshold
    grade: int = C.DEGRADE_GRADE_RT
    time_window: int = 0              # recovery timeout (seconds)
    slow_ratio_threshold: float = C.DEGRADE_DEFAULT_SLOW_RATIO_THRESHOLD
    min_request_amount: int = C.DEGRADE_DEFAULT_MIN_REQUEST_AMOUNT
    stat_interval_ms: int = C.DEGRADE_DEFAULT_STAT_INTERVAL_MS
    limit_app: str = C.LIMIT_APP_DEFAULT
    # Staged rollout (sentinel_tpu/rollout/): see FlowRule.candidate_set.
    candidate_set: Optional[str] = None
    rollout_stage: Optional[str] = None

    def is_valid(self) -> bool:
        if not self.resource or self.count < 0 or self.time_window < 0:
            return False
        if self.grade not in (C.DEGRADE_GRADE_RT, C.DEGRADE_GRADE_EXCEPTION_RATIO,
                              C.DEGRADE_GRADE_EXCEPTION_COUNT):
            return False
        if self.grade == C.DEGRADE_GRADE_EXCEPTION_RATIO and self.count > 1.0:
            return False
        if self.min_request_amount <= 0 or self.stat_interval_ms <= 0:
            return False
        return True


class DegradeRuleTensors(NamedTuple):
    resource_row: jax.Array    # int32[DR]
    grade: jax.Array           # int32[DR]
    threshold: jax.Array       # float32[DR] (max rt | ratio | count)
    trip_num: jax.Array        # int64[DR] ratio grades trip where
    trip_den: jax.Array        # int64[DR]   bad * trip_den >= trip_num * total
    min_request: jax.Array     # int32[DR]
    time_window_ms: jax.Array  # int64[DR]
    rules_by_row: jax.Array    # int32[R, K] degrade-rule ids per resource row

    @property
    def num_rules(self) -> int:
        return self.resource_row.shape[0]

    @property
    def slots(self) -> int:
        return self.rules_by_row.shape[1]


class DegradeState(NamedTuple):
    state: jax.Array          # int32[DR] BREAKER_*
    next_retry_ms: jax.Array  # int64[DR]
    win: W.RowWindow          # [DR, 1, 3] per-rule statIntervalMs window
    transitions: jax.Array    # int64[NUM_TRANSITIONS, DR] entries into each state
    last_open_ms: jax.Array   # int64[DR] clock of the last CLOSED -> OPEN; -1 never
    last_close_ms: jax.Array  # int64[DR] clock of the last HALF_OPEN -> CLOSED; -1 never


def make_degrade_state(rt: DegradeRuleTensors, stat_interval_ms: np.ndarray) -> DegradeState:
    dr = rt.num_rules
    # Each rule's statIntervalMs rides in the RowWindow bucket_ms vector.
    return DegradeState(
        state=jnp.zeros((dr,), jnp.int32),
        next_retry_ms=jnp.zeros((dr,), jnp.int64),
        win=W.make_row_window(dr, BREAKER_BUCKETS, NUM_CH, stat_interval_ms),
        transitions=jnp.zeros((NUM_TRANSITIONS, dr), jnp.int64),
        last_open_ms=jnp.full((dr,), -1, jnp.int64),
        last_close_ms=jnp.full((dr,), -1, jnp.int64),
    )


def _least_fraction_above(x: Fraction, n_max: int) -> Tuple[int, int]:
    """The least p / q > x with 1 <= q <= n_max, for 0 <= x < 1: a
    Stern-Brocot descent that keeps a/b <= x < c/d and takes each run of
    same-side mediant steps at once."""
    a, b, c, d = 0, 1, 1, 1
    while True:
        kr = min(math.floor((x * b - a) / (c - x * d)), (n_max - b) // d)
        a, b = a + kr * c, b + kr * d
        gap = x * b - a
        kl = (n_max - d) // b
        if gap > 0:
            kl = min(kl, math.ceil((c - x * d) / gap) - 1)
        c, d = c + kl * a, d + kl * b
        if kr == 0 and kl == 0:
            return c, d


def ratio_trip_fraction(threshold: float, trips_at_one: bool) -> Tuple[int, int]:
    """``(num, den)`` such that for window counts ``0 <= k <= n <=
    MAX_WINDOW_COUNT``, ``k * den >= num * n`` exactly when upstream's
    ``k * 1.0d / n > threshold`` holds in doubles.

    A double quotient exceeds ``threshold`` exactly when the true ratio
    exceeds the midpoint between ``threshold`` and the next double up
    (no ratio of such counts equals that midpoint: its denominator is a
    power of two past 2^53), so the answer is the least fraction above the
    midpoint whose denominator a window count can reach. ``trips_at_one``
    is the slow-ratio grade's rule that a ratio of 1.0 trips a threshold
    of 1.0 (or more). Both numbers are at most ``MAX_WINDOW_COUNT``, so
    either product fits in int64; ``(1, 0)`` never trips (a NaN threshold,
    which no double exceeds)."""
    if threshold < 0:
        return 0, 1
    if threshold >= 1.0:
        return (1, 1) if trips_at_one else (1, 0)
    if math.isnan(threshold):
        return 1, 0
    mid = (Fraction(threshold) + Fraction(math.nextafter(threshold, 2.0))) / 2
    return _least_fraction_above(mid, MAX_WINDOW_COUNT)


def ratio_trips(bad: jax.Array, total: jax.Array, num: jax.Array,
                den: jax.Array) -> jax.Array:
    """``bad / total > threshold`` as upstream's doubles decide it, for the
    threshold compiled to ``(num, den)`` by :func:`ratio_trip_fraction`."""
    return bad.astype(jnp.int64) * den >= num * total.astype(jnp.int64)


def compile_degrade_rules(
    rules: List[DegradeRule], registry: NodeRegistry, num_rows: int,
    min_slots: int = 0,
) -> Tuple[DegradeRuleTensors, np.ndarray]:
    """Returns (tensors, per-rule statIntervalMs host array — the window
    geometry is static per compile and feeds state construction)."""
    valid = [r for r in rules if r.is_valid()]
    dr = _round_up(len(valid), 8)
    res_row = np.full(dr, -1, np.int32)
    grade = np.zeros(dr, np.int32)
    threshold = np.zeros(dr, np.float32)
    trip_num = np.ones(dr, np.int64)
    trip_den = np.zeros(dr, np.int64)  # (1, 0): never trips
    min_request = np.full(dr, C.DEGRADE_DEFAULT_MIN_REQUEST_AMOUNT, np.int32)
    time_window_ms = np.zeros(dr, np.int64)
    stat_interval = np.zeros(dr, np.int64)  # 0 => unused row
    by_row: Dict[int, List[int]] = {}

    for i, r in enumerate(valid):
        row = registry.cluster_row(r.resource)
        res_row[i] = row
        grade[i] = r.grade
        threshold[i] = r.count
        if r.grade == C.DEGRADE_GRADE_RT:
            trip_num[i], trip_den[i] = ratio_trip_fraction(
                float(r.slow_ratio_threshold), trips_at_one=True)
        elif r.grade == C.DEGRADE_GRADE_EXCEPTION_RATIO:
            trip_num[i], trip_den[i] = ratio_trip_fraction(
                float(r.count), trips_at_one=False)
        min_request[i] = r.min_request_amount
        time_window_ms[i] = r.time_window * 1000
        stat_interval[i] = r.stat_interval_ms
        if row >= 0:
            by_row.setdefault(row, []).append(i)

    # 0 when no rules: the per-slot loop then vanishes at trace time,
    # so rule-free deployments pay nothing for this family (the
    # dropped-index scatters of an empty table still cost ~0.1ms/step
    # per scatter at batch 8192 on TPU). ``min_slots`` is the engine's
    # ratchet: crossing 0 -> 1 slots is a SHAPE change that retraces the
    # fused step, so the engine floors this at the widest slot count it
    # has ever compiled — one retrace when a family is first used, none
    # on later pushes (including dropping back to zero rules).
    k = max(min_slots, max((len(v) for v in by_row.values()), default=0))
    rules_by_row = np.full((num_rows, k), -1, np.int32)
    for row, ids in by_row.items():
        rules_by_row[row, : len(ids)] = ids

    t = DegradeRuleTensors(
        resource_row=jnp.asarray(res_row),
        grade=jnp.asarray(grade),
        threshold=jnp.asarray(threshold),
        trip_num=jnp.asarray(trip_num),
        trip_den=jnp.asarray(trip_den),
        min_request=jnp.asarray(min_request),
        time_window_ms=jnp.asarray(time_window_ms),
        rules_by_row=jnp.asarray(rules_by_row),
    )
    return t, stat_interval


class DegradeRuleManager(RuleManager):
    """Wholesale-swap registry (reference: ``DegradeRuleManager``)."""


# ---------------------------------------------------------------------------
# Device-side check (entry) and feed (exit)
# ---------------------------------------------------------------------------


class DegradeVerdict(NamedTuple):
    blocked: jax.Array  # bool[N]
    state: DegradeState
    slot: jax.Array  # int32[N] first-blocking rule slot (-1 = not blocked)


def check_degrade(
    rt: DegradeRuleTensors,
    ds: DegradeState,
    batch: EntryBatch,
    now_ms: jax.Array,
    candidate: jax.Array,  # bool[N] not blocked by earlier slots
) -> DegradeVerdict:
    """Vectorized ``CircuitBreaker.tryPass`` over the micro-batch."""
    n = batch.size
    blocked = jnp.zeros((n,), bool)
    # First blocking rule slot per request (sequential chain's throw
    # site) for decision attribution; -1 while unblocked.
    first_slot = jnp.full((n,), -1, jnp.int32)
    state = ds.state
    next_retry = ds.next_retry_ms
    probe_rules = []  # per-slot int32[N]: rule id probed by request i, or -1

    for k in range(rt.slots):
        rule_id = rt.rules_by_row.at[
            W.oob(batch.cluster_row, rt.rules_by_row.shape[0]), jnp.full((n,), k)
        ].get(mode="fill", fill_value=-1)
        has_rule = (rule_id >= 0) & candidate & (~blocked)

        st = state.at[W.oob(rule_id, rt.num_rules)].get(mode="fill", fill_value=C.BREAKER_CLOSED)
        nr = next_retry.at[W.oob(rule_id, rt.num_rules)].get(mode="fill", fill_value=0)

        is_open = st == C.BREAKER_OPEN
        is_half = st == C.BREAKER_HALF_OPEN
        retry_due = is_open & (now_ms >= nr)

        # One probe per rule per batch: first arrival with a due retry.
        # (Scatter-min of positions — O(N), no prefix machinery needed.)
        probe_ids = jnp.where(has_rule & retry_due, rule_id, -1)
        probe = has_rule & retry_due & first_in_segment(probe_ids, rt.num_rules)

        blocked_k = has_rule & (is_half | (is_open & ~probe))
        # has_rule already excludes earlier-slot blocks, so blocked_k is
        # true at most once per request across the loop.
        first_slot = jnp.where(blocked_k, k, first_slot)
        blocked = blocked | blocked_k

        # OPEN -> HALF_OPEN where a probe was admitted.
        state = state.at[W.oob(jnp.where(probe, rule_id, -1), rt.num_rules)].set(
            C.BREAKER_HALF_OPEN, mode="drop"
        )
        probe_rules.append(jnp.where(probe, rule_id, -1))

    # A probe granted at one slot whose request another slot then blocked
    # never completes, so its breaker would be stuck HALF_OPEN forever.
    # Revert those to OPEN (retry time untouched → re-probe-eligible at
    # once), the vectorized analog of the reference's terminate-hook
    # workaround for alibaba/Sentinel#1638. The revert marks its rules
    # _REVERTED first, so the record below reads every transition off the
    # rule axis: a rule OPEN before the batch and not after was probed
    # (one probe per rule per batch), and a marked one was reverted.
    for pr in probe_rules:
        dead = jnp.where(blocked, pr, -1)
        state = state.at[W.oob(dead, rt.num_rules)].set(_REVERTED, mode="drop")
    reverted = state == _REVERTED
    probed = (ds.state == C.BREAKER_OPEN) & (state != C.BREAKER_OPEN)
    state = jnp.where(reverted, C.BREAKER_OPEN, state)
    # Rows TO_OPEN, TO_HALF_OPEN, TO_CLOSED: a dense add, no scatter.
    transitions = ds.transitions + jnp.stack(
        [reverted, probed, jnp.zeros_like(probed)]).astype(jnp.int64)

    return DegradeVerdict(
        blocked=blocked,
        state=ds._replace(state=state, transitions=transitions),
        slot=first_slot)


def feed_degrade(
    rt: DegradeRuleTensors,
    ds: DegradeState,
    batch: ExitBatch,
    now_ms: jax.Array,
) -> DegradeState:
    """Vectorized ``onRequestComplete``: window feed + state transitions."""
    n = batch.cluster_row.shape[0]
    win = W.row_rotate(ds.win, now_ms)
    state = ds.state
    next_retry = ds.next_retry_ms

    valid = batch.cluster_row >= 0
    err = valid & batch.error

    # Varying-typed seeds so the lax.cond below type-checks under
    # shard_map (W.varying_zeros carries the rationale).
    half_bad = W.varying_zeros(batch.count, (rt.num_rules,), bool)
    half_good = W.varying_zeros(batch.count, (rt.num_rules,), bool)

    for k in range(rt.slots):
        rule_id = rt.rules_by_row.at[
            W.oob(batch.cluster_row, rt.rules_by_row.shape[0]), jnp.full((n,), k)
        ].get(mode="fill", fill_value=-1)
        has_rule = (rule_id >= 0) & valid

        # Exit batches with no breaker-ruled completions (degrade rules
        # are sparse in mixed deployments; small pipeline batches miss
        # them routinely) leave the window and probe votes provably
        # unchanged — skip the three window scatters via the cond.
        def _feed(args, rule_id=rule_id, has_rule=has_rule):
            win_, half_bad_, half_good_ = args
            rid = jnp.where(has_rule, rule_id, -1)
            thr = rt.threshold.at[W.oob(rule_id, rt.num_rules)].get(
                mode="fill", fill_value=0.0)
            grade = rt.grade.at[W.oob(rule_id, rt.num_rules)].get(
                mode="fill", fill_value=0)
            slow = has_rule & (grade == C.DEGRADE_GRADE_RT) & (
                batch.rt_ms.astype(jnp.float32) > thr
            )
            bad = jnp.where(grade == C.DEGRADE_GRADE_RT, slow, err & has_rule)

            cnt = jnp.where(has_rule, batch.count, 0)
            win_ = W.row_window_add(win_, now_ms, rid,
                                    jnp.full((n,), CH_TOTAL), cnt)
            win_ = W.row_window_add(win_, now_ms, rid,
                                    jnp.full((n,), CH_ERROR),
                                    jnp.where(err & has_rule, batch.count, 0))
            win_ = W.row_window_add(win_, now_ms, rid,
                                    jnp.full((n,), CH_SLOW),
                                    jnp.where(slow, batch.count, 0))

            # HALF_OPEN probe verdicts: any completion of the rule votes.
            st = state.at[W.oob(rule_id, rt.num_rules)].get(
                mode="fill", fill_value=-1)
            on_half = has_rule & (st == C.BREAKER_HALF_OPEN)
            half_bad_ = half_bad_.at[W.oob(
                jnp.where(on_half & bad, rule_id, -1), rt.num_rules)].set(
                True, mode="drop")
            half_good_ = half_good_.at[W.oob(
                jnp.where(on_half & ~bad, rule_id, -1), rt.num_rules)].set(
                True, mode="drop")
            return win_, half_bad_, half_good_

        win, half_bad, half_good = jax.lax.cond(
            jnp.any(has_rule), _feed, lambda args: args,
            (win, half_bad, half_good))

    # --- rule-axis transitions -------------------------------------------
    totals = W.row_window_totals(win, jnp.arange(rt.num_rules))  # [DR, 3]
    total = totals[:, CH_TOTAL]
    error = totals[:, CH_ERROR]
    enough = total >= rt.min_request

    # Ratio grades: upstream's strictly-greater double comparison, decided
    # exactly in integers (ratio_trip_fraction); the slow-call grade's
    # threshold of 1.0 also trips at a ratio of 1.0. The count grade
    # compares integer counts, exact in float32 below 2^24.
    bad = jnp.where(rt.grade == C.DEGRADE_GRADE_RT, totals[:, CH_SLOW], error)
    trip = ratio_trips(bad, total, rt.trip_num, rt.trip_den)
    trip = jnp.where(rt.grade == C.DEGRADE_GRADE_EXCEPTION_COUNT,
                     error.astype(jnp.float32) > rt.threshold, trip)
    trip = trip & enough

    is_closed = state == C.BREAKER_CLOSED
    is_half = state == C.BREAKER_HALF_OPEN

    # HALF_OPEN verdict: bad wins over good.
    tripped = is_closed & trip
    to_open = tripped | (is_half & half_bad)
    to_closed = is_half & half_good & (~half_bad)

    state = jnp.where(to_open, C.BREAKER_OPEN, state)
    state = jnp.where(to_closed, C.BREAKER_CLOSED, state)
    next_retry = jnp.where(to_open, now_ms + rt.time_window_ms, next_retry)

    # Closing resets the breaker's stats window (reference: resetStat()).
    win = win._replace(
        counts=jnp.where(to_closed[:, None, None], 0, win.counts)
    )
    return DegradeState(
        state=state, next_retry_ms=next_retry, win=win,
        transitions=ds.transitions + jnp.stack(
            [to_open, jnp.zeros_like(to_open), to_closed]).astype(jnp.int64),
        last_open_ms=jnp.where(tripped, now_ms, ds.last_open_ms),
        last_close_ms=jnp.where(to_closed, now_ms, ds.last_close_ms))
