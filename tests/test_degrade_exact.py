"""Breakers judged as upstream judges them: the ratio trip decided exactly
(no floating-point division on the device), two breakers on one resource
in rule order, and the state-change record, each against a plain
reference.

Upstream (alibaba/Sentinel ``ResponseTimeCircuitBreaker``,
``ExceptionCircuitBreaker``) trips on ``bad * 1.0d / total > threshold``
in Java doubles, which Python's float division reproduces.
"""

from fractions import Fraction

import jax
import numpy as np
import pytest

import sentinel_tpu as st
from sentinel_tpu.core import constants as C
from sentinel_tpu.core.batch import (EntryBatch, ExitBatch,
                                     make_entry_batch_np, make_exit_batch_np)
from sentinel_tpu.core.registry import NodeRegistry
from sentinel_tpu.models import degrade as D

RATIO_GRADES = (C.DEGRADE_GRADE_RT, C.DEGRADE_GRADE_EXCEPTION_RATIO)


def _upstream_trips(k: int, n: int, threshold: float, grade: int) -> bool:
    ratio = k * 1.0 / n
    if grade == C.DEGRADE_GRADE_RT:
        return ratio > threshold or (ratio == threshold == 1.0)
    return ratio > threshold


def _pairs_near_ties(threshold: float, totals: int):
    """Every (k, n), n in 1..totals, with k at n * threshold's floor and
    ceiling and one either side: every tie and its +-1 neighbours."""
    q = Fraction(str(threshold))
    ks, ns = [], []
    for n in range(1, totals + 1):
        lo = (n * q.numerator) // q.denominator
        for k in {lo - 1, lo, lo + 1, lo + 2}:
            if 0 <= k <= n:
                ks.append(k)
                ns.append(n)
    return np.asarray(ks, np.int32), np.asarray(ns, np.int32)


@pytest.mark.parametrize("grade", RATIO_GRADES, ids=("slow_ratio", "error_ratio"))
@pytest.mark.parametrize("threshold", (0.2, 0.5, 0.6, 1.0))
def test_ratio_trip_is_exact_at_every_tie(threshold, grade):
    rule = D.DegradeRule(
        resource="r", grade=grade, time_window=1,
        count=50 if grade == C.DEGRADE_GRADE_RT else threshold,
        slow_ratio_threshold=threshold)
    rt, _ = D.compile_degrade_rules([rule], NodeRegistry(8), 8)
    ks, ns = _pairs_near_ties(threshold, 2000)
    got = np.asarray(jax.jit(D.ratio_trips)(
        ks, ns, rt.trip_num[0], rt.trip_den[0]))
    want = np.asarray([_upstream_trips(int(k), int(n), threshold, grade)
                       for k, n in zip(ks, ns)])
    ties = ks.astype(np.int64) * Fraction(str(threshold)).denominator \
        == ns.astype(np.int64) * Fraction(str(threshold)).numerator
    assert ties.sum() >= 2000 // Fraction(str(threshold)).denominator
    np.testing.assert_array_equal(got, want)


def test_ratio_trip_fraction_matches_doubles_at_full_window_counts():
    """Random thresholds (short decimals and full doubles) against random
    counts up to the window's int32 limit, on either side of the cut."""
    rng = np.random.default_rng(25)
    for _ in range(300):
        t = float(rng.random())
        if rng.random() < 0.5:
            t = min(round(t, int(rng.integers(1, 7))), 0.999)
        num, den = D.ratio_trip_fraction(t, trips_at_one=False)
        assert 0 <= num <= D.MAX_WINDOW_COUNT and 0 < den <= D.MAX_WINDOW_COUNT
        for n in rng.integers(1, D.MAX_WINDOW_COUNT, 20):
            base = int(int(n) * t)
            for k in range(max(base - 1, 0), min(base + 3, int(n)) + 1):
                assert (k * den >= num * int(n)) == (k / int(n) > t), (t, k, n)
    for t in (-0.5, 0.0, float("nan"), 1.5, float("inf")):
        num, den = D.ratio_trip_fraction(t, trips_at_one=False)
        for k, n in ((0, 1), (1, 1), (0, 7), (1, 7), (3, 5), (5, 5)):
            assert (k * den >= num * n) == (k / n > t), (t, k, n)


# -- two breakers on one resource, against a plain serial loop --------------

SLOW_MAX_RT, SLOW_RATIO, ERROR_RATIO = 50, 0.6, 0.2
MIN_REQUEST, INTERVAL_MS = 5, 1000
WINDOWS_MS = (1000, 3000)  # retry windows of slot 0 and slot 1
CLOSED, OPEN, HALF_OPEN = C.BREAKER_CLOSED, C.BREAKER_OPEN, C.BREAKER_HALF_OPEN


class SerialBreakers:
    """Upstream's DegradeSlot over two breakers (slow ratio, then error
    ratio) on one resource, one request at a time, with the #1638 revert;
    counts every state change and the events the test has to cover."""

    def __init__(self):
        self.state = [CLOSED, CLOSED]
        self.retry = [0, 0]
        self.win = [[None, 0, 0] for _ in range(2)]  # start, total, bad
        self.counts = {k: [0, 0] for k in ("open", "half_open", "close")}
        self.last_open = [-1, -1]
        self.last_close = [-1, -1]
        self.events = {"revert": 0, "reopen": 0, "close": 0, "slot1_blocks": 0}

    def entry(self, now):
        """Returns the first blocking slot, or -1 when admitted."""
        probed = []
        for k in range(2):
            if self.state[k] == OPEN and now >= self.retry[k]:
                self.state[k] = HALF_OPEN
                self.counts["half_open"][k] += 1
                probed.append(k)
            elif self.state[k] != CLOSED:
                for p in probed:
                    self.state[p] = OPEN
                    self.counts["open"][p] += 1
                    self.events["revert"] += 1
                self.events["slot1_blocks"] += k == 1
                return k
        return -1

    def exit(self, now, rt_ms, error):
        for k in range(2):
            w = self.win[k]
            start = now - now % INTERVAL_MS
            if w[0] != start:
                w[:] = [start, 0, 0]
            bad = rt_ms > SLOW_MAX_RT if k == 0 else error
            w[1] += 1
            w[2] += bad
            if self.state[k] == HALF_OPEN:
                if bad:
                    self._open(k, now)
                    self.events["reopen"] += 1
                else:
                    self.state[k] = CLOSED
                    self.counts["close"][k] += 1
                    self.last_close[k] = now
                    w[1] = w[2] = 0
                    self.events["close"] += 1
            elif self.state[k] == CLOSED and w[1] >= MIN_REQUEST \
                    and w[2] * 1.0 / w[1] > (SLOW_RATIO, ERROR_RATIO)[k]:
                self._open(k, now)
                self.last_open[k] = now

    def _open(self, k, now):
        self.state[k] = OPEN
        self.retry[k] = now + WINDOWS_MS[k]
        self.counts["open"][k] += 1


def test_two_breakers_follow_a_serial_loop(engine, frozen_time):
    st.load_degrade_rules([
        st.DegradeRule(resource="dep", grade=C.DEGRADE_GRADE_RT,
                       count=SLOW_MAX_RT, slow_ratio_threshold=SLOW_RATIO,
                       time_window=WINDOWS_MS[0] // 1000,
                       min_request_amount=MIN_REQUEST,
                       stat_interval_ms=INTERVAL_MS),
        st.DegradeRule(resource="dep", grade=C.DEGRADE_GRADE_EXCEPTION_RATIO,
                       count=ERROR_RATIO,
                       time_window=WINDOWS_MS[1] // 1000,
                       min_request_amount=MIN_REQUEST,
                       stat_interval_ms=INTERVAL_MS),
    ])
    row = engine.registry.cluster_row("dep")
    entry = make_entry_batch_np(1)
    entry["cluster_row"][:] = row
    entry["count"][:] = 1
    exit_ = make_exit_batch_np(1)
    exit_.update(cluster_row=np.full(1, row, np.int32),
                 count=np.ones(1, np.int32), success=np.ones(1, bool))

    ref = SerialBreakers()
    rng = np.random.default_rng(1638)
    now = 1_700_000_000_000
    for _ in range(700):
        now += int(rng.integers(1, 60))
        # Bursts of bad calls in some seconds, healthy calls in others.
        phase = (now // 1000) % 4
        rt_ms = 200 if rng.random() < (0.1, 0.7, 0.7, 0.05)[phase] else 10
        error = bool(rng.random() < (0.05, 0.6, 0.1, 0.6)[phase])
        dec = engine.check_batch(EntryBatch(**entry), now_ms=now)
        want = ref.entry(now)
        assert int(np.asarray(dec.rule_slot)[0]) == want
        assert int(np.asarray(dec.reason)[0]) == (
            0 if want < 0 else int(C.BlockReason.DEGRADE))
        if want < 0:
            ref.exit(now, rt_ms, error)
            engine.complete_batch(ExitBatch(**dict(
                exit_, rt_ms=np.full(1, rt_ms, np.int32),
                error=np.full(1, error))), now_ms=now)
    assert all(v > 0 for v in ref.events.values()), ref.events

    rec = engine.breaker_transitions()
    assert [r.grade for r in rec["rules"]] == [
        C.DEGRADE_GRADE_RT, C.DEGRADE_GRADE_EXCEPTION_RATIO]
    np.testing.assert_array_equal(rec["toOpen"], ref.counts["open"])
    np.testing.assert_array_equal(rec["toHalfOpen"], ref.counts["half_open"])
    np.testing.assert_array_equal(rec["toClosed"], ref.counts["close"])
    np.testing.assert_array_equal(rec["lastOpenMs"], ref.last_open)
    np.testing.assert_array_equal(rec["lastCloseMs"], ref.last_close)


def test_breaker_transitions_exported(engine, frozen_time):
    from sentinel_tpu.telemetry.exporter import render_engine_metrics

    st.load_degrade_rules([
        st.DegradeRule(resource="ex", grade=C.DEGRADE_GRADE_EXCEPTION_RATIO,
                       count=0.5, time_window=5, min_request_amount=5)])
    for _ in range(5):
        with pytest.raises(ValueError):
            with st.entry("ex"):
                raise ValueError("boom")
    text = render_engine_metrics(engine)
    assert 'sentinel_tpu_breaker_transitions_total{to="open"} 1' in text
    assert 'sentinel_tpu_breaker_transitions_total{to="closed"} 0' in text
