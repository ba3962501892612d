"""Plain reference of a breakers-only deployment: several circuit breakers
(slow-ratio, error-ratio, error-count) on each resource, checked in rule
order as upstream's ``DegradeSlot`` checks them.

It replays the entry and exit batches the program was sent, at the clock
readings it was given, and returns each entry's verdict code; it counts
every breaker's state changes as upstream's
``CircuitBreakerStateChangeObserver`` would see them. Upstream admits
serially; the program admits micro-batches, and the reference follows the
batch semantics the program states for itself (docs/SEMANTICS.md
"Bounded deltas" 1, models/degrade.py), as ``reference/engine.py`` does:

- each breaker lets one probe through per batch once its retry time has
  come, taken by the first arrival that reached it (-> HALF_OPEN); every
  other arrival is blocked while it is OPEN or HALF_OPEN;
- a probe whose entry a later breaker of the same resource blocks is
  reverted to OPEN, its retry time untouched (upstream's terminate hook
  for alibaba/Sentinel#1638), and that counts as a change to OPEN;
- a breaker judges its thresholds once per exit batch on the batch's
  totals over one tumbling ``statIntervalMs`` bucket; in HALF_OPEN any bad
  completion in the batch re-opens it, else a good one closes it and
  clears its window.

Ratios are judged as upstream judges them, in doubles (``float64``):
``bad / total > threshold``, and a slow ratio of 1.0 also trips a
threshold of 1.0 or more. ``precision="bfloat16"`` is the control: every
count and ratio rounded to bfloat16 after each operation. Nothing here
imports the program.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

PASS, DEGRADE = 0, 2
CLOSED, OPEN, HALF_OPEN = 0, 1, 2
GRADE_RT, GRADE_ERROR_RATIO, GRADE_ERROR_COUNT = 0, 1, 2
KINDS = ("open", "half_open", "close")
NEVER = -(1 << 40)


def _float64(x):
    return np.asarray(x, np.float64)


def _bfloat16(x):
    """Rounded to bfloat16, carried as float64."""
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(np.float64)


class BreakersReference:
    """``layout``: the deployment's breakers (``benchmark/builders/
    breakers.py`` ``Layout``: ``n`` resources, each with ``slots`` rules of
    per-slot ``grade``, ``count``, ``slow_ratio``); ``precision``:
    ``float64`` as upstream states it, or ``bfloat16`` (the control)."""

    def __init__(self, layout, precision: str = "float64"):
        self.L = L = layout
        self.f = {"float64": _float64, "bfloat16": _bfloat16}[precision]
        shape = (L.n, L.slots)
        self.state = np.zeros(shape, np.int8)
        self.retry = np.zeros(shape, np.int64)
        self.start = np.full(shape, NEVER, np.int64)
        self.total = np.zeros(shape, np.int64)
        self.error = np.zeros(shape, np.int64)
        self.slow = np.zeros(shape, np.int64)
        # Per breaker: transitions into each of KINDS, and the clock of
        # its last trip (CLOSED -> OPEN) and last close; -1 = never.
        self.transitions = {k: np.zeros(shape, np.int64) for k in KINDS}
        self.last_open = np.full(shape, -1, np.int64)
        self.last_close = np.full(shape, -1, np.int64)

    def entry(self, now: int, res: np.ndarray) -> np.ndarray:
        """Verdict codes for one batch of single-token entries on resources
        ``res``."""
        blocked = np.zeros(len(res), bool)
        probes = []
        for k in range(self.L.slots):
            cand = np.flatnonzero(~blocked)
            rows = res[cand]
            st = self.state[rows, k]
            due = (st == OPEN) & (now >= self.retry[rows, k])
            probe = np.zeros(len(cand), bool)
            _, first = np.unique(rows[due], return_index=True)
            probe[np.flatnonzero(due)[first]] = True
            block = (st == HALF_OPEN) | ((st == OPEN) & ~probe)
            blocked[cand[block]] = True
            self.state[rows[probe], k] = HALF_OPEN
            self.transitions["half_open"][rows[probe], k] += 1
            probes.append((cand[probe], k))
        for pos, k in probes:
            dead = res[pos[blocked[pos]]]
            self.state[dead, k] = OPEN
            self.transitions["open"][dead, k] += 1
        return np.where(blocked, DEGRADE, PASS)

    def exit(self, now: int, res: np.ndarray, rt_ms: np.ndarray,
             error: np.ndarray) -> None:
        """One exit batch: completions on resources ``res``."""
        L, f = self.L, self.f
        cur = now - now % L.interval_ms
        stale = self.start != cur
        for arr in (self.total, self.error, self.slow):
            arr[stale] = 0
        self.start[:] = cur

        err = error.astype(bool)
        half_bad = np.zeros(self.state.shape, bool)
        half_good = np.zeros(self.state.shape, bool)
        for k in range(L.slots):
            slow = (L.grade[k] == GRADE_RT) & (rt_ms > L.count[k])
            bad = slow if L.grade[k] == GRADE_RT else err
            np.add.at(self.total[:, k], res, 1)
            np.add.at(self.error[:, k], res, err.astype(np.int64))
            np.add.at(self.slow[:, k], res, slow.astype(np.int64))
            on_half = self.state[res, k] == HALF_OPEN
            half_bad[res[on_half & bad], k] = True
            half_good[res[on_half & ~bad], k] = True

        trip = np.zeros(self.state.shape, bool)
        for k in range(L.slots):
            g, t = L.grade[k], f(L.count[k])
            total = np.maximum(f(self.total[:, k]), 1.0)
            if g == GRADE_RT:
                r, s = f(f(self.slow[:, k]) / total), f(L.slow_ratio[k])
                trip[:, k] = (r > s) | ((s >= 1.0) & (r >= 1.0))
            elif g == GRADE_ERROR_RATIO:
                trip[:, k] = f(f(self.error[:, k]) / total) > t
            else:
                trip[:, k] = f(self.error[:, k]) > t
        trip &= self.total >= L.min_request

        st = self.state
        tripped = (st == CLOSED) & trip
        to_open = tripped | ((st == HALF_OPEN) & half_bad)
        to_closed = (st == HALF_OPEN) & half_good & ~half_bad
        self.state = np.where(to_open, OPEN,
                              np.where(to_closed, CLOSED, st)).astype(np.int8)
        self.retry[to_open] = now + L.window_ms
        for arr in (self.total, self.error, self.slow):
            arr[to_closed] = 0
        self.transitions["open"] += to_open
        self.transitions["close"] += to_closed
        self.last_open[tripped] = now
        self.last_close[to_closed] = now
