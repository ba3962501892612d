"""The batch driver's closed loop, depth 1 (``drivers/batch.py``), over a
breakers-only deployment whose dependencies go through outages: each
round's exits are slow or fail by the share the outage schedule gives
their resource at that moment of the window.

Which resources are hot, each resource's phase in the outage cycle, the
length of its outage and whether it slows down or fails are fixed by the
traffic's ``layout_seed``; the seed draws the arrivals (``pool_batches``
entry batches, Zipf over the resources) and two tables of
``outcome_batches`` rows of uniforms, one deciding slowness and one
errors. Round ``i`` sends entry batch ``i % pool_batches`` and judges its
exits by uniform row ``i % outcome_batches`` at the moment the round
builds them, on a clock that starts at the window's first round.

The breakers' state-change record (``engine.breaker_transitions()``) is
read in ``warm`` (a baseline) and once after the window; the check replays
the window through ``reference/breakers.py`` and compares verdicts and
each breaker's transitions."""

from __future__ import annotations

import numpy as np

from benchmark import common
from benchmark.drivers import batch
from benchmark.reference.breakers import (GRADE_ERROR_RATIO, GRADE_RT,
                                          KINDS, BreakersReference)

# Record keys of engine.breaker_transitions(), in reference.KINDS order.
RECORD_KEYS = ("toOpen", "toHalfOpen", "toClosed")


class Driver(batch.Driver):
    def __init__(self, dep, traffic: dict, seed: int):
        from sentinel_tpu.core.batch import (EntryBatch, ExitBatch,
                                             make_entry_batch_np,
                                             make_exit_batch_np)

        self.dep = dep
        self.sut = dep.engine
        L = dep.layout
        width, pool = int(traffic["width"]), int(traffic["pool_batches"])
        self.width = width
        fixed = np.random.default_rng(int(traffic["layout_seed"]))
        rank_to_res = fixed.permutation(L.n)
        self.cycle_ms = float(traffic["cycle_s"]) * 1000.0
        self.phase_ms = fixed.uniform(0.0, self.cycle_ms, L.n)
        lo, hi = traffic["outage_s"]
        self.outage_ms = fixed.uniform(lo * 1000.0, hi * 1000.0, L.n)
        self.slowdown = np.zeros(L.n, bool)
        self.slowdown[fixed.permutation(L.n)[: L.n // 2]] = True
        self.ramp_ms = float(traffic["ramp_s"]) * 1000.0
        self.p = {k: float(traffic[k]) for k in (
            "healthy_slow_p", "healthy_error_p", "slowdown_slow_p",
            "failure_error_p")}
        self.rt_ms = (np.int32(traffic["fast_rt_ms"]),
                      np.int32(traffic["slow_rt_ms"]))
        # The breaker each outage kind drives: the slow-ratio one for a
        # slowdown, the error-ratio one for a failure.
        self.driven_slot = np.where(
            self.slowdown, int(np.flatnonzero(L.grade == GRADE_RT)[0]),
            int(np.flatnonzero(L.grade == GRADE_ERROR_RATIO)[0]))

        rng = np.random.default_rng(seed)
        cdf = common.zipf_cdf(L.n, traffic["zipf"])
        self.res = rank_to_res[common.zipf_draw(rng, cdf, (pool, width))]
        rows = int(traffic["outcome_batches"])
        self.u_slow = rng.random((rows, width))
        self.u_err = rng.random((rows, width))

        self.entries, self.exits = [], []
        for b in range(pool):
            res = self.res[b]
            e = make_entry_batch_np(width)
            e["cluster_row"][:] = dep.cluster_rows[res]
            e["dn_row"][:] = dep.default_rows[res]
            e["context_id"][:] = dep.context_id
            e["count"][:] = 1
            self.entries.append(EntryBatch(**e))
            x = make_exit_batch_np(width)
            x["count"][:] = 1
            x["success"][:] = True
            self.exits.append(ExitBatch(**x))
        self.log = []
        self.errors = 0
        self.control = False
        self.at_ms = {}       # round -> its outcome clock, ms into the window
        self.clock = None     # the outcome clock, set at the window's start

    # -- the outage schedule ---------------------------------------------------

    def _into_cycle(self, res: np.ndarray, at_ms) -> np.ndarray:
        """Milliseconds since the start of each resource's current cycle,
        its outage being the first ``outage_ms`` of it."""
        return np.mod(np.asarray(at_ms, np.float64) - self.phase_ms[res],
                      self.cycle_ms)

    def outcome_at(self, i: int, at_ms: float):
        """Round ``i``'s exits at ``at_ms`` into the window: (error, rt_ms)
        of each entry."""
        res = self.res[i % len(self.entries)]
        pos = self._into_cycle(res, at_ms)
        ramp = np.where(pos < self.outage_ms[res],
                        np.minimum(pos / self.ramp_ms, 1.0), 0.0)
        p = self.p
        down = self.slowdown[res]
        p_slow = np.where(down, np.maximum(p["healthy_slow_p"],
                                           ramp * p["slowdown_slow_p"]),
                          p["healthy_slow_p"])
        p_err = np.where(down, p["healthy_error_p"],
                         np.maximum(p["healthy_error_p"],
                                    ramp * p["failure_error_p"]))
        row = i % len(self.u_slow)
        slow = self.u_slow[row] < p_slow
        err = self.u_err[row] < p_err
        return err, np.where(slow, self.rt_ms[1], self.rt_ms[0])

    def outcome(self, i: int):
        at = self.clock() - self.ms0
        self.at_ms[i] = at
        return self.outcome_at(i, at)

    # -- the harness's calls -----------------------------------------------------

    def warm(self) -> None:
        """A first read of the breakers' record, the baseline the window's
        transitions are counted from (a program that keeps none stops
        here, before it compiles), then the batch driver's warm-up, whose
        no-op batches change no breaker."""
        self.base = self._record()
        super().warm()

    def run(self, seconds: float, on_start, annotate: bool) -> dict:
        def start(t0):
            self.clock = common.EpochClock()
            self.ms0 = self.clock()
            on_start(t0)

        return super().run(seconds, start, annotate)

    def _record(self) -> dict:
        """The program's record as arrays over (resource, slot)."""
        rec = self.dep.engine.breaker_transitions()
        L = self.dep.layout
        slot_of = {int(r["grade"]): k for k, r in enumerate(L.rules)}
        idx = np.asarray([(int(r.resource[3:]), slot_of[r.grade])
                          for r in rec["rules"]], np.int64).reshape(-1, 2)
        out = {}
        for key in RECORD_KEYS + ("lastOpenMs", "lastCloseMs"):
            arr = np.full((L.n, L.slots), -1 if key.startswith("last") else 0,
                          np.int64)
            arr[idx[:, 0], idx[:, 1]] = rec[key]
            out[key] = arr
        return out

    def layer_data(self) -> dict:
        """The record, read once after the window, and the two lags it
        gives: ``breaker.open_lag_ms``, the mean over the breakers whose
        last trip fell inside an outage of their resource, in the window,
        on the breaker that outage drives, of the trip's clock reading
        minus the outage's start; ``breaker.close_lag_ms``, the same for
        last closes that fell after an outage's end (before the next
        start), from that end. The outcome clock and the program's clock
        start apart by under a millisecond."""
        self.after = self._record()
        n = self.dep.layout.n
        r = np.arange(n)
        k = self.driven_slot
        lags = {}
        for name, key, inside in (("breaker.open_lag_ms", "lastOpenMs", True),
                                  ("breaker.close_lag_ms", "lastCloseMs", False)):
            at = self.after[key][r, k]
            seen = at >= self.ms0
            pos = self._into_cycle(r, at - self.ms0)
            out = pos < self.outage_ms
            keep = seen & (out if inside else ~out)
            lag = pos if inside else pos - self.outage_ms
            lags[name] = float(lag[keep].mean()) if keep.any() else None
        self.lags = lags
        return lags

    def check(self) -> dict:
        """Every verdict of the window against the reference, the thread
        gauges after every admitted entry exited, and every breaker's
        transitions over the window, by kind, with the clock readings of
        its last trip and close. With the control on, the bfloat16
        reference is judged in the program's place on the window's batches
        and clock readings; the program's own readings go to the
        summary."""
        ref, _, mismatched, reasons = self._replay(control=False)
        program_record = {kind: self.after[key] - self.base[key]
                          for kind, key in zip(KINDS, RECORD_KEYS)}
        program_record["last_open"] = self.after["lastOpenMs"]
        program_record["last_close"] = self.after["lastCloseMs"]
        transitions = self._transition_mismatches(ref, program_record)
        self.summary = {
            "verdicts_by_reason": reasons,
            "breaker_transitions": {k: int(ref.transitions[k].sum())
                                    for k in KINDS},
            "program_transitions": {k: int(program_record[k].sum())
                                    for k in KINDS},
            "rounds": len(self.log), "lags_ms": self.lags,
        }
        if self.control:
            self.summary["program_verdict_mismatches"] = mismatched
            self.summary["program_transition_mismatches"] = transitions
            ref, ctl, mismatched, _ = self._replay(control=True)
            transitions = self._transition_mismatches(ref, dict(
                ctl.transitions, last_open=ctl.last_open,
                last_close=ctl.last_close))
        return {"verdict_mismatches": (mismatched, 0),
                "thread_gauge_max": (int(np.abs(self.gauges).max()), 0),
                "transition_mismatches": (transitions, 0)}

    @staticmethod
    def _transition_mismatches(ref, record) -> int:
        """Breakers whose count of some kind of transition, or whose last
        trip or close reading, differs from the reference's."""
        differ = np.zeros(ref.state.shape, bool)
        for k in KINDS:
            differ |= record[k] != ref.transitions[k]
        differ |= record["last_open"] != ref.last_open
        differ |= record["last_close"] != ref.last_close
        return int(differ.sum())

    def _replay(self, control: bool):
        """Replays the window's log through the reference: returns it, the
        bfloat16 reference where ``control`` (judged in the program's
        place: its verdicts decide which exits are sent), the verdicts of
        whichever is judged that differ from the reference's, and those
        verdicts by reason."""
        L = self.dep.layout
        ref = BreakersReference(L)
        ctl = BreakersReference(L, precision="bfloat16") if control else None
        mismatched = 0
        reasons = {}
        for i, (b, now, reason, now_x) in enumerate(self.log):
            res = self.res[b]
            want = ref.entry(now, res)
            if reason is None:
                mismatched += self.width
                continue
            if ctl is not None:
                reason = ctl.entry(now, res)
            mismatched += int((want != reason).sum())
            for code, n in zip(*np.unique(reason, return_counts=True)):
                reasons[int(code)] = reasons.get(int(code), 0) + int(n)
            ok = reason == 0
            err, rt = self.outcome_at(i, self.at_ms[i])
            for r in (ref, ctl) if ctl is not None else (ref,):
                r.exit(now_x, res[ok], rt[ok], err[ok])
        return ref, ctl, mismatched, reasons
