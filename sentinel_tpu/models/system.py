"""System rules: whole-process adaptive protection (BBR-style).

Reference surface (SURVEY.md §2.1 "SystemSlot"): ``SystemRule`` (qps,
maxThread, avgRt, highestSystemLoad, highestCpuUsage), ``SystemRuleManager``
(merges all rules into one effective minimum per dimension;
``checkSystem``/``checkBbr``), ``SystemStatusListener`` (1 Hz OS poll).
Only inbound traffic (``EntryType.IN``) is guarded, against the global
``Constants.ENTRY_NODE`` aggregate. Upstream paths: ``core:slots/system/``
(reference mount was empty; citations are upstream-layout paths).

TPU-native design: the five effective thresholds compile to one small f32
tensor; load1/CPU are host-sampled at 1 Hz (``SystemStatusListener`` below,
reading ``/proc``) and carried in device state as a 2-element signal vector,
so the check itself is pure: ENTRY_NODE row stats + within-batch prefix +
signals → blocked mask. The BBR check uses the minute-window's per-second
max success count and the 1s window's min RT, mirroring
``maxSuccessQps() * minRt() / 1000``.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.rule_manager import RuleManager
from sentinel_tpu.core.batch import EntryBatch
from sentinel_tpu.core.registry import ENTRY_ROW
from sentinel_tpu.ops import fixpoint as FX
from sentinel_tpu.ops import window as W

NOT_SET = C.SYSTEM_RULE_NOT_SET  # -1.0

SIG_LOAD = 0
SIG_CPU = 1
NUM_SIGNALS = 2


@dataclass
class SystemRule:
    highest_system_load: float = NOT_SET
    highest_cpu_usage: float = NOT_SET
    qps: float = NOT_SET
    max_thread: float = NOT_SET
    avg_rt: float = NOT_SET
    # Staged rollout (sentinel_tpu/rollout/): see FlowRule.candidate_set.
    candidate_set: Optional[str] = None
    rollout_stage: Optional[str] = None

    def is_valid(self) -> bool:
        return any(
            v is not None and v >= 0
            for v in (
                self.highest_system_load,
                self.highest_cpu_usage,
                self.qps,
                self.max_thread,
                self.avg_rt,
            )
        )


class SystemRuleTensors(NamedTuple):
    """Effective thresholds (min across loaded rules; NOT_SET = unguarded)."""

    qps: jax.Array         # f32[] scalar
    max_thread: jax.Array  # f32[]
    avg_rt: jax.Array      # f32[]
    load: jax.Array        # f32[]
    cpu: jax.Array         # f32[]
    enabled: jax.Array     # bool[] any dimension set


def compile_system_rules(rules: List[SystemRule]) -> SystemRuleTensors:
    """Merge to one threshold per dimension (``SystemRuleManager.loadRules``)."""

    def eff(values: List[float]) -> float:
        vs = [v for v in values if v is not None and v >= 0]
        return min(vs) if vs else NOT_SET

    valid = [r for r in rules if r.is_valid()]
    qps = eff([r.qps for r in valid])
    max_thread = eff([r.max_thread for r in valid])
    avg_rt = eff([r.avg_rt for r in valid])
    load = eff([r.highest_system_load for r in valid])
    cpu = eff([r.highest_cpu_usage for r in valid])
    enabled = any(v >= 0 for v in (qps, max_thread, avg_rt, load, cpu))
    f = lambda v: jnp.asarray(v, jnp.float32)
    return SystemRuleTensors(
        qps=f(qps), max_thread=f(max_thread), avg_rt=f(avg_rt),
        load=f(load), cpu=f(cpu), enabled=jnp.asarray(enabled),
    )


class SystemRuleManager(RuleManager):
    """Wholesale-swap registry (reference: ``SystemRuleManager``)."""


def check_system(
    rt: SystemRuleTensors,
    signals: jax.Array,      # f32[NUM_SIGNALS] host-sampled [load1, cpu]
    w1: W.Window,
    w60: W.Window,
    sec_counts: jax.Array,   # int32[E, R] live current-second accumulator
    cur_threads: jax.Array,  # int32[R]
    batch: EntryBatch,
    candidate: jax.Array,    # bool[N]
    now_ms: jax.Array,
    spec1: Optional[W.WindowSpec] = None,  # w1 geometry (engine may retune)
) -> jax.Array:
    """Vectorized ``SystemRuleManager.checkSystem``: bool[N] blocked.

    ``w60`` holds only folded (completed) seconds; the live second lives in
    ``sec_counts`` (the step's staging accumulator). The BBR read masks
    stale buckets itself. Survivor resolution follows check_flow's
    convention (ops/fixpoint.py): uniform-count batches take the classic
    two passes reproducing the serial "blocked requests never count"
    rule exactly; MIXED acquire counts iterate to the fixpoint — the
    global IN prefix has the same truncated-second-pass over-admission
    class the flow and param sweeps had (r5).
    """

    def _blocked_for(survivors):
        return _eval_system(rt, signals, w1, w60, sec_counts, cur_threads,
                            batch, candidate, survivors=survivors,
                            now_ms=now_ms, spec1=spec1)

    # Only IN entries feed the global prefix: an OUT entry's odd count
    # must not push a uniform-IN batch off the exact two-pass hot path.
    survivors = FX.survivor_fixpoint(candidate, _blocked_for, batch.count,
                                     relevant=batch.entry_in)
    return _blocked_for(survivors)


def _eval_system(
    rt: SystemRuleTensors,
    signals: jax.Array,
    w1: W.Window,
    w60: W.Window,
    sec_counts: jax.Array,
    cur_threads: jax.Array,
    batch: EntryBatch,
    candidate: jax.Array,
    survivors: jax.Array,
    now_ms: jax.Array,
    spec1: Optional[W.WindowSpec] = None,
) -> jax.Array:
    n = batch.size
    applicable = candidate & batch.entry_in & rt.enabled

    # Within-batch arrival prefixes on the single ENTRY_NODE row: exclusive
    # cumsum over inbound survivors.
    contrib = jnp.where(survivors & batch.entry_in, batch.count, 0)
    tok_prefix = jnp.cumsum(contrib) - contrib
    # int32, not the x64 default of a python-int where: an int64 cumsum
    # lowers on TPU to a u32-pair reduce-window that overflows scoped VMEM
    # inside the pod step (tests/test_tpu_compile.py).
    ent_contrib = (survivors & batch.entry_in).astype(jnp.int32)
    ent_prefix = jnp.cumsum(ent_contrib) - ent_contrib

    # Per-second normalization of window sums (reference passQps divides by
    # the interval seconds) — 1.0 under the default geometry.
    qps_scale = jnp.float32(
        1000.0 / (spec1.interval_ms if spec1 is not None
                  else C.SECOND_WINDOW_MS))
    totals = W.all_totals(w1)[ENTRY_ROW]  # [E]
    pass_qps = (totals[C.MetricEvent.PASS].astype(jnp.float32)
                + tok_prefix.astype(jnp.float32)) * qps_scale
    succ = jnp.maximum(totals[C.MetricEvent.SUCCESS].astype(jnp.float32), 1.0)
    cur_rt = totals[C.MetricEvent.RT].astype(jnp.float32) / succ
    threads = cur_threads[ENTRY_ROW].astype(jnp.float32) + ent_prefix.astype(jnp.float32)

    qps_ok = (rt.qps < 0) | (pass_qps + batch.count.astype(jnp.float32) <= rt.qps)
    thr_ok = (rt.max_thread < 0) | (threads <= rt.max_thread)
    rt_ok = (rt.avg_rt < 0) | (cur_rt <= rt.avg_rt)

    # BBR gate on load: estimated capacity = maxSuccessQps · minRt / 1000.
    # maxSuccessQps: the minute window's busiest 1s bucket — fresh folded
    # buckets (masked) plus the live staged second, exactly the reference's
    # "partial current bucket counts too" behavior.
    spec_60s = W.WindowSpec(C.MINUTE_WINDOW_MS, C.MINUTE_BUCKETS)
    fresh = W.staleness_mask(w60, now_ms, spec_60s)
    bucket_succ = jnp.where(
        fresh, w60.counts[:, C.MetricEvent.SUCCESS, ENTRY_ROW], 0
    ).astype(jnp.float32)
    max_succ_qps = jnp.maximum(
        jnp.max(bucket_succ),
        sec_counts[C.MetricEvent.SUCCESS, ENTRY_ROW].astype(jnp.float32),
    )
    min_rt = jnp.min(w1.min_rt[:, ENTRY_ROW]).astype(jnp.float32)
    min_rt = jnp.where(min_rt >= W.MIN_RT_EMPTY, 0.0, min_rt)
    bbr_ok = (threads <= 1.0) | (threads <= max_succ_qps * min_rt / 1000.0)
    load_ok = (rt.load < 0) | (signals[SIG_LOAD] <= rt.load) | bbr_ok

    cpu_ok = (rt.cpu < 0) | (signals[SIG_CPU] <= rt.cpu)

    ok = qps_ok & thr_ok & rt_ok & load_ok & cpu_ok
    return applicable & (~ok)


class SystemStatusListener:
    """1 Hz host sampler of load1 + process-visible CPU usage.

    Reference: ``SystemStatusListener`` polls ``OperatingSystemMXBean``.
    Here: ``/proc/loadavg`` and a ``/proc/stat`` delta. Thread-safe reads of
    the latest sample via :meth:`snapshot`.
    """

    def __init__(self, interval_s: float = 1.0):
        self.interval_s = interval_s
        self._lock = threading.Lock()
        self._load = -1.0
        self._cpu = -1.0
        self._prev_stat = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        self._sample()  # prime synchronously so the first check has data
        self._thread = threading.Thread(
            target=self._run, name="sentinel-system-status", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            # Join so a stop()-then-start() can't leave two samplers racing
            # on the cleared stop event.
            self._thread.join(timeout=self.interval_s + 1.0)
        self._thread = None

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            self._sample()

    def _sample(self) -> None:
        load = self._read_load()
        cpu = self._read_cpu()
        with self._lock:
            if load is not None:
                self._load = load
            if cpu is not None:
                self._cpu = cpu

    def snapshot(self) -> np.ndarray:
        with self._lock:
            return np.asarray([self._load, self._cpu], np.float32)

    @staticmethod
    def _read_load() -> Optional[float]:
        try:
            with open("/proc/loadavg") as f:
                return float(f.read().split()[0])
        except (OSError, ValueError, IndexError):
            return None

    def _read_cpu(self) -> Optional[float]:
        try:
            with open("/proc/stat") as f:
                parts = f.readline().split()
            if parts[0] != "cpu":
                return None
            vals = [int(x) for x in parts[1:8]]
        except (OSError, ValueError, IndexError):
            return None
        idle = vals[3] + vals[4]  # idle + iowait
        total = sum(vals)
        prev = self._prev_stat
        self._prev_stat = (total, idle)
        if prev is None or total <= prev[0]:
            return None
        dt, di = total - prev[0], idle - prev[1]
        return max(0.0, min(1.0, 1.0 - di / dt)) if dt > 0 else None
