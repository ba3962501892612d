"""Sampled decision traces: every Nth blocked entry, pulled off-device
asynchronously and retained host-side.

Aggregate attribution counters (``attribution.py``) say WHICH rule
family is blocking a resource; a trace says what one concrete rejected
request looked like — (resource, origin, reason, first-blocking rule
slot, window snapshot) — the per-request debuggability the reference
gets for free from its BlockException stack traces.

The dispatch path only enqueues device-array references (bounded queue;
an arriving batch is dropped when it is full — sampling is lossy by
design, and the drop is counted); a
daemon worker materializes them (``np.asarray`` blocks on the transfer
in ITS thread, never the step stream), subsamples blocked lanes at the
configured cadence, resolves node rows to names through the registry,
and snapshots the sampled rows' instant window (``engine.row_window``:
those rows alone, read back outside the engine lock). Served by the
``traces`` ops command and the dashboard.
"""

from __future__ import annotations

import atexit
import queue
import threading
from typing import Dict, List, Optional

import numpy as np

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.config import (
    DEFAULT_TELEMETRY_TRACE_CAPACITY,
    DEFAULT_TELEMETRY_TRACE_SAMPLE_EVERY,
    TELEMETRY_TRACE_CAPACITY,
    TELEMETRY_TRACE_SAMPLE_EVERY,
)
from sentinel_tpu.metrics.profiling import TRACES_WINDOW_READ, span
from sentinel_tpu.telemetry.attribution import encode_reason_code


class DecisionTraceBuffer:
    """Host-side ring of sampled blocked-entry traces for one engine."""

    def __init__(self, engine, sample_every: Optional[int] = None,
                 capacity: Optional[int] = None):
        from sentinel_tpu.core.config import config as _cfg

        self.engine = engine
        if sample_every is None:
            sample_every = _cfg.get_int(
                TELEMETRY_TRACE_SAMPLE_EVERY,
                DEFAULT_TELEMETRY_TRACE_SAMPLE_EVERY)
        if capacity is None:
            capacity = _cfg.get_int(TELEMETRY_TRACE_CAPACITY,
                                    DEFAULT_TELEMETRY_TRACE_CAPACITY)
        self.sample_every = max(0, int(sample_every))  # 0 = disabled
        self.capacity = max(1, int(capacity))
        self._ring: List[Dict] = []
        self._lock = threading.Lock()
        # Bounded hand-off: the dispatch path must never block on
        # telemetry. A full queue drops the batch (counted).
        self._queue: "queue.Queue" = queue.Queue(maxsize=8)
        # Serializes _process between the worker and drain(): drain must
        # not return while the worker is mid-item, or readers would see
        # partial counts.
        self._proc_lock = threading.Lock()
        self._dropped = 0
        self._errors = 0
        self._error_logged_ms = 0.0
        self._seen_blocked = 0
        self._recorded = 0
        self._window_reads = 0
        self._window_rows = 0
        self._worker: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # stop() is terminal until start(): a submit racing (or
        # following) stop must be a silent no-op, never a worker
        # resurrection — engine.close() joins the worker exactly once.
        self._stopped = False

    # -- dispatch-path side (cheap; may run under the engine lock) --------

    @staticmethod
    def _snap(col):
        """Host-mutable columns must be snapshotted at submit: the
        pipelined path stages batches in RECYCLED pool buffers
        (core/batch.py) that are re-filled with a later cycle's entries
        once harvested — by the time the worker runs, the original
        arrays may hold someone else's rows. jax Arrays are immutable
        (and decisions always are), so only numpy needs the copy."""
        return col.copy() if isinstance(col, np.ndarray) else col

    def submit(self, batch, decisions, now_ms: int) -> None:
        """Queue one dispatched batch's verdicts for async sampling.
        Never blocks: a full hand-off queue drops the batch (counted),
        and a stopped buffer ignores the submit entirely."""
        if self.sample_every <= 0 or self._stopped:
            return
        self._ensure_worker()
        # Only the four columns _process reads are retained — snapshot
        # them (µs for a ≤2048-row batch) so the batch's backing
        # buffers can be recycled the moment its cycle harvests.
        batch = batch._replace(
            cluster_row=self._snap(batch.cluster_row),
            origin_row=self._snap(batch.origin_row),
            count=self._snap(batch.count),
            entry_in=self._snap(batch.entry_in))
        try:
            self._queue.put_nowait((batch, decisions, int(now_ms)))
        except queue.Full:
            self._dropped += 1

    # -- worker side ------------------------------------------------------

    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            with self._lock:
                # Re-check _stopped under the lock: a submit that passed
                # the unsynchronized fast-path check while stop() ran to
                # completion must NOT resurrect the worker (stop() flips
                # _stopped under this same lock before swapping the
                # worker out).
                if self._stopped:
                    return
                if self._worker is None or not self._worker.is_alive():
                    self._stop.clear()
                    self._worker = threading.Thread(
                        target=self._run, name="sentinel-trace-pump",
                        daemon=True)
                    self._worker.start()
                    # The worker materializes device arrays; a daemon
                    # thread frozen inside an XLA call at interpreter
                    # teardown aborts the process ("terminate called
                    # without an active exception") — stop it BEFORE
                    # Python finalizes, even when the engine is never
                    # close()d (scripts, demos).
                    atexit.register(self.stop)

    def _pump_one(self) -> bool:
        """Dequeue + process ONE item, atomically under the processing
        lock. Dequeue-inside-the-lock is what makes drain() sound: an
        item is either still in the queue (drain takes it) or being
        processed under the lock drain must acquire — never invisibly
        in-flight between the two."""
        with self._proc_lock:
            try:
                item = self._queue.get_nowait()
            except queue.Empty:
                return False
            try:
                self._process(*item)
            except Exception as ex:
                # Telemetry must never take the engine down, but its own
                # failure must be observable: counted (exported as
                # sentinel_tpu_traces_errors) + rate-limited logged.
                self._errors += 1
                self._note_error(ex)
            return True

    def _note_error(self, ex: Exception) -> None:
        import time as _time

        now = _time.monotonic()
        if now - self._error_logged_ms >= 10.0:
            self._error_logged_ms = now
            try:
                from sentinel_tpu.log.record_log import record_log

                record_log.warn("trace worker failed to process a batch "
                                "(errors=%d): %r", self._errors, ex)
            except Exception:
                pass

    def _run(self) -> None:
        while not self._stop.is_set():
            if not self._pump_one():
                # Idle poll: sampled traces tolerate ~50ms of latency,
                # and the wait doubles as the stop signal.
                self._stop.wait(0.05)

    def drain(self) -> None:
        """Process everything queued, in the CALLER's thread, and wait
        out any item the worker has in flight — after drain() returns,
        every batch submitted BEFORE the call is fully reflected in the
        ring (deterministic reads for tests and the ops command)."""
        while self._pump_one():
            pass
        with self._proc_lock:  # worker mid-item: wait for it to land
            pass

    def _process(self, batch, decisions, now_ms: int) -> None:
        reasons = np.asarray(decisions.reason)
        blocked_idx = np.nonzero(reasons > 0)[0]
        if blocked_idx.size == 0:
            return
        n = self.sample_every
        with self._lock:
            seen = self._seen_blocked
            self._seen_blocked = seen + blocked_idx.size
        # The k-th blocked entry here (k from 1) is the (seen + k)-th
        # ever seen; every n-th of those is picked.
        picked = blocked_idx[n - 1 - seen % n::n]
        if picked.size == 0:
            return
        slots = np.asarray(decisions.rule_slot)
        rows = np.asarray(batch.cluster_row)
        origin_rows = np.asarray(batch.origin_row)
        counts = np.asarray(batch.count)
        entry_in = np.asarray(batch.entry_in)
        window = self._window_snapshot(rows[picked])
        # Device-row view: in slot mode this is CURRENT tenancy — a
        # trace materializing across an eviction may name the successor
        # (documented bounded race; the flight-recorder history is the
        # leak-proof surface, via per-stamp tenancy snapshots).
        metas = self.engine._device_metas()
        for i in picked.tolist():
            row = int(rows[i])
            orow = int(origin_rows[i])
            reason = int(reasons[i])
            slot = int(slots[i])
            trace = {
                "timestamp": now_ms,
                "resource": metas[row].resource if 0 <= row < len(metas)
                else f"row:{row}",
                "origin": metas[orow].origin if 0 <= orow < len(metas)
                else "",
                "reason": C.BlockReason(reason).name
                if reason in C.BlockReason._value2member_map_ else str(reason),
                "ruleSlot": slot,
                "reasonCode": encode_reason_code(reason, slot),
                "count": int(counts[i]),
                "entryIn": bool(entry_in[i]),
                "window": window.get(row, {}),
            }
            with self._lock:
                self._recorded += 1
                self._ring.append(trace)
                del self._ring[:-self.capacity]

    def _window_snapshot(self, rows) -> Dict[int, Dict]:
        """Instant-window view of the sampled rows at trace time — one
        jitted read of those rows alone per sampled batch; rows outside
        the device tensor are skipped."""
        rows = np.unique(np.asarray(rows, np.int64))
        rows = rows[(rows >= 0) & (rows < self.engine.capacity)]
        if rows.size == 0:
            return {}
        try:
            with span(TRACES_WINDOW_READ):
                totals, threads = self.engine.row_window(rows)
        except Exception:
            return {}
        with self._lock:
            self._window_reads += 1
            self._window_rows += int(rows.size)
        return {
            row: {
                "passQps": round(float(t[C.MetricEvent.PASS]), 2),
                "blockQps": round(float(t[C.MetricEvent.BLOCK]), 2),
                "curThreadNum": int(th),
            }
            for row, t, th in zip(rows.tolist(), totals, threads)
        }

    # -- read side --------------------------------------------------------

    def snapshot(self, limit: Optional[int] = None,
                 offset: int = 0) -> Dict:
        """Ring + sampler counters, newest trace first. ``limit=0`` is
        the counters-only read (exporter / `telemetry` command);
        ``offset`` skips the newest N traces (pagination)."""
        from sentinel_tpu.telemetry.timeseries import page_newest_first

        with self._lock:
            traces = list(self._ring)
            seen, recorded = self._seen_blocked, self._recorded
            reads, read_rows = self._window_reads, self._window_rows
        traces = page_newest_first(traces, limit, offset)
        traces.reverse()  # newest first
        return {
            "sampleEvery": self.sample_every,
            "capacity": self.capacity,
            "seenBlocked": seen,
            "recorded": recorded,
            "droppedBatches": self._dropped,
            "windowReads": reads,
            "windowRows": read_rows,
            "errors": self._errors,
            "traces": traces,
        }

    def start(self) -> "DecisionTraceBuffer":
        """Re-arm a stopped buffer (tests / engine restart); the worker
        itself spawns lazily on the next submit."""
        self._stopped = False
        return self

    def stop(self) -> None:
        """Terminal until :meth:`start`: joins the worker, and later
        submits are silent no-ops (never a worker resurrection)."""
        # Flip + swap under the spawn lock so a racing _ensure_worker
        # either sees _stopped or finishes spawning before we take the
        # worker out — never a fresh worker left behind after stop().
        # The join stays OUTSIDE the lock: the worker takes self._lock
        # in _process and would deadlock against a lock-holding join.
        with self._lock:
            self._stopped = True
            self._stop.set()
            worker, self._worker = self._worker, None
        if worker is not None:
            worker.join(timeout=2.0)
        atexit.unregister(self.stop)  # idempotent; re-armed on start()
