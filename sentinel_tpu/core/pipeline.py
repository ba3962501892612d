"""Pipelined admission: async double-buffered micro-batched device steps.

SURVEY.md §7 hard part #1: a device dispatch costs ~10-100µs, so per-request
synchronous steps cap throughput at ~1/dispatch and serialize callers on the
engine lock. This module runs a collector thread that drains concurrently
submitted entries/exits into ONE fused step per cycle: p99 latency ≈ queue
wait + one step, and throughput scales with batch width instead of dispatch
rate — the host-side half of the reference's "statistics are lock-free"
property (all mutation rides one linearized step stream).

Double buffering (ISSUE 8): the collector never blocks on a verdict right
after dispatching it. Each cycle splits into three overlapped phases —

  * **stage** cycle N+1's batch into a recycled buffer-pool slot
    (``core/batch.py::BatchBufferPool`` — no per-cycle allocation) while
  * **compute** for cycle N is still in flight on the device (JAX async
    dispatch returns lazy ``Decisions``; the engine-lock critical section
    is enqueue-only), and
  * **harvest** resolves cycle N−1's tickets from the now-materialized
    device arrays.

Up to ``inflight_depth`` entry cycles ride the stream at once (default 2 =
classic double buffering, ``csp.sentinel.pipeline.inflight.depth``). Steps
are dispatched in submission order on one device stream with a strict data
dependency through the donated engine state, so completion order equals
dispatch order and the width-1 ordering proof extends unchanged to depth>1
(docs/SEMANTICS.md "Pipeline ordering").

Ordering guarantees: exits drain BEFORE entries each cycle, and submissions
are drained FIFO, so a thread's exit→entry program order is preserved
(THREAD-grade concurrency gauges stay exact). Batch widths come from the
engine's jit-cache ladder; a cycle never splits one submission. An idle
queue triggers an immediate harvest of everything in flight, so the
latency floor without concurrency stays one step, exactly as before.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Deque, List, Optional

from sentinel_tpu.core.batch import (
    BATCH_WIDTHS as LADDER,
    BatchBufferPool,
    EntryBatch,
    ExitBatch,
    MAX_PARAMS,
)


def _ladder_width(n: int) -> int:
    for w in LADDER:
        if n <= w:
            return w
    return LADDER[-1]


class _EntryTicket:
    __slots__ = ("fields", "done", "reason", "wait_us", "submit_ts")

    def __init__(self, fields):
        self.fields = fields  # dict of scalar batch fields (+params tuple)
        self.done = threading.Event()
        self.reason = -1
        self.wait_us = 0
        self.submit_ts = time.perf_counter()


class _ExitTicket:
    __slots__ = ("fields", "retried")

    def __init__(self, fields):
        self.fields = fields
        self.retried = False


class _InFlight:
    """One dispatched entry cycle awaiting harvest: its tickets, the lazy
    device Decisions, the pooled buffers the dispatch may still be
    reading, and the queue-wait already accrued at dispatch time."""

    __slots__ = ("entries", "dec", "bufs", "queue_wait_ms")

    def __init__(self, entries, dec, bufs, queue_wait_ms):
        self.entries = entries
        self.dec = dec
        self.bufs = bufs  # [(kind, buf), ...] released on harvest
        self.queue_wait_ms = queue_wait_ms


class Pipeline:
    """The collector loop bound to one engine."""

    def __init__(self, engine, max_batch: int = LADDER[-1],
                 linger_s: Optional[float] = None,
                 inflight_depth: Optional[int] = None,
                 pool_widths: Optional[tuple] = None):
        from sentinel_tpu.core.config import config as _cfg

        self.engine = engine
        self.max_batch = max_batch
        self.linger_s = (linger_s if linger_s is not None
                         else _cfg.pipeline_linger_us() / 1e6)
        self.inflight_depth = max(1, int(
            inflight_depth if inflight_depth is not None
            else _cfg.pipeline_inflight_depth()))
        widths = pool_widths
        if widths is None:
            # Every ladder width a cycle can actually hit: item counts
            # cap at max_batch, but the staged width rounds UP the
            # ladder (16 items -> a width-64 buffer).
            widths = _cfg.pipeline_pool_widths() \
                or tuple(w for w in LADDER
                         if w <= _ladder_width(max_batch))
        self.pool = BatchBufferPool(prealloc_widths=widths)
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.join_timeout_s = 2.0
        self.closed = False
        self.cycles = 0
        self.batched = 0
        self.harvests = 0
        self.fail_open_cycles = 0
        # In-flight bookkeeping: collector-thread-only mutation; readers
        # (stats, gauges) take len() snapshots, which the GIL keeps safe.
        self._inflight: Deque[_InFlight] = collections.deque()
        self.max_inflight = 0
        # Exit-only cycles have no harvest point of their own; their
        # buffers ride here until they can be folded into the NEXT
        # dispatched entry cycle's record — that cycle dispatches after
        # them on the ordered stream, so ITS harvest (not an older
        # cycle's) proves the exit transfer completed. Never released
        # from here directly.
        self._orphan_bufs: List[tuple] = []

    # -- submission (any thread) ------------------------------------------

    def submit_entry(self, fields) -> Optional[_EntryTicket]:
        """None once the pipeline is closed (caller takes the sync path)."""
        if self.closed:
            return None
        ticket = _EntryTicket(fields)
        self._queue.put(ticket)
        return ticket

    def submit_exit(self, fields) -> bool:
        if self.closed:
            return False
        self._queue.put(_ExitTicket(fields))
        return True

    # -- stats (any thread) ------------------------------------------------

    def inflight_depth_now(self) -> int:
        return len(self._inflight)

    def stats(self) -> dict:
        return {
            "cycles": self.cycles,
            "batched": self.batched,
            "harvests": self.harvests,
            "failOpenCycles": self.fail_open_cycles,
            "inflightDepth": len(self._inflight),
            "inflightDepthMax": self.max_inflight,
            "configuredDepth": self.inflight_depth,
            "poolAllocated": self.pool.allocated,
            "poolReused": self.pool.reused,
        }

    # -- the loop ----------------------------------------------------------

    def start(self) -> "Pipeline":
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._run, name="sentinel-pipeline", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        from sentinel_tpu.log.record_log import record_log

        self.closed = True  # reject new submissions first
        self._stop.set()
        thread, self._thread = self._thread, None
        if thread is not None:
            thread.join(timeout=self.join_timeout_s)
            if thread.is_alive():
                # The collector is wedged mid-cycle (a hung dispatch or a
                # compile that outlived the join budget). Running the
                # inline drain now would have TWO threads calling _cycle
                # against one engine state — the double-drain race. Refuse
                # loudly: stragglers resolve when (if) the collector's
                # final drain runs; callers time out into the documented
                # fail-open path either way.
                record_log.warn(
                    "pipeline collector still alive after %.1fs join; "
                    "refusing inline drain (collector owns the cycle)",
                    self.join_timeout_s)
                return
        # Collector is gone: flush stragglers that beat the closed flag,
        # then resolve anything still in flight. No harvest can run after
        # stop() returns — the deque is empty and the thread is dead.
        # Orphaned exit buffers are deliberately NOT recycled (nothing
        # proved their transfers done); the pool dies with the pipeline.
        # A dead backend mid-drain fails that cycle's tickets open inside
        # _cycle — swallow the re-raise and keep draining, so stop()
        # always returns with every ticket resolved and the caller's
        # stats fold always runs.
        while True:
            try:
                if not self._drain_cycle():
                    break
            except Exception as ex:  # noqa: BLE001 — keep draining
                record_log.warn("pipeline stop drain failed: %r", ex)
        self._harvest_all()
        self._orphan_bufs = []

    def _run(self):
        from sentinel_tpu.log.record_log import record_log

        while not self._stop.is_set():
            try:
                if not self._drain_cycle():
                    if self._inflight:
                        # Queue idle with work in flight: resolve the
                        # oldest cycle now — the no-concurrency latency
                        # floor stays one step.
                        self._harvest_one()
                        continue
                    # Nothing pending: block until the next submission,
                    # then fold it into a normal lingered cycle so a
                    # burst's first arrival doesn't run as its own
                    # width-1 step.
                    try:
                        item = self._queue.get(timeout=0.05)
                    except queue.Empty:
                        continue
                    self._drain_cycle(initial=[item])
            except Exception as ex:  # keep the loop alive, fail the cycle
                record_log.warn("pipeline cycle failed: %r", ex)
        self._harvest_all()  # resolve every in-flight ticket before exit

    def _drain_cycle(self, initial=None) -> bool:
        items = list(initial) if initial else []
        while len(items) < self.max_batch:
            try:
                items.append(self._queue.get_nowait())
            except queue.Empty:
                break
        if not items:
            return False
        if self.linger_s and len(items) < self.max_batch:
            # Brief linger folds late-arriving concurrent callers in.
            deadline = threading.Event()
            deadline.wait(self.linger_s)
            while len(items) < self.max_batch:
                try:
                    items.append(self._queue.get_nowait())
                except queue.Empty:
                    break
        self._cycle(items)
        # Depth cap: with the configured number of cycles already in
        # flight, resolve the oldest BEFORE staging another — this wait
        # overlaps the younger cycles' device compute, which is the whole
        # point of the double buffer.
        while len(self._inflight) >= self.inflight_depth:
            self._harvest_one()
        return True

    def _cycle(self, items: List):
        exits = [t for t in items if isinstance(t, _ExitTicket)]
        entries = [t for t in items if isinstance(t, _EntryTicket)]
        exit_bufs: List[tuple] = []
        # Exits first: program order for exit→entry on one thread. A failed
        # exit flush is re-enqueued once — dropping exits would leak the
        # concurrency gauge permanently.
        if exits:
            try:
                exit_bufs.append(("exit", self._flush_exits(exits)))
            except Exception:
                retry = [t for t in exits if not t.retried]
                for t in retry:
                    t.retried = True
                    self._queue.put(t)
                if not retry:  # second failure: give up loudly
                    raise
        if entries:
            try:
                self._flush_entries(entries, exit_bufs)
            except Exception:
                # The exit dispatch (if any) succeeded — its buffers are
                # merely awaiting a later sync point, like any orphan.
                self._orphan_bufs.extend(exit_bufs)
                for t in entries:
                    t.reason = -2  # engine error: caller passes unguarded
                    t.done.set()
                self.fail_open_cycles += 1
                raise
        elif exit_bufs:
            self._orphan_bufs.extend(exit_bufs)

    def _flush_exits(self, exits: List[_ExitTicket]):
        width = _ladder_width(len(exits))
        buf = self.pool.acquire("exit", width)
        for i, t in enumerate(exits):
            f = t.fields
            for k in ("cluster_row", "dn_row", "origin_row", "entry_in",
                      "count", "rt_ms", "success", "error"):
                buf[k][i] = f[k]
            for j, h in enumerate(f.get("params", ())[:MAX_PARAMS]):
                buf["param_hash"][i, j] = h
                buf["param_present"][i, j] = True
        try:
            self.engine._run_exit_batch(ExitBatch(**buf))
        except Exception:
            self.pool.release("exit", buf)
            raise
        return buf

    def _flush_entries(self, entries: List[_EntryTicket],
                       exit_bufs: List[tuple]):
        t0 = time.perf_counter()
        width = _ladder_width(len(entries))
        buf = self.pool.acquire("entry", width)
        for i, t in enumerate(entries):
            f = t.fields
            for k in ("cluster_row", "dn_row", "origin_row", "origin_id",
                      "origin_named", "context_id", "count", "prioritized",
                      "entry_in", "skip_cluster", "pre_blocked"):
                buf[k][i] = f[k]
            for j, h in enumerate(f.get("params", ())[:MAX_PARAMS]):
                buf["param_hash"][i, j] = h
                buf["param_present"][i, j] = True
        try:
            # Enqueue-only under the engine lock: JAX async dispatch
            # returns lazy Decisions; nothing blocks on the verdict here.
            dec = self.engine._run_entry_batch(EntryBatch(**buf))
        except Exception:
            self.pool.release("entry", buf)
            raise
        queue_wait_ms = (t0 - entries[0].submit_ts) * 1e3
        self.cycles += 1
        self.batched += len(entries)
        # Fold pending exit-only-cycle buffers in: they dispatched
        # BEFORE this entry step, so this record's harvest proves their
        # transfers completed too.
        bufs = [("entry", buf)] + exit_bufs + self._orphan_bufs
        self._orphan_bufs = []
        self._inflight.append(_InFlight(entries, dec, bufs, queue_wait_ms))
        if len(self._inflight) > self.max_inflight:
            self.max_inflight = len(self._inflight)

    # -- harvest -----------------------------------------------------------

    def _harvest_one(self) -> None:
        """Materialize the OLDEST in-flight cycle's verdicts and resolve
        its tickets. Blocking here overlaps every younger cycle's device
        compute; once this cycle's arrays are ready, the ordered stream
        guarantees every dispatch enqueued before it has completed, so
        its buffers (and any orphaned exit buffers) return to the pool."""
        rec = self._inflight.popleft()
        t0 = time.perf_counter()
        try:
            reasons, waits = self.engine.harvest_decisions(rec.dec)
        except Exception:
            # The async compute died after dispatch (backend failure
            # surfacing at materialization): fail this cycle's
            # tickets open — the engine has already dropped to a cold
            # state, and the next dispatch recovers. Buffers are NOT
            # recycled (the failed stream may still reference them);
            # losing a few pool slots to a rare outage beats corruption.
            for t in rec.entries:
                t.reason = -2
                t.done.set()
            self.fail_open_cycles += 1
            self.harvests += 1
            raise
        device_wait_ms = (time.perf_counter() - t0) * 1e3
        self.harvests += 1
        for i, t in enumerate(rec.entries):
            t.reason = int(reasons[i])
            t.wait_us = int(waits[i])
            t.done.set()
        self.engine.step_timer.record_pipeline(
            depth=len(self._inflight) + 1,
            queue_wait_ms=rec.queue_wait_ms,
            device_wait_ms=device_wait_ms)
        # The same split feeds the unified latency waterfall (ISSUE 18):
        # pipeline queue/device waits share the wire stages' log2
        # geometry and exporter family instead of a parallel one-off
        # pair. getattr: harvest is reachable during engine construction.
        waterfall = getattr(self.engine, "waterfall", None)
        if waterfall is not None:
            waterfall.observe_pipeline(rec.queue_wait_ms, device_wait_ms)
        for kind, buf in rec.bufs:
            self.pool.release(kind, buf)

    def _harvest_all(self) -> None:
        from sentinel_tpu.log.record_log import record_log

        while self._inflight:
            try:
                self._harvest_one()
            except Exception as ex:  # noqa: BLE001 — keep draining
                record_log.warn("pipeline drain harvest failed: %r", ex)
