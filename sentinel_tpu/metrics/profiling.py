"""Step timing + program spans (SURVEY.md §5 "Tracing / profiling").

The reference has no in-process profiler (introspection stops at the
command API's live-stat dumps); the survey's TPU plan adds two things the
tensor design makes natural:

  * **per-step timing** — every device dispatch (entry/exit batch) is
    recorded: an enqueue wall time always (JAX dispatch is async, so this
    measures host-side submit cost), and a *sampled* synchronous wall
    time every ``sync_every``-th dispatch (block on the decisions) that
    estimates true end-to-end step latency without serializing the
    steady-state stream. The cadence is config-tunable
    (``csp.sentinel.profile.syncEvery``). Snapshots report p50/p95/p99
    per kind and feed the ``profile`` ops command plus the OpenMetrics
    exporter (sentinel_tpu/telemetry/).
  * **program spans** — :func:`span` marks host work at the seams of
    admission, the wire and the token batcher with a
    ``jax.profiler.TraceAnnotation``, so a profiler trace (``jax.profiler
    .start_trace``) shows it on the same timeline as the device's XLA
    modules and ops. With no trace running a span costs well under a
    microsecond. Spans sit at batch, call or loop-iteration grain, never
    per entry, and surround work, not idle waits (the batcher's linger,
    a deliberate wait that holds the device idle, is the one exception).
    The fused step's own sections carry ``jax.named_scope`` names
    (ops/step.py), which land in the compiled ops' metadata.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Dict, Optional

import numpy as np
from jax.profiler import TraceAnnotation

# Program span names: one place, all prefixed ``sentinel.`` so a trace
# reader tells them from any caller's own annotations.
# Host admission (core/engine.py), batch API and synchronous path alike.
ADMIT_CHECK_BATCH = "sentinel.admit.check_batch"        # all of check_batch
ADMIT_ENTRY_DISPATCH = "sentinel.admit.entry_dispatch"  # the entry jit call
ADMIT_REFRESH_SIGNALS = "sentinel.admit.refresh_signals"  # when it copies
ADMIT_DECISION_TRACES = "sentinel.admit.decision_traces"  # traces.submit
ADMIT_POPULATION = "sentinel.admit.population"          # _observe_population
ADMIT_COMPLETE_BATCH = "sentinel.admit.complete_batch"  # all of complete_batch
ADMIT_EXIT_DISPATCH = "sentinel.admit.exit_dispatch"    # the exit jit call
# Decision-trace worker (telemetry/trace_ring.py): the sampled rows' window
# read, dispatch and readback.
TRACES_WINDOW_READ = "sentinel.traces.window_read"
# Wire frontend (cluster/reactor.py).
REACTOR_READ = "sentinel.reactor.read"
REACTOR_SUBMIT = "sentinel.reactor.submit"
REACTOR_FLUSH = "sentinel.reactor.flush"
REACTOR_RESOLVE = "sentinel.reactor.resolve"
# Token batcher (cluster/server.py _Batcher).
BATCHER_LINGER = "sentinel.batcher.linger"
BATCHER_DISPATCH = "sentinel.batcher.dispatch"
BATCHER_HARVEST = "sentinel.batcher.harvest"
# Token client (cluster/client.py).
CLIENT_SEND = "sentinel.client.send"
CLIENT_RECEIVE = "sentinel.client.receive"


def span(name: str) -> TraceAnnotation:
    """A program span: ``with span(ADMIT_CHECK_BATCH): ...`` records the
    block on the profiler's timeline while a trace runs."""
    return TraceAnnotation(name)


def _pct(values, q: float) -> float:
    """Exact nearest-rank quantile: the ceil(q·n/100)-th order statistic.

    ``np.percentile``'s default linear interpolation invents values
    between samples — p99 of 7 samples reported ~max-ε, a latency no
    dispatch ever exhibited, and under-reported the true worst sample.
    Nearest-rank always returns an OBSERVED sample: exact at any n
    (p99 of 7 samples = the max), and converging to the interpolated
    estimate as the ring fills."""
    vals = np.sort(np.asarray(values, dtype=np.float64))
    idx = max(0, math.ceil(q / 100.0 * vals.size) - 1)
    return float(vals[idx])


# Cumulative step-duration histogram geometry (log2 edges, ms, + +Inf
# overflow): sub-ms resolution at the bottom because a routed CPU/TPU
# step is tens of µs to tens of ms; the top edge clears any cold-compile
# outlier a sampled dispatch can observe.
STEP_DURATION_EDGES_MS = (
    0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0,
    1024.0, 2048.0, 4096.0)
NUM_STEP_DURATION_BUCKETS = len(STEP_DURATION_EDGES_MS) + 1


class StepTimer:
    """Lock-guarded rolling timing stats for device step dispatches."""

    def __init__(self, ring: int = 512, sync_every: int = 64):
        self._lock = threading.Lock()
        self._ring = ring
        self.sync_every = sync_every
        self._counts: Dict[str, int] = {}
        self._entries: Dict[str, int] = {}
        self._enqueue: Dict[str, list] = {}
        self._sync: Dict[str, list] = {}
        # CUMULATIVE per-kind histogram of the sampled synchronous step
        # walls. The rolling rings above answer "what did recent steps
        # look like" (post-hoc, cleared on reset); these counters are
        # monotone for the engine's lifetime so scrapers — and step-
        # latency SLO burn rates over them — can rate() the series
        # (`sentinel_tpu_step_duration_*`). Deliberately NOT cleared by
        # reset(): a profile-command reset must never make a counter
        # family go backwards mid-scrape.
        self._duration_hist: Dict[str, list] = {}
        self._duration_sum_ms: Dict[str, float] = {}
        # Pipelined-admission decomposition (ISSUE 8): per harvested
        # cycle, the queue wait (oldest ticket submit -> dispatch) and
        # the device wait (harvest blocking on the materialized
        # verdicts), plus the in-flight depth observed at harvest. The
        # split answers the question BENCH_7's t1 pathology raised:
        # is a slow pipelined op queue wait (host serialization) or
        # device wait (step wall)?
        self._pl_queue: list = []
        self._pl_device: list = []
        self._pl_depth_sum = 0
        self._pl_cycles = 0

    def record(self, kind: str, batch_n: int, enqueue_ms: float,
               sync_ms: Optional[float] = None) -> None:
        with self._lock:
            self._counts[kind] = self._counts.get(kind, 0) + 1
            self._entries[kind] = self._entries.get(kind, 0) + batch_n
            buf = self._enqueue.setdefault(kind, [])
            buf.append(enqueue_ms)
            del buf[:-self._ring]
            if sync_ms is not None:
                sbuf = self._sync.setdefault(kind, [])
                sbuf.append(sync_ms)
                del sbuf[:-self._ring]
                hist = self._duration_hist.setdefault(
                    kind, [0] * NUM_STEP_DURATION_BUCKETS)
                b = 0
                while b < len(STEP_DURATION_EDGES_MS) \
                        and sync_ms > STEP_DURATION_EDGES_MS[b]:
                    b += 1
                hist[b] += 1
                self._duration_sum_ms[kind] = \
                    self._duration_sum_ms.get(kind, 0.0) + sync_ms

    def record_pipeline(self, depth: int, queue_wait_ms: float,
                        device_wait_ms: float) -> None:
        """Record one harvested pipeline cycle's wait decomposition."""
        with self._lock:
            self._pl_cycles += 1
            self._pl_depth_sum += depth
            self._pl_queue.append(queue_wait_ms)
            del self._pl_queue[:-self._ring]
            self._pl_device.append(device_wait_ms)
            del self._pl_device[:-self._ring]

    def pipeline_snapshot(self) -> Dict[str, float]:
        """Queue-wait vs device-wait split + mean achieved in-flight
        depth over recorded harvests (empty-safe zeros)."""
        with self._lock:
            out: Dict[str, float] = {
                "harvestedCycles": self._pl_cycles,
                "meanInflightDepth": round(
                    self._pl_depth_sum / self._pl_cycles, 3)
                if self._pl_cycles else 0.0,
            }
            for name, ring in (("queueWait", self._pl_queue),
                               ("deviceWait", self._pl_device)):
                if ring:
                    out[f"{name}P50Ms"] = round(_pct(ring, 50), 3)
                    out[f"{name}P95Ms"] = round(_pct(ring, 95), 3)
                else:
                    out[f"{name}P50Ms"] = 0.0
                    out[f"{name}P95Ms"] = 0.0
            return out

    def duration_histogram(self) -> Dict[str, Dict]:
        """Cumulative sampled-step-wall histogram per kind:
        ``{kind: {"buckets": [per-bucket counts], "sumMs": float,
        "count": int}}`` indexed like :data:`STEP_DURATION_EDGES_MS`
        plus the +Inf overflow."""
        with self._lock:
            return {
                kind: {
                    "buckets": list(hist),
                    "sumMs": self._duration_sum_ms.get(kind, 0.0),
                    "count": sum(hist),
                }
                for kind, hist in self._duration_hist.items()
            }

    def should_sync(self, kind: str) -> bool:
        """True on the sampled dispatches that should block and measure."""
        with self._lock:
            return self._counts.get(kind, 0) % self.sync_every == 0

    def snapshot(self, reset: bool = False) -> Dict[str, Dict[str, float]]:
        """Read (and with ``reset=True`` atomically clear) the stats —
        one lock acquisition, so a poller doing read-and-clear never
        drops dispatches recorded between the two operations."""
        with self._lock:
            out = {}
            for kind, n in self._counts.items():
                enq = self._enqueue.get(kind, []) or [0.0]
                sync = self._sync.get(kind)
                row = {
                    "dispatches": n,
                    "entries": self._entries.get(kind, 0),
                    "enqueueP50Ms": round(_pct(enq, 50), 3),
                    "enqueueP95Ms": round(_pct(enq, 95), 3),
                    "enqueueP99Ms": round(_pct(enq, 99), 3),
                }
                if sync:
                    row["stepP50Ms"] = round(_pct(sync, 50), 3)
                    row["stepP95Ms"] = round(_pct(sync, 95), 3)
                    row["stepP99Ms"] = round(_pct(sync, 99), 3)
                    row["stepSamples"] = len(sync)
                out[kind] = row
            if reset:
                self._counts.clear()
                self._entries.clear()
                self._enqueue.clear()
                self._sync.clear()
            return out

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._entries.clear()
            self._enqueue.clear()
            self._sync.clear()


_DISPATCH_SPANS = {"entry": ADMIT_ENTRY_DISPATCH, "exit": ADMIT_EXIT_DISPATCH}


def timed_call(timer: StepTimer, kind: str, batch_n: int, fn, *args,
               sample: bool = True, **kwargs):
    """Run ``fn(*args, **kwargs)`` (a jitted ``"entry"`` or ``"exit"``
    dispatch returning a pytree) inside its program span, recording the
    enqueue wall always and, with ``sample``, blocking for a true step
    wall on sampled dispatches. A caller that reads the result back
    itself (the batch API) passes ``sample=False``."""
    do_sync = sample and timer.should_sync(kind)
    t0 = time.perf_counter()
    with span(_DISPATCH_SPANS[kind]):
        out = fn(*args, **kwargs)
    enqueue_ms = (time.perf_counter() - t0) * 1e3
    sync_ms = None
    if do_sync:
        import jax

        jax.block_until_ready(out)
        sync_ms = (time.perf_counter() - t0) * 1e3
    timer.record(kind, batch_n, enqueue_ms, sync_ms)
    return out
