"""The decision-trace worker's window read (telemetry/trace_ring.py over
``engine.row_window``): it reads only the sampled rows, never through
``row_stats()``, picks the same entries as a per-entry counting loop, and
compiles nothing once ``engine.warmup()`` has run.

The reference for the window values is ``row_stats()`` at the same frozen
clock reading; the reference for the picks is the loop the worker ran
before, kept here as ``_loop_picks``.
"""

import numpy as np
import pytest

import sentinel_tpu as st
from sentinel_tpu.core import constants as C
from sentinel_tpu.core.batch import (
    Decisions,
    EntryBatch,
    ExitBatch,
    make_entry_batch_np,
    make_exit_batch_np,
)
from sentinel_tpu.core.engine import ROW_READ_WIDTHS
from sentinel_tpu.telemetry.trace_ring import DecisionTraceBuffer

BASE_MS = 1_700_000_000_000


def _lanes(engine, resources, exit_=False):
    """An entry (or exit) batch with one lane per resource name."""
    reg = engine.registry
    parent = reg.entrance_row("ctx")
    buf = (make_exit_batch_np if exit_ else make_entry_batch_np)(
        len(resources))
    for i, res in enumerate(resources):
        cr, dn, orow, oid = reg.resolve_entry(res, "ctx", "", parent,
                                              int(C.EntryType.OUT))
        buf["cluster_row"][i] = cr
        buf["dn_row"][i] = dn
        buf["origin_row"][i] = orow
        buf["count"][i] = 1
        if exit_:
            buf["rt_ms"][i] = 3 + i % 40
            buf["success"][i] = True
            buf["error"][i] = i % 5 == 0
        else:
            buf["origin_id"][i] = oid
            buf["context_id"][i] = reg.context_id("ctx")
    return (ExitBatch if exit_ else EntryBatch)(**buf)


def _busy_engine(engine, frozen_time):
    """Traffic over 40 resources spread across the instant window's
    buckets, some of it old enough to rotate out at the read."""
    st.load_flow_rules([st.FlowRule(resource=f"w{i}", count=3)
                        for i in range(0, 40, 4)])
    rng = np.random.default_rng(7)
    for t in (0, 300, 700, 1100, 1300):
        res = [f"w{i}" for i in rng.integers(0, 40, 96)]
        engine.check_batch(_lanes(engine, res), now_ms=BASE_MS + t)
        engine.complete_batch(_lanes(engine, res[:50], exit_=True),
                              now_ms=BASE_MS + t + 5)
    frozen_time.freeze_time(BASE_MS + 1600)


@pytest.mark.parametrize("k", [5, 40, 300, 1100])
def test_row_window_equals_row_stats_on_those_rows(engine, frozen_time, k):
    """Widths 8, 64 and 512, and a read chunked past the last width:
    every value of the sampled rows, duplicates included, in order."""
    _busy_engine(engine, frozen_time)
    rows = np.random.default_rng(k).integers(0, engine.capacity, k)
    rows[: k // 2] = rows[0]  # duplicates
    totals, threads = engine.row_window(rows)
    all_totals, all_threads = engine.row_stats()
    assert totals.shape == (k, C.NUM_EVENTS) and threads.shape == (k,)
    assert all_totals[:, C.MetricEvent.PASS].sum() > 0  # not all empty
    np.testing.assert_array_equal(totals, all_totals[rows])
    np.testing.assert_array_equal(threads, all_threads[rows])
    assert totals.dtype == all_totals.dtype
    assert threads.dtype == all_threads.dtype


def test_row_window_rejects_rows_outside_the_tensor(engine, frozen_time):
    _busy_engine(engine, frozen_time)
    for bad in ([-1], [engine.capacity], [3, engine.capacity + 9]):
        with pytest.raises(IndexError):
            engine.row_window(bad)


def test_window_snapshot_skips_rows_outside_the_tensor(engine, frozen_time):
    """Out-of-range rows are skipped, the rest read as row_stats gives
    them, and duplicates read once."""
    _busy_engine(engine, frozen_time)
    engine.traces.drain()
    before = engine.traces.snapshot(limit=0)
    row = engine.registry.resources()["w8"]
    window = engine.traces._window_snapshot(
        [row, -1, engine.capacity, row, engine.capacity + 5])
    totals, threads = engine.row_stats()
    assert window == {row: {
        "passQps": round(float(totals[row, C.MetricEvent.PASS]), 2),
        "blockQps": round(float(totals[row, C.MetricEvent.BLOCK]), 2),
        "curThreadNum": int(threads[row]),
    }}
    snap = engine.traces.snapshot(limit=0)
    assert snap["windowReads"] == before["windowReads"] + 1
    assert snap["windowRows"] == before["windowRows"] + 1
    assert engine.traces._window_snapshot([-3, engine.capacity]) == {}
    assert engine.traces.snapshot(limit=0)["windowReads"] == \
        snap["windowReads"]


def test_trace_window_never_reads_the_whole_window(engine, monkeypatch):
    """The worker reads the sampled rows through row_window alone: with
    row_stats() raising, traces still carry the window, and it equals
    what row_stats() gives at the same clock reading."""
    engine.traces.sample_every = 1
    st.load_flow_rules([st.FlowRule(resource="nr", count=1)])

    def whole_window():
        raise AssertionError("the trace path read the whole window")

    monkeypatch.setattr(engine, "row_stats", whole_window)
    engine.check_batch(_lanes(engine, ["nr"] * 3), now_ms=BASE_MS)
    engine.traces.drain()
    snap = engine.traces.snapshot()
    assert snap["errors"] == 0 and snap["recorded"] == 2
    assert snap["windowReads"] == 1 and snap["windowRows"] == 1
    monkeypatch.undo()
    totals, threads = engine.row_stats()
    row = engine.registry.resources()["nr"]
    want = {"passQps": round(float(totals[row, C.MetricEvent.PASS]), 2),
            "blockQps": round(float(totals[row, C.MetricEvent.BLOCK]), 2),
            "curThreadNum": int(threads[row])}
    assert want == {"passQps": 1.0, "blockQps": 2.0, "curThreadNum": 1}
    assert [tr["window"] for tr in snap["traces"]] == [want, want]


def test_sampled_trace_compiles_nothing_after_warmup(engine):
    """warmup() compiles the row read at every width; sampled traces and
    a read chunked past the last width then compile no new program."""
    engine.traces.sample_every = 1
    st.load_flow_rules([st.FlowRule(resource="cw", count=0)])
    engine.warmup((8,))
    compiled = engine._row_read_jit._cache_size()
    assert compiled == len(ROW_READ_WIDTHS)
    engine.check_batch(_lanes(engine, ["cw"] * 8), now_ms=BASE_MS)
    engine.traces.drain()
    engine.row_window(np.arange(ROW_READ_WIDTHS[-1] * 2 + 3) % 7)
    assert engine.traces.snapshot(limit=0)["windowReads"] == 1
    assert engine._row_read_jit._cache_size() == compiled


# -- the pick ------------------------------------------------------------------

class _StubEngine:
    """Just what the worker reads of an engine, with an empty window."""

    capacity = 64

    def row_window(self, rows):
        rows = np.asarray(rows)
        return (np.zeros((rows.size, C.NUM_EVENTS), np.float32),
                np.zeros(rows.size, np.int32))

    def _device_metas(self):
        return []


def _loop_picks(reasons_by_batch, n):
    """The worker's former pick: count every blocked entry, one by one,
    and take each n-th, the count carried across batches."""
    seen, picks = 0, []
    for b, reasons in enumerate(reasons_by_batch):
        for i in np.nonzero(reasons > 0)[0].tolist():
            seen += 1
            if seen % n == 0:
                picks.append((b, i))
    return seen, picks


@pytest.mark.parametrize("n", [1, 2, 7, 64])
def test_vectorised_pick_equals_the_counting_loop(n):
    rng = np.random.default_rng(n)
    widths = [1, 5, 37, 64, 200, 2048, 3, 130] * 3
    reasons_by_batch = []
    buf = DecisionTraceBuffer(_StubEngine(), sample_every=n,
                              capacity=1 << 20)
    for b, width in enumerate(widths):
        share = rng.choice([0.0, 0.05, 0.5, 1.0])
        reasons = np.where(rng.random(width) < share,
                           rng.integers(1, 6, width), 0).astype(np.int32)
        reasons_by_batch.append(reasons)
        ebuf = make_entry_batch_np(width)
        ebuf["cluster_row"][:] = rng.integers(-2, 70, width)
        ebuf["count"][:] = b * 4096 + np.arange(width)  # names the entry
        dec = Decisions(reason=reasons, wait_us=np.zeros(width, np.int64),
                        rule_slot=rng.integers(-1, 3, width).astype(np.int32))
        buf._process(EntryBatch(**ebuf), dec, BASE_MS + b)
    seen, picks = _loop_picks(reasons_by_batch, n)
    snap = buf.snapshot()
    assert snap["seenBlocked"] == seen
    assert snap["recorded"] == len(picks) > 0
    got = [tr["count"] for tr in reversed(snap["traces"])]
    assert got == [b * 4096 + i for b, i in picks]
