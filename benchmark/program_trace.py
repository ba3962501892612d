"""The program's own spans and scopes in a profiler trace, beside what
``trace.py`` reduces:

- ``program_spans``: for each program span (``jax.profiler.TraceAnnotation``
  names starting ``sentinel.``, metrics/profiling.py) its count, total and
  self seconds, and the p50 / p99 of its durations in µs. Self time is the
  duration less the program spans nested inside it on the same thread line.
- ``idle_gaps``: the device's idle time by what the host did in it. The
  part of a gap that program spans' self intervals cover, on any thread,
  goes to those spans (shared equally where spans of several names
  overlap); the rest of the gap keeps ``trace.py``'s label, the harness
  span that overlaps the gap most, else ``no_harness_span``. A gap is
  split, not labelled whole: one that runs from the caller's readback into
  the next call would otherwise go whole to the part of the call it meets.
- ``scope_s``: device seconds per scope of the fused step
  (``entry_step/flow``, ``exit_step/commit``; ``jax.named_scope`` in
  ops/step.py), each op's self time (less the ops nested inside it on the
  "XLA Ops" line: a while or a conditional holds its body's ops) given to
  its innermost section; ``device_ops``: the top ops as ``<scope>:<op>``.
- ``step_programs``: the executions of each program whose ops carry
  ``entry_step`` or ``exit_step`` (both are ``jit__unknown(<fingerprint>)``
  on the "XLA Modules" line), keyed by that scope.
- ``metrics``: the per-layer numbers these give (``METRICS``), each None
  where its span or scope is absent.

An op's scope is the ``op_name`` metadata XLA gives it, which the chip's
trace keeps as the ``tf_op`` stat of the op's event metadata, keyed by the
``program_id`` stat and the op's text. ``ProfileData`` does not expose
metadata stats, so ``op_names`` reads them from the file's XSpace protobuf
itself. Run a cell with its trace kept and reduced here (on the chip):

    python3 benchmark/program_trace.py --workload headline_batch --seed 7 --seconds 20

``--cost-pairs N`` measures instead what tracing costs end to end
(``tracing_cost``).
"""

from __future__ import annotations

import argparse
import json
import re
import shutil
import statistics
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Optional

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import common, harness, trace as T  # noqa: E402

PROGRAM_PREFIX = "sentinel."
STEPS = ("entry_step", "exit_step")
SECTIONS = ("roll", "authority", "system", "param", "custom", "flow",
            "degrade", "shadow", "commit", "telemetry")
UNSCOPED = "unscoped"
_STEP_PATH = re.compile(r"(?:^|/)(%s)(?:/(\w+))?" % "|".join(STEPS))
_PROGRAM_ID = re.compile(r"\((\d+)\)$")  # "jit__unknown(3596068238647074520)"


def scope_of(op_name: Optional[str]) -> str:
    """``entry_step/flow`` for ``jit(_unknown)/entry_step/flow/cond/...``;
    the step alone outside its sections; ``unscoped`` elsewhere."""
    m = _STEP_PATH.search(op_name or "")
    if m is None:
        return UNSCOPED
    return f"{m[1]}/{m[2]}" if m[2] in SECTIONS else m[1]


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, varint or (start, end) of a length-delimited value)
    for each field of the protobuf message in ``buf[lo:hi]``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, v


def op_names(path) -> dict:
    """``{(program_id, op text): op_name}`` for the ops of the device
    planes: each event metadata's ``program_id`` and ``tf_op`` stats
    (xplane.proto: XSpace.planes 1; XPlane.name 2, event_metadata 4,
    stat_metadata 5; map entry key 1, value 2; XEventMetadata.name 2,
    stats 5; XStatMetadata.name 2; XStat.metadata_id 1, uint64_value 3,
    int64_value 4, str_value 5, ref_value 7)."""
    buf = Path(path).read_bytes()
    out = {}
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        fields = list(_fields(buf, *plane))
        name = next((bytes(buf[slice(*v)]).decode() for k, v in fields if k == 2), "")
        if not T.DEVICE_PLANE.match(name):
            continue
        stat_names, metas = {}, []
        for k, v in fields:
            if k in (4, 5):
                entry = dict(_fields(buf, *v))
                if k == 5:
                    sub = dict(_fields(buf, *entry[2]))
                    stat_names[entry[1]] = bytes(buf[slice(*sub[2])]).decode()
                else:
                    metas.append(entry[2])
        for span in metas:
            text, stats = "", {}
            for k, v in _fields(buf, *span):
                if k == 2:
                    text = bytes(buf[slice(*v)]).decode()
                elif k == 5:
                    st = dict(_fields(buf, *v))
                    stats[stat_names.get(st.get(1))] = st
            pid = stats.get("program_id", {})
            pid = pid.get(3, pid.get(4))
            tf_op = stats.get("tf_op", {})
            if 5 in tf_op:
                op = bytes(buf[slice(*tf_op[5])]).decode()
            else:
                op = stat_names.get(tf_op.get(7))
            if op:
                out[(pid, text)] = op
    return out


def self_intervals(events):
    """``events``: (key, start, end) on one line, nested or apart. ->
    (key, [(start, end), ...]) per event: its interval less the events
    nested inside it."""
    out = []
    stack = []  # [key, end, pieces, cursor]: cursor = covered up to here

    def close():
        key, end, pieces, cursor = stack.pop()
        pieces.append((cursor, end))
        out.append((key, pieces))
        if stack:  # a child that outlasts its parent covers that tail too
            stack[-1][3] = max(stack[-1][3], end, cursor)

    for key, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            close()
        if stack:
            stack[-1][2].append((stack[-1][3], s))
        stack.append([key, e, [], s])
    while stack:
        close()
    return [(k, [(a, b) for a, b in p if b > a]) for k, p in out]


def _ns(e):
    """An event's start and end in whole ns, rounded as trace.py rounds."""
    s = int(e.start_ns)
    return s, s + int(e.duration_ns)


def _read(path):
    """Host thread lines (program and harness spans), device lines and the
    window (first and last event, host or device, as trace.py takes it) of
    one trace, on its one timeline (ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    threads, devices, stamps = [], [], []
    for plane in pd.planes:
        if T.DEVICE_PLANE.match(plane.name):
            dev = {T.OPS_LINE: [], T.MODULES_LINE: []}
            for line in plane.lines:
                if line.name in dev:
                    dev[line.name] = [(e.name, *_ns(e)) for e in line.events]
            if dev[T.OPS_LINE] or dev[T.MODULES_LINE]:
                devices.append(dev)
                stamps += [t for evs in dev.values() for ev in evs
                           for t in ev[1:3]]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = []
                for e in line.events:
                    s, end = _ns(e)
                    stamps += (s, end)
                    if e.name.startswith((PROGRAM_PREFIX, T.SPAN_PREFIX)):
                        evs.append((e.name, s, end))
                if evs:
                    threads.append(evs)
    window = (min(stamps), max(stamps)) if stamps else None
    return threads, devices, window


def _spans(threads):
    """Per program span name: its intervals and its self pieces (ns)."""
    whole, pieces = defaultdict(list), defaultdict(list)
    for evs in threads:
        prog = [ev for ev in evs if ev[0].startswith(PROGRAM_PREFIX)]
        for name, s, e in prog:
            whole[name].append((s, e))
        for name, ps in self_intervals(prog):
            pieces[name].extend(ps)
    return whole, pieces


def _union_s(pieces_by_name, names) -> float:
    ps = [p for n in names for p in pieces_by_name.get(n, [])]
    ms, me = T.merge(np.asarray([a for a, _ in ps]), np.asarray([b for _, b in ps]))
    return float((me - ms).sum()) / 1e9


def _gaps(dev, lo, hi):
    """One device's idle gaps in the window [lo, hi), sorted and disjoint."""
    evs = dev[T.OPS_LINE] or dev[T.MODULES_LINE]
    ms, me = T.merge(np.asarray([ev[1] for ev in evs]),
                     np.asarray([ev[2] for ev in evs]))
    gs, ge = np.concatenate([[lo], me]), np.concatenate([ms, [hi]])
    keep = ge > gs
    return gs[keep], ge[keep]


def _label(gs, ge, pieces_by_name):
    """Index of the name whose pieces cover most of each gap, -1 where
    none covers any of it."""
    names = sorted(pieces_by_name)
    if not names or not len(gs):
        return names, np.full(len(gs), -1)
    cover = np.stack([_covered_by(pieces_by_name[n], gs, ge) for n in names])
    return names, np.where(cover.max(axis=0) > 0, np.argmax(cover, axis=0), -1)


def _idle_under(pieces_by_name, gs, ge) -> dict:
    """Idle ns (gaps ``gs``..``ge``, disjoint) under each name's pieces;
    where the spans of several names overlap, shared equally."""
    names = sorted(pieces_by_name)
    merged = [T.merge(np.asarray([a for a, _ in pieces_by_name[n]]),
                      np.asarray([b for _, b in pieces_by_name[n]])) for n in names]
    cuts = np.unique(np.concatenate([np.concatenate(m) for m in merged]))
    a, b = cuts[:-1], cuts[1:]  # spans where the set of active names is fixed
    active = np.stack([T.covered(ms, me, a, b) > 0 for ms, me in merged])
    idle = T.covered(gs, ge, a, b) / np.maximum(active.sum(axis=0), 1)
    return {n: float((idle * act).sum()) for n, act in zip(names, active)}


def _covered_by(pieces, gs, ge):
    ms, me = T.merge(np.asarray([a for a, _ in pieces]),
                     np.asarray([b for _, b in pieces]))
    return T.covered(ms, me, gs, ge)


def reduce_program_trace(path, top: int = 10) -> dict:
    threads, devices, window = _read(path)
    whole, pieces = _spans(threads)
    out = {"program_spans": {}, "idle_gaps": [], "scope_s": {}, "device_ops": [],
           "step_programs": {}, "window_s": 0.0, "_whole": whole}
    for name, iv in sorted(whole.items()):
        d = np.asarray([e - s for s, e in iv], np.float64)
        out["program_spans"][name] = {
            "count": len(d), "total_s": d.sum() / 1e9,
            "self_s": sum(b - a for a, b in pieces[name]) / 1e9,
            "p50_us": float(np.percentile(d, 50)) / 1e3,
            "p99_us": float(np.percentile(d, 99)) / 1e3}
    if window is None:
        return out
    lo, hi = window
    out["window_s"] = (hi - lo) / 1e9
    if not devices:
        return out

    # Idle gaps: program spans first, then the harness's own spans.
    harness = defaultdict(list)
    for evs in threads:
        for name, s, e in evs:
            if name.startswith(T.SPAN_PREFIX):
                harness[name].append((s, e))
    by = defaultdict(float)
    for dev in devices:
        gs, ge = _gaps(dev, lo, hi)
        rest = (ge - gs).astype(np.float64)
        if pieces and len(gs):
            for n, ns in _idle_under(pieces, gs, ge).items():
                by[n] += ns
            rest -= _covered_by([p for ps in pieces.values() for p in ps], gs, ge)
        hnames, hnamed = _label(gs, ge, harness)
        for hj, r in zip(hnamed, rest):
            by[hnames[hj] if hj >= 0 else T.NO_SPAN] += r
    by = {k: v for k, v in by.items() if v > 0}
    n_dev = len(devices)
    out["idle_gaps"] = [[k, v / 1e9 / n_dev] for k, v in
                        sorted(by.items(), key=lambda kv: -kv[1])[:top]]

    # Device time per scope, and each program by the step its ops carry.
    meta = op_names(path)
    scope_ns, op_ns = defaultdict(int), defaultdict(int)
    steps = defaultdict(lambda: [0, 0])
    for dev in devices:
        ops = sorted(dev[T.OPS_LINE], key=lambda op: op[1])
        mods = sorted(dev[T.MODULES_LINE], key=lambda m: m[1])
        starts = np.asarray([s for _, s, _ in ops], np.int64)
        # The program an op ran in: the module execution it starts within.
        pids = [(m := _PROGRAM_ID.search(name)) and int(m[1]) for name, _, _ in mods]
        at = np.searchsorted(np.asarray([s for _, s, _ in mods], np.int64),
                             starts, side="right") - 1
        scopes = [scope_of(meta.get((pids[j] if j >= 0 else None, text)))
                  for (text, _, _), j in zip(ops, at)]
        # A module is its step's where its ops carry that step alone; its
        # ops without an op_name (copies XLA adds) count to the step.
        top_of = np.asarray([sc.split("/")[0] for sc in scopes] or [""])
        step_of = []
        for name, s, e in mods:
            inside = top_of[np.searchsorted(starts, s):np.searchsorted(starts, e)]
            carried = [st for st in STEPS if (inside == st).any()]
            step_of.append(carried[0] if len(carried) == 1 else None)
            if len(carried) == 1:
                p = steps[carried[0]]
                p[0] += 1
                p[1] += e - s
        scopes = [step_of[j] if sc == UNSCOPED and j >= 0 and step_of[j] else sc
                  for sc, j in zip(scopes, at)]
        for k, ps in self_intervals([(i, s, e) for i, (_, s, e) in enumerate(ops)]):
            ns = sum(b - a for a, b in ps)
            scope_ns[scopes[k]] += ns
            op_ns[f"{scopes[k]}:{ops[k][0].split(' = ')[0]}"] += ns
    out["scope_s"] = {k: v / 1e9 for k, v in sorted(scope_ns.items())}
    out["device_ops"] = [[k, v / 1e9] for k, v in
                         sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]]
    out["step_programs"] = {k: {"count": c, "device_s": ns / 1e9}
                            for k, (c, ns) in sorted(steps.items())}
    return out


def _mean_us(red, name):
    s = red["program_spans"].get(name)
    return s["total_s"] / s["count"] * 1e6 if s else None


def _step_ms(red, step):
    p = red["step_programs"].get(step)
    return p["device_s"] / p["count"] * 1e3 if p else None


def _busy(red, names):
    whole = red["_whole"]
    if not any(n in whole for n in names):
        return None
    return _union_s(whole, names) / red["window_s"]


# The per-layer numbers the program's spans and scopes give, by name.
METRICS = {
    "admit.entry_host_us": lambda r: _mean_us(r, "sentinel.admit.check_batch"),
    "admit.entry_dispatch_us": lambda r: _mean_us(r, "sentinel.admit.entry_dispatch"),
    "admit.exit_host_us": lambda r: _mean_us(r, "sentinel.admit.complete_batch"),
    "step.entry_device_ms": lambda r: _step_ms(r, "entry_step"),
    "step.exit_device_ms": lambda r: _step_ms(r, "exit_step"),
    "wire.reactor_busy_share": lambda r: _busy(r, (
        "sentinel.reactor.read", "sentinel.reactor.submit",
        "sentinel.reactor.flush")),
    "wire.batcher_busy_share": lambda r: _busy(r, (
        "sentinel.batcher.dispatch", "sentinel.batcher.harvest")),
    "wire.batcher_linger_us": lambda r: _mean_us(r, "sentinel.batcher.linger"),
}


def summarize(red: dict) -> dict:
    """The reduction as JSON: the metrics that read something, then the
    breakdown."""
    out = {"metrics": {k: v for k, f in METRICS.items()
                       if (v := f(red)) is not None}}
    out.update({k: v for k, v in red.items() if not k.startswith("_")})
    return out


def tracing_cost(name: str, seed: int, seconds: float, pairs: int) -> dict:
    """What tracing costs end to end: in one process, after one build and
    warm-up, ``pairs`` windows with the profiler off and as many traced
    from start to end (the harness's spans on, as in a traced run), in
    the order off, on, on, off, off, on, ...; each side's end-to-end
    numbers and their medians."""
    cell, config = harness.find_cell(common.benchmark_spec(), name)
    cfg = common.load_json(common.ROOT / config["file"])
    traffic = common.load_json(common.BENCH / "traffic" / f"{cell['traffic']}.json")
    harness.enable_cache()
    builder = common.import_piece("builders", cfg["builder"])
    driver_mod = common.import_piece("drivers", traffic["driver"])
    dep = builder.build(cfg)
    runs = {"untraced": [], "traced": []}
    try:
        drv = driver_mod.Driver(dep, traffic, seed)
        drv.warm()
        for k in range(2 * pairs):
            traced = k % 4 in (1, 2)
            tracer = None
            if traced:
                tracer = harness._Tracer(seconds)
                tracer.lead, tracer.length = 0.0, seconds
            window = drv.run(seconds, tracer.start if tracer else (lambda t0: None),
                             annotate=traced)
            if tracer:
                tracer.thread.join()
            runs["traced" if traced else "untraced"].append(window["end_to_end"])
    finally:
        dep.close()
        shutil.rmtree(common.TRACE_DIR, ignore_errors=True)
    return {side: {"runs": rows, "median": {
        m: statistics.median(r[m] for r in rows) for m in rows[0]}}
        for side, rows in runs.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="copy the raw trace here (.xplane.pb)")
    ap.add_argument("--cost-pairs", type=int, default=0,
                    help="measure what tracing costs instead (tracing_cost)")
    args = ap.parse_args(argv)

    if args.cost_pairs:
        out = tracing_cost(args.workload, args.seed, args.seconds, args.cost_pairs)
        print(json.dumps(out), flush=True)
        return 0
    keep = args.keep or str(common.BENCH / ".cache" / "program_trace.xplane.pb")
    out = harness.run_cell(args.workload, args.seed, args.seconds, True,
                           time.perf_counter(), keep_trace=keep)
    red = summarize(reduce_program_trace(keep))
    print(json.dumps({"correct": out["correct"], "metrics": out["metrics"],
                      "device": out["device"], "program": red}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
