"""The program-span reduction (benchmark/program_trace.py) on hand-made
traces, built as text protos the way record_trace.py builds its small
one, and on the committed v5e trace, which holds no program span."""

import json
from pathlib import Path

import pytest

from benchmark import program_trace as PT
from benchmark import trace as T

DATA = Path(__file__).parent / "data" / "v5e_small.xplane.pb"
TF_OP, PROGRAM_ID = 9, 10  # stat metadata ids, as a chip's trace names them


def _events(evs, names, stats):
    """Events (name, start, end[, op_name, program_id]); as on the chip, an
    op's op_name and program id are stats of its event metadata."""
    out = []
    for name, start, end, *op in evs:
        mid = names.setdefault((name, *op), len(names) + 1)
        if op:
            stats[mid] = (f"stats {{ metadata_id: {TF_OP} str_value: {json.dumps(op[0])} }} "
                          f"stats {{ metadata_id: {PROGRAM_ID} uint64_value: {op[1]} }}")
        out.append(f"events {{ metadata_id: {mid} offset_ps: {start * 1000} "
                   f"duration_ps: {(end - start) * 1000} }}")
    return "\n".join(out)


def _plane(pid, name, lines):
    names, stats = {}, {}
    body = "\n".join(f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0\n'
                     f"{_events(evs, names, stats)}\n}}"
                     for i, (ln, evs) in enumerate(lines))
    meta = "\n".join(f"event_metadata {{ key: {m} value {{ id: {m} "
                     f"name: {json.dumps(n[0])} {stats.get(m, '')} }} }}"
                     for n, m in names.items())
    stat = "\n".join(f'stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                     for k, n in ((TF_OP, "tf_op"), (PROGRAM_ID, "program_id")))
    return f'planes {{ id: {pid} name: "{name}"\n{body}\n{meta}\n{stat}\n}}'


def _trace(tmp_path, ops=(), modules=(), threads=()):
    """Device lines and host thread lines, in ns."""
    from jax.profiler import ProfileData

    text = _plane(1, "/device:TPU:0", [(T.MODULES_LINE, modules),
                                        (T.OPS_LINE, ops)])
    text += "\n" + _plane(2, "/host:CPU", [("python", evs) for evs in threads])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return path


def test_self_time_leaves_out_nested_program_spans(tmp_path):
    path = _trace(tmp_path, threads=[[
        ("sentinel.admit.check_batch", 0, 100),
        ("sentinel.admit.entry_dispatch", 10, 50),
        ("sentinel.admit.population", 60, 70),
        ("bench.check_batch", 0, 120),  # the harness's: not a program span
        ("sentinel.admit.check_batch", 200, 230)]])
    red = PT.reduce_program_trace(path)
    spans = red["program_spans"]
    assert set(spans) == {"sentinel.admit.check_batch",
                          "sentinel.admit.entry_dispatch",
                          "sentinel.admit.population"}
    cb = spans["sentinel.admit.check_batch"]
    assert cb["count"] == 2
    assert cb["total_s"] == pytest.approx(130e-9)
    assert cb["self_s"] == pytest.approx((130 - 40 - 10) * 1e-9)
    assert spans["sentinel.admit.entry_dispatch"]["self_s"] == pytest.approx(40e-9)
    metrics = PT.summarize(red)["metrics"]
    assert metrics["admit.entry_host_us"] == pytest.approx(65e-3)
    assert metrics["admit.entry_dispatch_us"] == pytest.approx(40e-3)
    assert "admit.exit_host_us" not in metrics  # absent, not 0


def test_idle_gaps_prefer_program_spans_over_harness_spans(tmp_path):
    ops = [("%fusion.1", 0, 10), ("%fusion.2", 100, 110), ("%fusion.3", 200, 210)]
    path = _trace(tmp_path, ops=ops, threads=[
        # Thread 1, the caller: the harness span covers both gaps whole,
        # a program span nested in it part of the first.
        [("bench.request_token", 5, 205),
         ("sentinel.client.send", 80, 90)],
        # Thread 2: program spans cover more of the first gap.
        [("sentinel.batcher.harvest", 20, 30),
         ("sentinel.batcher.dispatch", 40, 50),
         ("sentinel.batcher.harvest", 60, 65),
         ("sentinel.batcher.linger", 85, 95)]])
    red = PT.reduce_program_trace(path)
    # Gap [10, 100): what program spans cover goes to them, whatever thread
    # they ran on, though the harness span covers it all; [85, 90), where
    # two threads' spans overlap, is split between them. The rest of the
    # gap, and all of [110, 200), keep the harness span's label.
    assert dict(red["idle_gaps"]) == pytest.approx({
        "sentinel.batcher.harvest": 15e-9, "sentinel.batcher.dispatch": 10e-9,
        "sentinel.client.send": 7.5e-9, "sentinel.batcher.linger": 7.5e-9,
        "bench.request_token": (90 - 40 + 90) * 1e-9})


def test_op_self_time_under_nested_ops_goes_to_its_scope(tmp_path):
    flow = "jit(_unknown)/entry_step/flow/cond/branch_1_fun/add"
    ops = [("%while.8", 0, 100, "jit(_unknown)/entry_step/flow/while", 11),
           ("%fusion.1", 10, 30, flow, 11),
           ("%fusion.2", 40, 90, "jit(_unknown)/entry_step/commit/dot_general", 11),
           ("%copy.3", 100, 104, "jit(_unknown)/entry_step/not", 11),
           ("%copy.4", 104, 106),  # no op_name: the step it ran in
           # The exit program's own %fusion.1 is another op of another scope.
           ("%fusion.1", 200, 210, "jit(_unknown)/exit_step/degrade/mul", 22),
           ("%fusion.5", 300, 305, "jit(acquire_step)/add", 33)]
    modules = [("jit__unknown(11)", 0, 110), ("jit__unknown(22)", 195, 215),
               ("jit_acquire_step(33)", 300, 306)]
    red = PT.reduce_program_trace(_trace(tmp_path, ops=ops, modules=modules))
    assert red["scope_s"] == pytest.approx({
        "entry_step/flow": 50e-9, "entry_step/commit": 50e-9,
        "entry_step": 6e-9, "exit_step/degrade": 10e-9, PT.UNSCOPED: 5e-9})
    assert dict(red["device_ops"])["exit_step/degrade:%fusion.1"] == pytest.approx(10e-9)
    ops_s = dict(red["device_ops"])
    assert ops_s["entry_step/flow:%while.8"] == pytest.approx(30e-9)
    assert ops_s["entry_step/flow:%fusion.1"] == pytest.approx(20e-9)
    assert red["step_programs"] == {
        "entry_step": {"count": 1, "device_s": pytest.approx(110e-9)},
        "exit_step": {"count": 1, "device_s": pytest.approx(20e-9)}}
    metrics = PT.summarize(red)["metrics"]
    assert metrics["step.entry_device_ms"] == pytest.approx(110e-6)
    assert metrics["step.exit_device_ms"] == pytest.approx(20e-6)


def test_busy_shares_take_the_union_of_whole_spans(tmp_path):
    ops = [("%fusion.1", 0, 10), ("%fusion.2", 990, 1000)]
    path = _trace(tmp_path, ops=ops, threads=[
        [("sentinel.reactor.read", 100, 300),
         ("sentinel.reactor.flush", 250, 300)],  # nested: counted once
        [("sentinel.reactor.submit", 200, 400),  # another thread, overlapping
         ("sentinel.batcher.linger", 500, 600),
         ("sentinel.batcher.linger", 700, 740)]])
    metrics = PT.summarize(PT.reduce_program_trace(path))["metrics"]
    assert metrics["wire.reactor_busy_share"] == pytest.approx(300 / 1000)
    assert metrics["wire.batcher_linger_us"] == pytest.approx(70e-3)
    assert "wire.batcher_busy_share" not in metrics


def test_scope_of_names_the_innermost_section():
    assert PT.scope_of("jit(_unknown)/entry_step/flow/cond/x") == "entry_step/flow"
    assert PT.scope_of("jit(_unknown)/exit_step/roll/add") == "exit_step/roll"
    assert PT.scope_of("jit(_unknown)/exit_step/sin") == "exit_step"
    assert PT.scope_of("jit(acquire_step)/add") == PT.UNSCOPED
    assert PT.scope_of(None) == PT.UNSCOPED


@pytest.mark.skipif(not DATA.exists(), reason="no recorded v5e trace")
def test_recorded_v5e_trace_reduces_as_before():
    """No program span and no op metadata in the committed trace: the
    gaps keep trace.py's labels and seconds, in the same window."""
    base = T.reduce_trace(DATA)
    red = PT.reduce_program_trace(DATA)
    assert red["program_spans"] == {}
    assert red["window_s"] == pytest.approx(base["window_s"])
    assert dict(red["idle_gaps"]) == pytest.approx(dict(base["idle_gaps"]))
    assert red["step_programs"] == {}
    assert set(red["scope_s"]) == {PT.UNSCOPED}
    assert sum(red["scope_s"].values()) <= base["busy_s"] + 1e-9
    assert PT.summarize(red)["metrics"] == {}
