"""Program spans and step scopes (metrics/profiling.py, ops/step.py).

Under ``jax.profiler.start_trace`` the engine's batch API and one
loopback token request through ``ClusterTokenServer`` leave their
``sentinel.*`` spans on the host's thread lines, nested where the calls
nest; the fused entry and exit steps carry one ``jax.named_scope`` per
section in their HLO op metadata; and the batch API's dispatches reach
the StepTimer that the ``profile`` command reads."""

import functools
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sentinel_tpu as st
from sentinel_tpu.cluster.client import ClusterTokenClient
from sentinel_tpu.cluster.rules import ClusterFlowRuleManager
from sentinel_tpu.cluster.server import ClusterTokenServer
from sentinel_tpu.cluster.token_service import DefaultTokenService
from sentinel_tpu.core.batch import (EntryBatch, ExitBatch,
                                     make_entry_batch_np, make_exit_batch_np)
from sentinel_tpu.metrics import profiling as prof
from sentinel_tpu.ops import step as S

WIDTH = 8
FLOW_ID = 8300

ADMIT_SPANS = (prof.ADMIT_CHECK_BATCH, prof.ADMIT_ENTRY_DISPATCH,
               prof.ADMIT_REFRESH_SIGNALS, prof.ADMIT_DECISION_TRACES,
               prof.ADMIT_POPULATION, prof.ADMIT_COMPLETE_BATCH,
               prof.ADMIT_EXIT_DISPATCH)
TOKEN_SPANS = (prof.REACTOR_READ, prof.REACTOR_SUBMIT, prof.REACTOR_FLUSH,
               prof.REACTOR_RESOLVE, prof.BATCHER_LINGER,
               prof.BATCHER_DISPATCH, prof.BATCHER_HARVEST,
               prof.CLIENT_SEND, prof.CLIENT_RECEIVE)


def _traced(logdir, fn):
    """Run ``fn`` under the profiler; -> one list per host thread line of
    its ``sentinel.*`` events as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(logdir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(logdir.glob("**/*.xplane.pb"))[-1]
    lines = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events if e.name.startswith("sentinel.")]
            if evs:
                lines.append(evs)
    return lines


def _batches(engine):
    st.load_flow_rules([st.FlowRule(resource="spanRes", count=1000)])
    row = engine.registry.cluster_row("spanRes")
    e = make_entry_batch_np(WIDTH)
    e["cluster_row"][:] = row
    e["count"][:] = 1
    x = make_exit_batch_np(WIDTH)
    x["cluster_row"][:] = row
    x["count"][:] = 1
    x["success"][:] = True
    return EntryBatch(**e), ExitBatch(**x)


def test_batch_api_spans_nest_on_the_callers_thread(engine, frozen_time,
                                                    tmp_path):
    entry, exit_ = _batches(engine)
    np.asarray(engine.check_batch(entry).reason)  # compile outside the trace
    engine.complete_batch(exit_)
    frozen_time.advance_time(1500)  # the OS-signal refresh is due again

    def round_trip():
        np.asarray(engine.check_batch(entry).reason)
        engine.complete_batch(exit_)

    lines = _traced(tmp_path, round_trip)
    names = {n for evs in lines for n, _, _ in evs}
    assert set(ADMIT_SPANS) <= names
    # One thread ran the round: every admission span sits on its line,
    # once each, and the dispatch spans nest inside their call's span.
    (line,) = [evs for evs in lines if any(
        n == prof.ADMIT_CHECK_BATCH for n, _, _ in evs)]
    by = {n: (s, e) for n, s, e in line}
    assert sorted(n for n, _, _ in line) == sorted(ADMIT_SPANS)
    for inner in (prof.ADMIT_ENTRY_DISPATCH, prof.ADMIT_REFRESH_SIGNALS,
                  prof.ADMIT_DECISION_TRACES, prof.ADMIT_POPULATION):
        lo, hi = by[prof.ADMIT_CHECK_BATCH]
        assert lo <= by[inner][0] and by[inner][1] <= hi, inner
    lo, hi = by[prof.ADMIT_COMPLETE_BATCH]
    assert lo <= by[prof.ADMIT_EXIT_DISPATCH][0]
    assert by[prof.ADMIT_EXIT_DISPATCH][1] <= hi


def test_signal_refresh_span_only_when_it_copies(engine, frozen_time,
                                                 tmp_path):
    entry, _ = _batches(engine)
    np.asarray(engine.check_batch(entry).reason)
    lines = _traced(tmp_path, lambda: engine.check_batch(entry))
    names = [n for evs in lines for n, _, _ in evs]
    assert prof.ADMIT_ENTRY_DISPATCH in names
    assert prof.ADMIT_REFRESH_SIGNALS not in names  # refreshed < 1 s ago


def test_loopback_token_request_spans(frozen_time, tmp_path):
    rules = ClusterFlowRuleManager()
    rules.load_rules("default", [st.FlowRule(
        resource="span-res", count=1e9, cluster_mode=True,
        cluster_config={"flowId": FLOW_ID, "thresholdType": 1})])
    svc = DefaultTokenService(rules)
    svc.request_tokens([(FLOW_ID, 1, False)])  # absorb the width-1 compile
    server = ClusterTokenServer(svc, host="127.0.0.1", port=0).start()
    client = ClusterTokenClient("127.0.0.1", server.bound_port,
                                request_timeout_s=10.0).start()
    try:
        deadline = time.monotonic() + 10
        while not client.is_connected():
            assert time.monotonic() < deadline, "client never connected"
            time.sleep(0.01)
        assert client.request_token(FLOW_ID).status == 0

        def one_request():
            assert client.request_token(FLOW_ID).status == 0
            time.sleep(0.1)  # the reactor's flush after the reply

        lines = _traced(tmp_path, one_request)
    finally:
        client.stop()
        server.stop()
    names = {n for evs in lines for n, _, _ in evs}
    assert set(TOKEN_SPANS) <= names
    # Batcher spans share the batcher's thread; the client's receive runs
    # on its reader thread, apart from the caller's send.
    (batcher,) = [evs for evs in lines
                  if any(n == prof.BATCHER_LINGER for n, _, _ in evs)]
    assert {n for n, _, _ in batcher} == {prof.BATCHER_LINGER,
                                          prof.BATCHER_DISPATCH,
                                          prof.BATCHER_HARVEST}
    assert not any({prof.CLIENT_SEND, prof.CLIENT_RECEIVE}
                   <= {n for n, _, _ in evs} for evs in lines)


ENTRY_SCOPES = ("roll", "authority", "system", "param", "custom", "flow",
                "degrade", "shadow", "commit", "telemetry")
EXIT_SCOPES = ("roll", "commit", "telemetry", "degrade", "param", "shadow")


def _scopes(hlo_text: str, step: str) -> set:
    """Sections named in the compiled ops' metadata: the path component
    under the step's own scope that more components follow."""
    return set(re.findall(rf'op_name="[^"]*/{step}/(\w+)/', hlo_text))


def test_step_sections_carry_named_scopes(engine):
    entry, exit_ = _batches(engine)
    st.load_param_flow_rules([st.ParamFlowRule("spanRes", param_idx=0,
                                               count=5)])
    engine._ensure_compiled()
    state, rules = engine._state, engine._rules
    # A staged candidate and one SPI checker put every section in play.
    state = state._replace(shadow=S.make_shadow_state(
        state.cur_threads.shape[0], rules, state.degrade))
    never = lambda st_, rules_, batch, now, cand: jnp.zeros_like(cand)
    now = jnp.int64(1_700_000_000_000)
    entry_hlo = jax.jit(functools.partial(
        S.entry_step, extra_checkers=(never,))).lower(
        state, rules, entry, now, shadow_rules=rules, canary_bps=100,
        canary_salt=0).compile().as_text()
    exit_hlo = jax.jit(S.exit_step).lower(
        state, rules, exit_, now, shadow_rules=rules).compile().as_text()
    assert set(ENTRY_SCOPES) <= _scopes(entry_hlo, "entry_step")
    assert set(EXIT_SCOPES) <= _scopes(exit_hlo, "exit_step")
    assert "/exit_step/" not in entry_hlo and "/entry_step/" not in exit_hlo


def test_profile_command_counts_batch_api_dispatches(engine):
    from sentinel_tpu.transport.command_center import CommandRequest
    from sentinel_tpu.transport.handlers import cmd_profile

    entry, exit_ = _batches(engine)
    engine.step_timer.reset()
    for _ in range(3):
        np.asarray(engine.check_batch(entry).reason)
        engine.complete_batch(exit_)
    resp = cmd_profile(CommandRequest(parameters={}, body="", engine=engine))
    assert resp.success
    out = json.loads(resp.result)
    for kind in ("entry", "exit"):
        assert out[kind]["dispatches"] == 3
        assert out[kind]["entries"] == 3 * WIDTH
        # The caller reads the verdicts back itself: no sampled syncs.
        assert "stepSamples" not in out[kind]
