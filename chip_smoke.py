"""Bring-up smoke: drive sentinel-tpu's main paths once on a TPU.

    python chip_smoke.py             # one chip: boot, then phases a-d
    python chip_smoke.py --chips 4   # the pod path on four chips, nothing else

The deployment is BASELINE.json's headline: ``SentinelEngine(capacity=32768)``
with 10k resources and bench.py's rule mix (every 10th resource flow-ruled,
every 20th with a breaker, every 40th param-ruled) at finite thresholds,
loaded through the public loaders, then ``engine.warmup()`` (the documented
boot order), then traffic on an injected clock.

Every phase is checked against a plain numpy/python reference computed from
the traffic alone, must show both passes and blocks, and prints one JSON
line. Any failure exits non-zero: nothing is caught and carried past. The
last line, ``{"ok": true, "device": {...}}``, is printed only when every phase
held. With no TPU the script exits 2 before any phase.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from collections import Counter

import numpy as np

N_RES = 10_000
CAPACITY = 32_768
WIDTH = 2048                 # the widest BATCH_WIDTHS rung
WINDOWS = 3                  # one-second windows per phase
BATCHES = 4                  # entry (and exit) batches per window
WINDOW_GAP_MS = 2000         # > 1 s: no window or param bucket carries over
BATCH_GAP_MS = 50            # a window's traffic stays in one 500 ms bucket
T0 = 1_700_000_000_000

FLOW_COUNT = 3               # headline flow rules (QPS, DEFAULT)
PARAM_COUNT = 2              # headline param rules, per value
PARAM_VALUES = 2             # distinct values per param-ruled resource
MIN_REQUEST = 2              # headline breakers
EXC_COUNT = 1                # exception-count breakers trip above this
SLOW_RT_MS = 100             # slow-ratio breakers: an exit above is slow

# Phase b (synchronous entry): two leased QPS resources, one breaker and
# one param rule on the device path. (resource, arg, arrivals per window)
SYNC_PLAN = (("q0", None, 7), ("q1", None, 7), ("brk", None, 6),
             ("par", "u1", 4), ("par", "u2", 2))
SYNC_Q_COUNT, SYNC_BRK_COUNT, SYNC_PAR_COUNT = 5, 4, 3
SYNC_BRK_MIN_REQUEST, SYNC_BRK_EXC = 3, 2

# Phase c (pipelined): device-path resources, THREADS submitters.
PIPE_RES, PIPE_COUNT, THREADS, PER_THREAD = 4, 4, 4, 6

# Phase d (token server, BASELINE config #4): 64 global flows.
FLOWS, FLOW_ID0, TOKEN_COUNT, TOKEN_REQUESTS, CLIENTS = 64, 1000, 3, 320, 4

# --chips 4: cluster-mode rules on the pod mesh and the 2x2 (dcn, ici) mesh.
POD_DEVICES, POD_CAPACITY, POD_RES, POD_COUNT = 4, 4096, 64, 20
POD_PER_DEV, POD_STEPS = 256, 3


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Clock:
    """The engine's injected timebase (``SentinelEngine(clock=...)``)."""

    def __init__(self, t: int):
        self.t = t

    def __call__(self) -> int:
        return self.t


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits

    def since(self, mark) -> dict:
        return {"compile_s": round(self.seconds - mark[0], 3),
                "cache_hits": self.hits - mark[1]}


# -- the headline deployment ------------------------------------------------


def headline_rules(n: int = N_RES):
    """(flow, degrade, param) rules: bench.py's mix at finite thresholds,
    plus the phase b/c resources."""
    import sentinel_tpu as st

    flow = [st.FlowRule(resource=f"res{i}", count=FLOW_COUNT)
            for i in range(0, n, 10)]
    degrade = [st.DegradeRule(resource=f"res{i}", grade=i % 3,
                              count=(SLOW_RT_MS, 0.5, EXC_COUNT)[i % 3],
                              slow_ratio_threshold=0.5, time_window=60,
                              min_request_amount=MIN_REQUEST)
               for i in range(0, n, 20)]
    param = [st.ParamFlowRule(f"res{i}", param_idx=0, count=PARAM_COUNT)
             for i in range(0, n, 40)]
    flow += [st.FlowRule(resource=r, count=SYNC_Q_COUNT) for r in ("q0", "q1")]
    flow.append(st.FlowRule(resource="brk", count=SYNC_BRK_COUNT))
    degrade.append(st.DegradeRule(
        resource="brk", grade=2, count=SYNC_BRK_EXC, time_window=60,
        min_request_amount=SYNC_BRK_MIN_REQUEST))
    param.append(st.ParamFlowRule("par", param_idx=0, count=SYNC_PAR_COUNT))
    # A breaker that never trips keeps "par" and the pipe resources on the
    # device path (the token lease takes plain QPS rules).
    degrade.append(st.DegradeRule(resource="par", grade=0, count=1000,
                                  time_window=60))
    for j in range(PIPE_RES):
        flow.append(st.FlowRule(resource=f"pipe{j}", count=PIPE_COUNT))
        degrade.append(st.DegradeRule(resource=f"pipe{j}", grade=1,
                                      count=0.5, time_window=60))
    return flow, degrade, param


def boot(clock: Clock, meter: CompileMeter, n: int = N_RES,
         capacity: int = CAPACITY, widths=None):
    import sentinel_tpu as st

    mark = meter.mark()
    eng = st.reset(capacity=capacity)
    eng.set_clock(clock)
    flow, degrade, param = headline_rules(n)
    st.load_flow_rules(flow)
    st.load_degrade_rules(degrade)
    st.load_param_flow_rules(param)
    t = time.perf_counter()
    eng.warmup(widths)
    emit("boot", resources=n, capacity=capacity,
         rules={"flow": len(flow), "degrade": len(degrade),
                "param": len(param)},
         warmup_s=round(time.perf_counter() - t, 3), **meter.since(mark))
    return eng


def check_counters(eng, phase: str) -> dict:
    """Any fail-open or cluster fallback fails the smoke: the engine keeps
    Sentinel's fallbackToLocalOrPass policy, which would hide a dead step."""
    c = {"fail_open_count": eng.fail_open_count,
         "failOpenCycles": eng.pipeline_stats()["failOpenCycles"],
         "cluster_fallback_count": eng.cluster_fallback_count}
    check(not any(c.values()), f"{phase}: fail-open or fallback {c}")
    return c


def check_mix(phase: str, passes: int, blocks: int) -> None:
    check(passes > 0 and blocks > 0,
          f"{phase}: needs passes and blocks, got {passes}/{blocks}")


# -- phase a: check_batch / complete_batch at width 2048 ---------------------


def phase_batch(eng, clock: Clock, rng, meter: CompileMeter, t0: int,
                n: int = N_RES, width: int = WIDTH) -> None:
    from sentinel_tpu.core import constants as C
    from sentinel_tpu.core.batch import (EntryBatch, ExitBatch,
                                         make_entry_batch_np,
                                         make_exit_batch_np)

    mark = meter.mark()
    started = time.perf_counter()
    reg = eng.registry
    ctx = C.CONTEXT_DEFAULT_NAME
    ent = reg.entrance_row(ctx)
    c_rows = np.asarray([reg.cluster_row(f"res{i}") for i in range(n)],
                        np.int32)
    d_rows = np.asarray([reg.default_row(ctx, f"res{i}", ent)
                         for i in range(n)], np.int32)
    ctx_id = reg.context_id(ctx)
    idx = np.arange(n)
    ruled, param, bad = idx % 10 == 0, idx % 40 == 0, idx % 40 == 20
    grade = idx % 3
    is_open = np.zeros(n, bool)
    got = np.zeros((WINDOWS, n), np.int64)
    want = np.zeros((WINDOWS, n), np.int64)
    reasons = Counter()
    for w in range(WINDOWS):
        base = t0 + w * WINDOW_GAP_MS
        half = width // 2
        traffic = []
        for _ in range(BATCHES):
            res = np.concatenate([rng.integers(0, n, half),
                                  rng.integers(0, n // 10, width - half) * 10])
            rng.shuffle(res)
            traffic.append((res, rng.integers(0, PARAM_VALUES, width)))
        # Reference, from the traffic alone: QPS DEFAULT admits
        # min(arrivals, count) per window; param rules (checked before
        # flow) admit min(arrivals, count) per value; an open breaker
        # admits nothing.
        all_res = np.concatenate([r for r, _ in traffic])
        all_val = np.concatenate([v for _, v in traffic])
        arr = np.bincount(all_res, minlength=n)
        arr_v = np.zeros((n, PARAM_VALUES), np.int64)
        np.add.at(arr_v, (all_res, all_val), 1)
        ref = arr.copy()
        ref[ruled] = np.minimum(arr[ruled], FLOW_COUNT)
        ref[param] = np.minimum(
            np.minimum(arr_v[param], PARAM_COUNT).sum(axis=1), FLOW_COUNT)
        ref[is_open] = 0
        want[w] = ref

        passed = []
        for b, (res, val) in enumerate(traffic):
            clock.t = base + b * BATCH_GAP_MS
            buf = make_entry_batch_np(width)
            buf["cluster_row"][:] = c_rows[res]
            buf["dn_row"][:] = d_rows[res]
            buf["context_id"][:] = ctx_id
            buf["count"][:] = 1
            buf["param_hash"][:, 0] = np.where(param[res], 2 * res + 1 + val, 0)
            buf["param_present"][:, 0] = param[res]
            reason = np.asarray(eng.check_batch(EntryBatch(**buf)).reason)
            reasons.update(C.BlockReason(r).name for r in reason.tolist())
            ok = reason == C.BlockReason.PASS
            np.add.at(got[w], res[ok], 1)
            passed.append((res[ok], val[ok]))
        for b, (res, val) in enumerate(passed):
            clock.t = base + 200 + b * BATCH_GAP_MS
            k = len(res)
            xbuf = make_exit_batch_np(width)
            xbuf["cluster_row"][:k] = c_rows[res]
            xbuf["dn_row"][:k] = d_rows[res]
            xbuf["count"][:k] = 1
            xbuf["rt_ms"][:k] = np.where(bad[res], 5 * SLOW_RT_MS, 5)
            xbuf["success"][:k] = True
            xbuf["error"][:k] = bad[res]
            xbuf["param_hash"][:k, 0] = np.where(param[res], 2 * res + 1 + val, 0)
            xbuf["param_present"][:k, 0] = param[res]
            eng.complete_batch(ExitBatch(**xbuf))
        # Every exit of a bad breaker is slow (grade 0) or an error
        # (grades 1, 2): it trips once its window holds enough requests.
        is_open |= bad & (ref >= MIN_REQUEST) & ((grade != 2) | (ref > EXC_COUNT))

    diff = np.argwhere(got != want)
    check(len(diff) == 0, "batch: passes differ from the reference at "
          f"(window, resource) {diff[:5].tolist()}: got "
          f"{[int(got[w, r]) for w, r in diff[:5]]}, want "
          f"{[int(want[w, r]) for w, r in diff[:5]]}")
    total = WINDOWS * BATCHES * width
    passes = int(got.sum())
    check_mix("batch", passes, total - passes)
    check(reasons["DEGRADE"] > 0 and reasons["PARAM_FLOW"] > 0
          and reasons["FLOW"] > 0, f"batch: a rule family never blocked {reasons}")
    emit("batch", width=width, windows=WINDOWS, entries=total, passes=passes,
         blocks=total - passes, ref_passes=int(want.sum()),
         ref_blocks=total - int(want.sum()),
         blocks_by_reason={k: v for k, v in reasons.items() if k != "PASS"},
         breakers_open=int(is_open.sum()),
         seconds=round(time.perf_counter() - started, 3),
         **meter.since(mark), **check_counters(eng, "batch"))


# -- phase b: synchronous entry / exit ----------------------------------------


def _sync_reference():
    """Serial replay of SYNC_PLAN: entries in plan order, each passed entry
    exits at once; "brk" exits raise, so its breaker trips mid-window."""
    want = []
    tripped = False
    for _ in range(WINDOWS):
        got = Counter()
        errors = total = 0  # the breaker's 1 s stat window rolls over
        for res, arg, n in SYNC_PLAN:
            for _ in range(n):
                if res in ("q0", "q1"):
                    ok = got[(res, arg)] < SYNC_Q_COUNT
                elif res == "par":
                    ok = got[(res, arg)] < SYNC_PAR_COUNT
                else:
                    ok = not tripped and got[(res, arg)] < SYNC_BRK_COUNT
                if not ok:
                    continue
                got[(res, arg)] += 1
                if res == "brk":
                    errors += 1
                    total += 1
                    tripped |= (total >= SYNC_BRK_MIN_REQUEST
                                and errors > SYNC_BRK_EXC)
        want.append(got)
    return want


def phase_sync(eng, clock: Clock, meter: CompileMeter, t0: int) -> None:
    import sentinel_tpu as st

    mark = meter.mark()
    leases = set(eng._leases)
    check({"q0", "q1"} <= leases and not {"brk", "par"} & leases,
          f"sync: lease routing unexpected {sorted(leases & {'q0', 'q1', 'brk', 'par'})}")
    got, kinds = [], Counter()
    for w in range(WINDOWS):
        clock.t = t0 + w * WINDOW_GAP_MS
        passes = Counter()
        for res, arg, n in SYNC_PLAN:
            for _ in range(n):
                try:
                    h = st.entry(res, args=(arg,) if arg else ())
                except st.BlockException as ex:
                    kinds[type(ex).__name__] += 1
                    continue
                passes[(res, arg)] += 1
                if res == "brk":
                    h.trace(RuntimeError("downstream failed"))
                h.exit()
        got.append(passes)
    want = _sync_reference()
    check(got == want, f"sync: got {got}, want {want}")
    n_pass = sum(sum(c.values()) for c in got)
    n_all = WINDOWS * sum(n for _, _, n in SYNC_PLAN)
    check_mix("sync", n_pass, n_all - n_pass)
    check(kinds["DegradeException"] > 0 and kinds["ParamFlowException"] > 0,
          f"sync: expected breaker and param blocks, got {dict(kinds)}")
    emit("sync", entries=n_all, passes=n_pass, blocks=n_all - n_pass,
         ref_passes=sum(sum(c.values()) for c in want),
         blocks_by_exception=dict(kinds), **meter.since(mark),
         **check_counters(eng, "sync"))


# -- phase c: pipelined admission ---------------------------------------------


def phase_pipeline(eng, clock: Clock, meter: CompileMeter, t0: int) -> None:
    import sentinel_tpu as st

    mark = meter.mark()
    check(not {f"pipe{j}" for j in range(PIPE_RES)} & set(eng._leases),
          "pipeline: pipe resources must take the device path")
    eng.start_pipeline()
    got = np.zeros((WINDOWS, PIPE_RES), np.int64)
    lock = threading.Lock()
    try:
        for w in range(WINDOWS):
            clock.t = t0 + w * WINDOW_GAP_MS

            def submit(t, w=w):
                for j in range(PER_THREAD):
                    r = (t + j) % PIPE_RES
                    h = st.entry_ok(f"pipe{r}")
                    if h is not None:
                        with lock:
                            got[w, r] += 1
                        h.exit()

            threads = [threading.Thread(target=submit, args=(t,))
                       for t in range(THREADS)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
    finally:
        eng.stop_pipeline()
    arr = np.zeros(PIPE_RES, np.int64)
    for t in range(THREADS):
        for j in range(PER_THREAD):
            arr[(t + j) % PIPE_RES] += 1
    want = np.tile(np.minimum(arr, PIPE_COUNT), (WINDOWS, 1))
    check((got == want).all(), f"pipeline: got {got.tolist()}, want {want.tolist()}")
    stats = eng.pipeline_stats()
    check(stats["cycles"] > 0, "pipeline: no cycle ran")
    n_all = WINDOWS * THREADS * PER_THREAD
    n_pass = int(got.sum())
    check_mix("pipeline", n_pass, n_all - n_pass)
    emit("pipeline", threads=THREADS, entries=n_all, passes=n_pass,
         blocks=n_all - n_pass, ref_passes=int(want.sum()),
         cycles=stats["cycles"], batched=stats["batched"],
         **meter.since(mark), **check_counters(eng, "pipeline"))


# -- phase d: cluster token server on loopback TLV ----------------------------


def phase_token_server(eng, rng, meter: CompileMeter, t0: int) -> None:
    import jax
    import sentinel_tpu as st
    from sentinel_tpu.cluster.client import ClusterTokenClient
    from sentinel_tpu.cluster.constants import TokenResultStatus
    from sentinel_tpu.cluster.rules import ClusterFlowRuleManager
    from sentinel_tpu.cluster.server import ClusterTokenServer
    from sentinel_tpu.cluster.token_service import DefaultTokenService
    from sentinel_tpu.utils import time_util

    mark = meter.mark()
    rules = ClusterFlowRuleManager()
    rules.load_rules("default", [
        st.FlowRule(resource=f"clus{i}", count=TOKEN_COUNT, cluster_mode=True,
                    cluster_config={"flowId": FLOW_ID0 + i, "thresholdType": 1})
        for i in range(FLOWS)])
    svc = DefaultTokenService(rules)
    time_util.freeze_time(t0)
    server = ClusterTokenServer(svc, host="127.0.0.1", port=0).start()
    clients = []
    try:
        # Boot-time precompile of the batch widths CLIENTS callers fold
        # into (unknown flow ids commit nothing).
        for k in range(1, CLIENTS + 1):
            svc.request_tokens([(None, 0, False)] * k)
        state_devs = {d for leaf in jax.tree.leaves(svc._state)
                      for d in leaf.devices()}
        check(state_devs == {jax.devices()[0]},
              f"token server: state on {state_devs}")
        clients = [ClusterTokenClient("127.0.0.1", server.bound_port,
                                      "default", request_timeout_s=30.0).start()
                   for _ in range(CLIENTS)]
        deadline = time.time() + 30
        while not all(c.is_connected() for c in clients):
            check(time.time() < deadline, "token server: clients never connected")
            time.sleep(0.02)
        got = np.zeros((WINDOWS, FLOWS), np.int64)
        want = np.zeros((WINDOWS, FLOWS), np.int64)
        statuses = Counter()
        lock = threading.Lock()
        for w in range(WINDOWS):
            time_util.freeze_time(t0 + w * WINDOW_GAP_MS)
            flows = rng.integers(0, FLOWS, TOKEN_REQUESTS)
            want[w] = np.minimum(np.bincount(flows, minlength=FLOWS),
                                 TOKEN_COUNT)

            def acquire(c, part, w=w):
                for f in part:
                    r = c.request_token(FLOW_ID0 + int(f))
                    with lock:
                        statuses[TokenResultStatus(r.status).name] += 1
                        if r.status == TokenResultStatus.OK:
                            got[w, f] += 1

            threads = [threading.Thread(target=acquire,
                                        args=(c, flows[i::CLIENTS]))
                       for i, c in enumerate(clients)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
    finally:
        for c in clients:
            c.stop()
        server.stop()
        time_util.unfreeze_time()
    check(set(statuses) <= {"OK", "BLOCKED"},
          f"token server: unexpected statuses {dict(statuses)}")
    check((got == want).all(), "token server: grants differ from the "
          f"reference in {int((got != want).sum())} (window, flow) cells")
    n_all = WINDOWS * TOKEN_REQUESTS
    check_mix("token_server", statuses["OK"], statuses["BLOCKED"])
    emit("token_server", flows=FLOWS, acquires=n_all, passes=statuses["OK"],
         blocks=statuses["BLOCKED"], ref_passes=int(want.sum()),
         **meter.since(mark), **check_counters(eng, "token_server"))


# -- --chips 4: the pod-wide psum limiter ------------------------------------


def _pod_rules(n_res: int, scopes):
    import sentinel_tpu as st

    return [st.FlowRule(resource=f"pod{j}", count=POD_COUNT, cluster_mode=True,
                        cluster_config={"scope": scopes[j % len(scopes)]})
            for j in range(n_res)]


def _pod_pack(rules, capacity: int, now: int):
    from sentinel_tpu.core.registry import NodeRegistry
    from sentinel_tpu.models import authority as A
    from sentinel_tpu.models import degrade as D
    from sentinel_tpu.models import flow as F
    from sentinel_tpu.models import param_flow as PF
    from sentinel_tpu.models import system as Y
    from sentinel_tpu.ops import step as S

    reg = NodeRegistry(capacity)
    rows = np.asarray([reg.cluster_row(r.resource) for r in rules], np.int32)
    ft, _ = F.compile_flow_rules(rules, reg, capacity)
    dt, di = D.compile_degrade_rules([], reg, capacity)
    pt = PF.compile_param_rules([], reg, capacity)
    pack = S.RulePack(flow=ft, degrade=dt,
                      authority=A.compile_authority_rules([], reg, capacity),
                      system=Y.compile_system_rules([]), param=pt)
    one = S.make_state(capacity, ft.num_rules, now,
                       degrade=D.make_degrade_state(dt, di),
                       param=PF.make_param_state(pt.num_rules))
    return rows, pack, one


def _entry_np(rows, picks):
    from sentinel_tpu.core.batch import make_entry_batch_np

    buf = make_entry_batch_np(len(picks))
    buf["cluster_row"][:] = rows[picks]
    buf["count"][:] = 1
    return buf


def _exit_np(rows, picks, ok):
    from sentinel_tpu.core.batch import make_exit_batch_np

    buf = make_exit_batch_np(len(picks))
    buf["cluster_row"][:] = np.where(ok, rows[picks], -1)
    buf["count"][:] = 1
    buf["success"][:] = True
    return buf


def _now(t: int, sharding):
    import jax

    return jax.device_put(np.int64(t), sharding)


def _run_mesh(entry, exit_, state, pack, rows, traffic, shardings, t0):
    """Drive entry (then exit, if given) per step; -> (state, admitted
    [W, S, n]). ``shardings`` = (batch sharding, replicated sharding)."""
    import jax
    from sentinel_tpu.core.batch import EntryBatch, ExitBatch

    batch_sh, rep_sh = shardings
    admitted = np.zeros(traffic.shape, bool)
    for w in range(traffic.shape[0]):
        for s in range(traffic.shape[1]):
            now = t0 + w * WINDOW_GAP_MS + s * 10
            picks = traffic[w, s]
            eb = jax.device_put(EntryBatch(**_entry_np(rows, picks)), batch_sh)
            state, dec = entry(state, pack, eb, _now(now, rep_sh))
            ok = np.asarray(dec.reason) == 0
            admitted[w, s] = ok
            if exit_ is not None:
                xb = jax.device_put(ExitBatch(**_exit_np(rows, picks, ok)),
                                    batch_sh)
                state = exit_(state, pack, xb, _now(now + 5, rep_sh))
    return state, admitted


def _check_pod_bound(name, traffic, admitted, groups, n_res):
    """Per window and resource, over each group of devices sharing a quota:
    min(arrivals, thr) <= admitted <= thr + (D-1) x max per-device
    per-step admission (docs/SEMANTICS.md delta 2), and admission stops
    in the step after the group's count reaches thr."""
    n_w, n_s, width = traffic.shape
    n_dev = sum(len(g) for g in groups)
    per_dev = width // n_dev
    worst = 0
    for w in range(n_w):
        for g in groups:
            cols = np.concatenate([np.arange(d * per_dev, (d + 1) * per_dev)
                                   for d in g])
            res = traffic[w][:, cols]
            adm = admitted[w][:, cols]
            for j in range(n_res):
                arr_ds = [[int((res[s, k * per_dev:(k + 1) * per_dev] == j).sum())
                           for k in range(len(g))] for s in range(n_s)]
                step_adm = [int(((res[s] == j) & adm[s]).sum()) for s in range(n_s)]
                total = sum(step_adm)
                lo = min(sum(map(sum, arr_ds)), POD_COUNT)
                hi = POD_COUNT + (len(g) - 1) * max(
                    min(a, POD_COUNT) for row in arr_ds for a in row)
                check(lo <= total <= hi, f"{name}: window {w} resource {j} "
                      f"devices {g} admitted {total} outside [{lo}, {hi}]")
                for s in range(1, n_s):
                    if sum(step_adm[:s]) >= POD_COUNT:
                        check(step_adm[s] == 0, f"{name}: window {w} resource "
                              f"{j} admitted after the quota propagated")
                worst = max(worst, total - POD_COUNT)
    return worst


def phase_pod(devices, rng, meter: CompileMeter) -> None:
    import jax
    from concurrent.futures import ThreadPoolExecutor
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from sentinel_tpu.core.batch import EntryBatch, ExitBatch
    from sentinel_tpu.ops import step as S
    from sentinel_tpu.parallel import cluster as PC
    from sentinel_tpu.parallel import namespaces as NS

    n_dev, half = len(devices), len(devices) // 2
    mark = meter.mark()
    # [window, step, row] resource picks; row r goes to device r // POD_PER_DEV.
    traffic = rng.integers(0, POD_RES, (WINDOWS, POD_STEPS, n_dev * POD_PER_DEV))

    # Pod mesh: one global quota per cluster-mode rule, via psum.
    rows, pack, one = _pod_pack(_pod_rules(POD_RES, ("pod",)), POD_CAPACITY, T0)
    mesh = Mesh(np.asarray(devices), (PC.AXIS,))
    pod_sh = (NamedSharding(mesh, P(PC.AXIS)), NamedSharding(mesh, P()))
    pod_entry, pod_exit = PC.make_pod_steps(mesh, cluster_param=False)
    pod_state = jax.device_put(PC.make_pod_state(n_dev, one), pod_sh[0])
    pod_pack = jax.device_put(pack, pod_sh[1])
    # 2x2 (dcn, ici) mesh: even resources pod-scope (one quota per ici
    # slice), odd resources global (one quota across both slices).
    rows2, pack2, one2 = _pod_pack(_pod_rules(POD_RES, ("pod", "global")),
                                   POD_CAPACITY, T0)
    mesh2 = NS.make_dcn_mesh(2, half, devices)
    dcn_sh = (NamedSharding(mesh2, P((NS.DCN_AXIS, NS.ICI_AXIS))),
              NamedSharding(mesh2, P()))
    dcn_entry, _ = NS.make_dcn_pod_steps(mesh2, cluster_param=False)
    dcn_state = jax.device_put(NS.make_dcn_pod_state(2, half, one2),
                               NamedSharding(mesh2, P(NS.DCN_AXIS, NS.ICI_AXIS)))
    dcn_pack = jax.device_put(pack2, dcn_sh[1])
    # One chip, the pod's rules and traffic.
    one_sh = (devices[0], devices[0])
    one_state = jax.device_put(one, devices[0])
    one_pack = jax.device_put(pack, devices[0])

    # The four programs compile concurrently (the compiler runs outside
    # the GIL); each is lowered against its real first-step arguments.
    first = traffic[0, 0]
    programs = [
        (pod_entry, (pod_state, pod_pack, EntryBatch(**_entry_np(rows, first))), pod_sh),
        (pod_exit, (pod_state, pod_pack, ExitBatch(**_exit_np(rows, first, first >= 0))), pod_sh),
        (dcn_entry, (dcn_state, dcn_pack, EntryBatch(**_entry_np(rows2, first))), dcn_sh),
        (S.entry_step, (one_state, one_pack, EntryBatch(**_entry_np(rows, first))), one_sh),
    ]

    def compile_one(prog):
        fn, (state, pack_, batch), (batch_sh, rep_sh) = prog
        return jax.jit(fn, donate_argnums=(0,)).lower(
            state, pack_, jax.device_put(batch, batch_sh),
            _now(T0, rep_sh)).compile()

    started = time.perf_counter()
    with ThreadPoolExecutor(len(programs)) as pool:
        pod_entry_c, pod_exit_c, dcn_entry_c, one_entry_c = pool.map(
            compile_one, programs)
    compile_wall = time.perf_counter() - started

    pod_state, adm_pod = _run_mesh(pod_entry_c, pod_exit_c, pod_state,
                                   pod_pack, rows, traffic, pod_sh, T0)
    for leaf in jax.tree.leaves(pod_state):
        check(leaf.sharding.device_set == set(devices)
              and len(leaf.addressable_shards) == n_dev,
              f"pod: a state leaf is not sharded over {n_dev} devices")
    check(int(np.asarray(pod_state.cur_threads).sum()) == 0,
          "pod: exits did not balance the thread gauges")
    over_pod = _check_pod_bound("pod", traffic, adm_pod,
                                [list(range(n_dev))], POD_RES)

    dcn_state, adm_dcn = _run_mesh(dcn_entry_c, None, dcn_state, dcn_pack,
                                   rows2, traffic, dcn_sh, T0)
    for leaf in jax.tree.leaves(dcn_state):
        check(leaf.sharding.device_set == set(devices),
              "dcn: a state leaf is not sharded over every device")
    even = traffic % 2 == 0
    over_dcn = max(
        _check_pod_bound("dcn pod-scope", np.where(even, traffic, -1), adm_dcn,
                         [list(range(half)), list(range(half, n_dev))], POD_RES),
        _check_pod_bound("dcn global", np.where(~even, traffic, -1), adm_dcn,
                         [list(range(n_dev))], POD_RES))

    # One chip, same traffic and rules: exactly min(arrivals, thr).
    _, adm_one = _run_mesh(one_entry_c, None, one_state, one_pack, rows,
                           traffic, one_sh, T0)
    ref_one = 0
    for w in range(WINDOWS):
        flat = traffic[w].ravel()
        got = np.bincount(flat[adm_one[w].ravel()], minlength=POD_RES)
        want = np.minimum(np.bincount(flat, minlength=POD_RES), POD_COUNT)
        check((got == want).all(), f"one chip: window {w} admitted "
              f"{got.tolist()}, reference {want.tolist()}")
        ref_one += int(want.sum())
    n_all = traffic.size
    for name, adm in (("pod", adm_pod), ("dcn", adm_dcn), ("one_chip", adm_one)):
        check_mix(name, int(adm.sum()), n_all - int(adm.sum()))
    emit("pod", devices=n_dev, resources=POD_RES, threshold=POD_COUNT,
         entries=n_all, passes_pod=int(adm_pod.sum()),
         passes_dcn=int(adm_dcn.sum()), passes_one_chip=int(adm_one.sum()),
         ref_passes_one_chip=ref_one, max_overshoot_pod=int(over_pod),
         max_overshoot_dcn=int(over_dcn),
         compile_wall_s=round(compile_wall, 3), **meter.since(mark))


# -- driver -------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2

    from sentinel_tpu.ops import segment
    from sentinel_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    check(not segment._use_cpu_exact(), "the CPU sort/scatter route is on")
    meter = CompileMeter()
    rng = np.random.default_rng(args.seed)
    emit("device", platform=devices[0].platform,
         device_kind=devices[0].device_kind, count=len(devices),
         jax=jax.__version__, compile_cache=cache_dir)

    if args.chips == 4:
        used = devices[:POD_DEVICES]
        phase_pod(used, rng, meter)
    else:
        used = devices[:1]
        clock = Clock(T0)
        eng = boot(clock, meter)
        state_devs = {d for leaf in jax.tree.leaves(eng._state)
                      for d in leaf.devices()}
        check(state_devs == {devices[0]}, f"engine state on {state_devs}")
        span = WINDOWS * WINDOW_GAP_MS
        phase_batch(eng, clock, rng, meter, T0 + span)
        phase_sync(eng, clock, meter, T0 + 2 * span)
        phase_pipeline(eng, clock, meter, T0 + 3 * span)
        phase_token_server(eng, rng, meter, T0 + 4 * span)
        eng.close()
        emit("done", **meter.since((0.0, 0)))
    print(json.dumps({"ok": True, "device": {
        "platform": used[0].platform, "kind": used[0].device_kind,
        "count": len(used)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
