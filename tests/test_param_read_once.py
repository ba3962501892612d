"""The param-flow check reads its state once per step.

``check_param_flow`` gathers everything that does not depend on the
survivor set (rule lookup, bucket table cells, sketch readings) once, before
the survivor fixpoint; the sweeps and the commit only add the segmented
prefix over the survivors. Two pins:

  * **structure**: the lowered entry step gathers each ``[PR, D, W]``
    sketch at most three times per rule slot (admission sketch at the
    value's columns, promotion sketch at the value's and at the owner's
    columns) and each ``[PR, S]`` bucket table once per rule slot;
  * **bit identity**: verdicts, ``wait_us``, the blocking rule slot and
    every ``ParamFlowState`` field match, step by step, goldens recorded
    from the per-sweep implementation this replaced
    (``tests/data/param_flow_golden.json``; rewrite with
    ``python tests/test_param_read_once.py --write``).
"""

from __future__ import annotations

import hashlib
import json
import re
import sys
from pathlib import Path

if __name__ == "__main__":
    import os

    os.environ["JAX_PLATFORMS"] = "cpu"

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.batch import EntryBatch, make_entry_batch_np
from sentinel_tpu.core.registry import NodeRegistry
from sentinel_tpu.models import authority as A
from sentinel_tpu.models import degrade as D
from sentinel_tpu.models import flow as F
from sentinel_tpu.models import param_flow as P
from sentinel_tpu.models import system as Y
from sentinel_tpu.ops import step as S

NOW0 = 1_700_000_000_000
GOLDEN = Path(__file__).parent / "data" / "param_flow_golden.json"


# --------------------------------------------------------------------------
# structure: gathers per rule slot in the lowered entry step

def _entry_program(n_res: int, capacity: int, param_rules):
    """Lowered ``entry_step`` over the headline mix at a small size."""
    reg = NodeRegistry(capacity)
    rows = np.asarray([reg.cluster_row(f"res{i}") for i in range(n_res)])
    ft, _ = F.compile_flow_rules(
        [F.FlowRule(resource=f"res{i}", count=20) for i in range(0, n_res, 10)],
        reg, capacity)
    dt, di = D.compile_degrade_rules(
        [D.DegradeRule(resource=f"res{i}", count=100, grade=i % 3,
                       time_window=10) for i in range(5, n_res, 20)],
        reg, capacity)
    pt = P.compile_param_rules(param_rules, reg, capacity)
    pack = S.RulePack(
        flow=ft, degrade=dt,
        authority=A.compile_authority_rules([], reg, capacity),
        system=Y.compile_system_rules([]),
        param=pt)
    state = S.make_state(capacity, ft.num_rules, NOW0,
                         degrade=D.make_degrade_state(dt, di),
                         param=P.make_param_state(pt.num_rules))
    buf = make_entry_batch_np(512)
    buf["cluster_row"][:] = rows[np.arange(512) % n_res]
    buf["count"][:] = 1
    batch = EntryBatch(**{k: jnp.asarray(v) for k, v in buf.items()})
    text = jax.jit(S.entry_step).lower(
        state, pack, batch, jnp.asarray(NOW0, jnp.int64)).as_text()
    return text, pt, state.param


def _gathers_from(text: str, operand_type: str) -> int:
    """Number of ``stablehlo.gather`` ops whose operand is ``operand_type``."""
    pat = re.compile(r'"stablehlo\.gather"\([^)]*\).*:\s*\(' + re.escape(operand_type) + ",")
    return sum(1 for line in text.splitlines() if pat.search(line))


def _tensor_type(a) -> str:
    names = {"float32": "f32", "uint32": "ui32", "int32": "i32", "int64": "i64"}
    return "tensor<" + "x".join(map(str, a.shape)) + "x" + names[str(a.dtype)] + ">"


HEADLINE_PARAM = lambda n: [P.ParamFlowRule(f"res{i}", param_idx=0, count=5)
                            for i in range(0, n, 40)]
TWO_ON_ONE = lambda n: HEADLINE_PARAM(n) + [
    P.ParamFlowRule("res0", param_idx=1, count=3)]


@pytest.mark.parametrize("rules", [HEADLINE_PARAM, TWO_ON_ONE],
                         ids=["one_slot", "two_slots"])
def test_entry_step_reads_param_state_once_per_slot(rules):
    # 400 resources over 2048 rows: the headline_10k rehearsal sizes.
    text, rt, ps = _entry_program(400, 2048, rules(400))
    k = rt.slots
    assert k == (2 if rules is TWO_ON_ONE else 1)
    sketch = _gathers_from(text, _tensor_type(ps.cms))
    assert 1 <= sketch <= 3 * k, (sketch, k)
    # filled_ms and passed_us share one type: two tables, one read each.
    per_type = {}
    for field in ("key", "tokens", "filled_ms", "passed_us", "threads"):
        t = _tensor_type(getattr(ps, field))
        per_type[t] = per_type.get(t, 0) + k
    for t, most in per_type.items():
        n = _gathers_from(text, t)
        assert 1 <= n <= most, (t, n, most)


# --------------------------------------------------------------------------
# bit identity: step-by-step digests against the recorded goldens

N = 256
# Clock readings (ms after NOW0): inside one window, across the 1 s and the
# 2 s windows' boundaries, and past an idle gap.
STEP_MS = (0, 3, 400, 999, 1000, 1001, 1700, 2500, 4100, 4101)


def _mixed_rules(cluster: bool = False):
    """One rule per grade/behaviour, each on its own resource (res0..res3;
    res4 carries no rule)."""
    kw = dict(cluster_mode=cluster)
    return [
        P.ParamFlowRule("res0", param_idx=0, count=5,
                        items=[P.ParamFlowItem(object=7, count=2)], **kw),
        P.ParamFlowRule("res1", param_idx=0, count=3,
                        grade=C.PARAM_FLOW_GRADE_THREAD, **kw),
        P.ParamFlowRule("res2", param_idx=0, count=10,
                        control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                        max_queueing_time_ms=200, **kw),
        P.ParamFlowRule("res3", param_idx=0, count=4, burst_count=2,
                        duration_in_sec=2, **kw),
    ]


def _two_rules():
    """Two rules on res0 over different parameters, and a second pair on
    res1 with a thread-grade rule in the second slot."""
    return [
        P.ParamFlowRule("res0", param_idx=0, count=5),
        P.ParamFlowRule("res0", param_idx=1, count=3),
        P.ParamFlowRule("res1", param_idx=1, count=2,
                        control_behavior=C.CONTROL_BEHAVIOR_RATE_LIMITER,
                        max_queueing_time_ms=500),
        P.ParamFlowRule("res1", param_idx=0, count=2,
                        grade=C.PARAM_FLOW_GRADE_THREAD),
    ]


def _values(rng, n, pool):
    """Zipf-ish draws over ``pool`` values; every 3rd pool value collides
    with another's slot (differs by a multiple of the table width)."""
    base = 1 + np.arange(pool, dtype=np.uint64) * 37
    base[2::3] = base[0:-2:3][: len(base[2::3])] + P.DEFAULT_SLOTS * 5
    ranks = np.minimum(rng.zipf(1.3, n) - 1, pool - 1)
    return base[ranks].astype(np.uint32)


def _batches(case: str, rng, res_rows):
    """Per step: (batch dict, candidate mask, extra_cms or None).
    ``res_rows[i]`` is resource ``res{i}``'s row."""
    out = []
    for i, _ in enumerate(STEP_MS):
        buf = make_entry_batch_np(N)
        rows = res_rows[rng.integers(0, 5, N)]
        rows[rng.random(N) < 0.05] = -1        # padding lanes
        buf["cluster_row"][:] = rows
        buf["param_present"][:, :2] = rng.random((N, 2)) < 0.95
        buf["param_hash"][:, 0] = _values(rng, N, 24)
        buf["param_hash"][:, 1] = _values(rng, N, 12)
        if case == "uniform" or case == "pod":
            buf["count"][:] = 1
        elif case == "mixed":
            buf["count"][:] = rng.integers(1, 4, N)
        elif case == "two_rules":
            buf["count"][:] = 1 if i % 2 == 0 else rng.integers(1, 3, N)
        elif case == "storm":
            hot = (1 + np.arange(8) * 101).astype(np.uint32)
            if i == 0:
                vals = np.repeat(hot, N // 8)
            else:
                # Cold keys: half random, half on the hot keys' slots.
                vals = rng.integers(1, 2**32, N, dtype=np.uint64).astype(np.uint32)
                steal = rng.random(N) < 0.5
                vals[steal] = (hot[rng.integers(0, 8, N)]
                               + P.DEFAULT_SLOTS * rng.integers(1, 50, N)
                               ).astype(np.uint32)[steal]
                vals[: 8] = hot
            buf["cluster_row"][:] = res_rows[0]
            buf["param_hash"][:, 0] = vals
            buf["param_present"][:, 0] = True
            buf["count"][:] = 1
        buf["skip_cluster"][:] = rng.random(N) < 0.1
        cand = (buf["cluster_row"] >= 0) & (rng.random(N) < 0.9)
        extra = None
        if case == "pod":
            extra = rng.integers(0, 3, (8, P.CMS_DEPTH, P.CMS_WIDTH)
                                 ).astype(np.float32)
        out.append((buf, cand, extra))
    return out


CASES = {
    "uniform": _mixed_rules,
    "mixed": _mixed_rules,
    "storm": lambda: [P.ParamFlowRule("res0", param_idx=0, count=5)],
    "two_rules": _two_rules,
    "pod": lambda: _mixed_rules(cluster=True),
}


def _digest(a) -> str:
    a = np.ascontiguousarray(np.asarray(a))
    return hashlib.sha256(str(a.dtype).encode() + str(a.shape).encode()
                          + a.tobytes()).hexdigest()[:20]


def _run(case: str):
    reg = NodeRegistry(64)
    res_rows = np.asarray([reg.cluster_row(f"res{i}") for i in range(5)],
                          np.int32)
    rt = P.compile_param_rules(CASES[case](), reg, 64, hash_fn=int)
    rng = np.random.default_rng(sorted(CASES).index(case) + 2024)

    @jax.jit
    def check(ps, batch, now, cand, extra):
        return P.check_param_flow(rt, ps, batch, now, cand, extra_cms=extra)

    ps = P.make_param_state(rt.num_rules)
    steps = []
    for dt, (buf, cand, extra) in zip(STEP_MS, _batches(case, rng, res_rows)):
        batch = EntryBatch(**{k: jnp.asarray(v) for k, v in buf.items()})
        v = check(ps, batch, jnp.asarray(NOW0 + dt, jnp.int64),
                  jnp.asarray(cand),
                  None if extra is None else jnp.asarray(extra))
        ps = v.state
        blocked = np.asarray(v.blocked)
        rec = {"blocked": int(blocked.sum()),
               "admitted": int((cand & ~blocked).sum()),
               "digest": {"blocked": _digest(v.blocked),
                          "wait_us": _digest(v.wait_us),
                          "slot": _digest(v.slot)}}
        rec["digest"].update({f: _digest(getattr(ps, f))
                              for f in P.ParamFlowState._fields})
        steps.append(rec)
    return steps


def test_each_rule_sits_at_one_row_and_slot():
    """What lets every slot read before any slot commits: a rule id occurs
    once in ``rules_by_row``, so slot k's writes touch no rule slot k+1
    reads."""
    reg = NodeRegistry(64)
    rules = _two_rules() + _mixed_rules() + [
        P.ParamFlowRule("res0", param_idx=2, count=1),
        P.ParamFlowRule("res0", param_idx=3, count=-1)]  # invalid: dropped
    rt = P.compile_param_rules(rules, reg, 64, hash_fn=int)
    ids = np.asarray(rt.rules_by_row)
    assert rt.slots == 4  # res0 carries four valid rules
    placed = ids[ids >= 0]
    assert len(placed) == len(set(placed.tolist())) == len(rules) - 1


@pytest.mark.parametrize("case", sorted(CASES))
def test_param_flow_matches_golden(case):
    want = json.loads(GOLDEN.read_text())[case]
    got = _run(case)
    # The sequence must exercise both verdicts, or it pins nothing.
    assert sum(s["blocked"] for s in got) > 0
    assert sum(s["admitted"] for s in got) > 0
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, (case, i, STEP_MS[i])
    assert len(got) == len(want)


if __name__ == "__main__" and "--write" in sys.argv:
    GOLDEN.parent.mkdir(parents=True, exist_ok=True)
    GOLDEN.write_text(json.dumps({c: _run(c) for c in sorted(CASES)},
                                 indent=1, sort_keys=True) + "\n")
