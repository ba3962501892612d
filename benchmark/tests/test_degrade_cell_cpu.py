"""CPU rehearsal of ``degrade_outage_1k`` at its ``rehearsal`` sizes: the
cell is correct, its control is not, and the check catches two faults.
Run by hand, not by the tier-1 suite:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_degrade_cell_cpu.py -q
"""

import functools
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import harness

CELL = "degrade_outage_1k"
SEED = 2**31 + 7  # more than 32 signed bits, as the driver's are


def _run(seconds=3.0, trace=False, **kw):
    return harness.run_cell(CELL, SEED, seconds, trace, time.perf_counter(),
                            rehearsal=True, **kw)


def test_cell_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {"verdict_mismatches", "thread_gauge_max",
                                  "transition_mismatches"}
    assert "setup_s" in out["metrics"] and len(out["metrics"]) >= 2


def test_traced_run_reports_both_lags():
    out = _run(trace=True)
    assert out["correct"], out["checks"]
    for name in ("breaker.open_lag_ms", "breaker.close_lag_ms"):
        assert out["metrics"][name]["value"] > 0


def test_control_is_not_correct():
    """The reference in bfloat16, replayed in the program's place on the
    window's own batches and clock readings."""
    assert not _run(control=True)["correct"]


def _freeze_breakers(drv):
    """The exit step returns the breakers' state it was given."""
    import jax
    from sentinel_tpu.ops import step as S

    eng = drv.dep.engine
    step = functools.partial(S.exit_step, spec1=eng._spec1)

    def frozen(state, *args, **kw):
        return step(state, *args, **kw)._replace(degrade=state.degrade)

    eng._exit_jit = jax.jit(frozen)
    drv.warm()


def _alter_verdict(drv):
    """One verdict per batch flipped where it is produced."""
    eng = drv.dep.engine
    real = eng.check_batch

    def check_batch(batch, now_ms=None):
        reason = np.array(real(batch, now_ms=now_ms).reason)
        reason[0] = 2 if reason[0] == 0 else 0
        return SimpleNamespace(reason=reason)

    drv.sut = SimpleNamespace(check_batch=check_batch,
                              complete_batch=eng.complete_batch)


@pytest.mark.parametrize("fault", [_freeze_breakers, _alter_verdict],
                         ids=lambda f: f.__name__)
def test_fault_is_caught(fault):
    assert not _run(fault=fault)["correct"]
