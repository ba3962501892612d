"""A breakers-only engine deployment: every resource guarded by the same
list of ``DegradeRule``s, loaded in rule order, and no other rule, built
through the public entry points (``st.reset``, ``st.load_degrade_rules``)."""

from __future__ import annotations

import numpy as np

from benchmark.builders.engine import EngineDeployment


class Layout:
    """The deployment's breakers as arrays over (resource index ``i``,
    slot ``k``): resource ``res{i}`` carries rule ``k`` of the config's
    ``rules`` at slot ``k``. What both the program and the reference are
    given."""

    def __init__(self, cfg: dict):
        self.n = int(cfg["resources"])
        self.rules = [dict(r) for r in cfg["rules"]]
        self.slots = len(self.rules)
        col = lambda key, default=0.0: np.asarray(
            [r.get(key, default) for r in self.rules], np.float64)
        self.grade = col("grade").astype(np.int64)        # [K]
        self.count = col("count")                          # [K]
        self.slow_ratio = col("slow_ratio_threshold", 1.0)  # [K]
        self.min_request = int(cfg["min_request_amount"])
        self.interval_ms = int(cfg["stat_interval_ms"])
        self.window_ms = int(cfg["time_window_s"]) * 1000


def rules(layout: Layout):
    import sentinel_tpu as st

    L = layout
    return [st.DegradeRule(
        resource=f"res{i}", grade=int(r["grade"]), count=r["count"],
        slow_ratio_threshold=r.get("slow_ratio_threshold", 1.0),
        min_request_amount=L.min_request, stat_interval_ms=L.interval_ms,
        time_window=L.window_ms // 1000)
        for i in range(L.n) for r in L.rules]


def build(cfg: dict) -> EngineDeployment:
    import sentinel_tpu as st
    from sentinel_tpu.core import constants as C

    layout = Layout(cfg)
    eng = st.reset(capacity=int(cfg["capacity"]))
    st.load_degrade_rules(rules(layout))
    reg = eng.registry
    ctx = C.CONTEXT_DEFAULT_NAME
    ent = reg.entrance_row(ctx)
    c_rows = np.asarray([reg.cluster_row(f"res{i}") for i in range(layout.n)],
                        np.int32)
    d_rows = np.asarray([reg.default_row(ctx, f"res{i}", ent)
                         for i in range(layout.n)], np.int32)
    if (c_rows < 0).any() or (d_rows < 0).any():
        raise RuntimeError("the registry ran out of rows for the deployment")
    return EngineDeployment(eng, layout, c_rows, d_rows, reg.context_id(ctx))
