"""Hot-parameter flow rules: per-argument-value token buckets.

Reference surface (SURVEY.md §2.2 "sentinel-parameter-flow-control"):
``ParamFlowRule`` (paramIdx, grade QPS/THREAD, count, durationInSec,
burstCount, controlBehavior DEFAULT/RATE_LIMITER, per-value ``ParamFlowItem``
exceptions, clusterMode), ``ParamFlowRuleManager``, ``ParamFlowChecker``
(``passDefaultLocalCheck`` token-bucket CAS over ``tokenCounters`` /
``timeCounters``; ``passThrottleLocalCheck`` per-value leaky bucket;
LRU-bounded key space via ``CacheMap``). Upstream paths: ``param:…``
(reference mount was empty; citations are upstream-layout paths).

TPU-native design (the BASELINE "CMS + top_k" north star, two tiers):

  * **Hot tier — exact.** Each rule owns a fixed direct-mapped slot table
    on device — ``slot = hash(value) % S`` — holding exact bucket state
    (owner key, tokens, refill time, thread gauge). The table IS the
    top-k hot set: ownership is *promotion-gated* (below), so sustained
    hot keys hold their slots and get exact token-bucket semantics, the
    analog of the reference's LRU ``CacheMap`` hot entries.
  * **Cold tier — count-min sketch.** A per-rule CMS
    (``[D, W]`` with D independent multiplicative hashes of the 32-bit
    value hash) counts every admitted acquire in the current duration
    window. A key that does NOT own its slot admits against
    ``max_count − min_d CMS[d, h_d(key)]`` instead of a free fresh
    bucket — so a 100k-key space is still rate-limited per value, with
    one-sided error: CMS only over-estimates, so cold keys can only be
    under-admitted, never over-admitted (fail-closed; property-tested in
    tests/test_param_cms.py).
  * **Promotion (space-saving top-k).** An admitted non-owner key takes
    the slot only when its CMS count has reached the owner's — a
    cold-key storm can no longer evict a genuinely hot key's exact
    bucket, while a newly-hot key wins the slot within one window.
    QPS/DEFAULT grade uses this two-tier path; THREAD and RATE_LIMITER
    grades keep direct eviction (their per-value state has no windowed
    CMS analog).

Within a micro-batch, arrival-order exactness uses the same
segmented-prefix machinery as flow rules. Per-value exception items
compile to an exact-match (hash → threshold) side table, checked before
the rule-wide threshold — matching ``ParamFlowItem`` semantics for the
value types our host hash covers.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.rule_manager import RuleManager
from sentinel_tpu.core.batch import EntryBatch, ExitBatch, MAX_PARAMS
from sentinel_tpu.core.registry import NodeRegistry
from sentinel_tpu.ops import fixpoint as FX
from sentinel_tpu.ops import window as W
from sentinel_tpu.ops.segment import segmented_prefix_dense
from sentinel_tpu.utils.shapes import round_up as _round_up

DEFAULT_SLOTS = 2048  # per-rule bucket table width (reference LRU cap: 4000)
MAX_ITEMS = 8         # per-rule exact-value exception slots

# Count-min sketch geometry (cold tier). With W=2048 and D=4, the classic
# bound gives over-estimate ≤ ~e·N/W per row (N = window acquires) with
# probability 1 − e^−D; one-sided error only.
# Both sketches (admission + promotion) share this depth. Measured dead
# ends (r4, real chip) — don't re-try:
# - a shallower promotion sketch (depth 2) halves its gather/scatter
#   cost but fattens the min-estimate's low tail enough that one of
#   ~100k storm challengers out-scores a hot owner
#   (test_hot_key_exact_and_survives_cold_storm);
# - probing via blocked one-hot matmuls instead of scalar gathers
#   (rowvals = onehot_rule @ table, sampled at pos) benched 1.27ms vs
#   0.84ms per 8192-probe step: the [block, D, W] rowvals
#   materialization costs more than the ~13ns/elem DynamicGather it
#   replaces.
CMS_DEPTH = 4
CMS_WIDTH = 2048
# Odd multiplicative-hash constants (Knuth/xxhash-style); row d's position
# for a 32-bit value hash v is ((v · A_d) >> 16) mod W, computable on
# device from the stored owner key too.
_CMS_MULT = np.array([0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F],
                     np.uint32)


def _cms_positions(pv_hash: jax.Array) -> jax.Array:
    """[N] uint32 value hashes -> [N, D] int32 sketch columns."""
    h = pv_hash[:, None] * jnp.asarray(_CMS_MULT)[None, :]  # uint32 wrap
    return ((h >> jnp.uint32(16)) % jnp.uint32(CMS_WIDTH)).astype(jnp.int32)


@dataclass
class ParamFlowItem:
    """Per-value threshold exception (reference: ``ParamFlowItem``)."""

    object: object
    count: float
    # class_type is implicit: the host hash is type-tagged (engine._hash_param)


@dataclass
class ParamFlowRule:
    resource: str
    param_idx: int
    count: float
    grade: int = C.PARAM_FLOW_GRADE_QPS
    duration_in_sec: int = 1
    burst_count: int = 0
    control_behavior: int = C.CONTROL_BEHAVIOR_DEFAULT
    max_queueing_time_ms: int = 0
    items: List[ParamFlowItem] = field(default_factory=list)
    cluster_mode: bool = False
    cluster_config: Optional[dict] = None
    # Staged rollout (sentinel_tpu/rollout/): see FlowRule.candidate_set.
    candidate_set: Optional[str] = None
    rollout_stage: Optional[str] = None

    def is_valid(self) -> bool:
        if not self.resource or self.count < 0 or self.duration_in_sec <= 0:
            return False
        if self.burst_count < 0 or self.max_queueing_time_ms < 0:
            return False
        if not (0 <= self.param_idx < MAX_PARAMS):
            return False
        if self.grade not in (C.PARAM_FLOW_GRADE_QPS, C.PARAM_FLOW_GRADE_THREAD):
            return False
        if self.control_behavior not in (
            C.CONTROL_BEHAVIOR_DEFAULT, C.CONTROL_BEHAVIOR_RATE_LIMITER
        ):
            return False
        return True


class ParamRuleTensors(NamedTuple):
    resource_row: jax.Array  # int32[PR]
    param_idx: jax.Array     # int32[PR]
    grade: jax.Array         # int32[PR]
    threshold: jax.Array     # float32[PR]
    duration_ms: jax.Array   # int64[PR]
    burst: jax.Array         # float32[PR]
    behavior: jax.Array      # int32[PR]
    max_queue_us: jax.Array  # int64[PR]
    item_hash: jax.Array     # uint32[PR, MAX_ITEMS] 0 = empty
    item_count: jax.Array    # float32[PR, MAX_ITEMS]
    cluster_mode: jax.Array  # bool[PR]
    remote_mode: jax.Array   # bool[PR] cluster rule with a flowId (token server)
    rules_by_row: jax.Array  # int32[R, K]

    @property
    def num_rules(self) -> int:
        return self.resource_row.shape[0]

    @property
    def slots(self) -> int:
        return self.rules_by_row.shape[1]


class ParamFlowState(NamedTuple):
    """Per-(rule, hash-slot) bucket table + cold-tier CMS (re-created on
    rule load)."""

    key: jax.Array        # uint32[PR, S] owner param hash, 0 = empty
    tokens: jax.Array     # float32[PR, S] remaining tokens (QPS/default)
    filled_ms: jax.Array  # int64[PR, S] last refill time
    passed_us: jax.Array  # int64[PR, S] throttle-mode leaky-bucket head
    threads: jax.Array    # int32[PR, S] concurrency gauge (THREAD grade)
    cms: jax.Array        # float32[PR, D, W] THIS-window acquire sketch
                          # (admission tier; hard-reset each window)
    cms_hot: jax.Array    # float32[PR, D, W] decayed hotness sketch
                          # (promotion gate only; halves each window so a
                          # hot owner's history survives the boundary.
                          # Both gate sides — challenger AND owner — read
                          # THIS sketch, so collision inflation cancels
                          # instead of biasing the comparison.)
    cms_start: jax.Array  # int64[PR] sketch window start (per-rule duration)


def make_param_state(num_rules: int, table_slots: int = DEFAULT_SLOTS) -> ParamFlowState:
    pr, s = num_rules, table_slots
    return ParamFlowState(
        key=jnp.zeros((pr, s), jnp.uint32),
        tokens=jnp.zeros((pr, s), jnp.float32),
        filled_ms=jnp.zeros((pr, s), jnp.int64),
        passed_us=jnp.zeros((pr, s), jnp.int64),
        threads=jnp.zeros((pr, s), jnp.int32),
        cms=jnp.zeros((pr, CMS_DEPTH, CMS_WIDTH), jnp.float32),
        cms_hot=jnp.zeros((pr, CMS_DEPTH, CMS_WIDTH), jnp.float32),
        cms_start=jnp.zeros((pr,), jnp.int64),
    )


def compile_param_rules(
    rules: List["ParamFlowRule"],
    registry: NodeRegistry,
    num_rows: int,
    hash_fn=None,
    min_slots: int = 0,
) -> ParamRuleTensors:
    from sentinel_tpu.utils.param_hash import hash_param

    hash_fn = hash_fn or hash_param
    valid = [r for r in rules if r.is_valid()]
    pr = _round_up(len(valid), 8)
    res_row = np.full(pr, -1, np.int32)
    param_idx = np.zeros(pr, np.int32)
    grade = np.zeros(pr, np.int32)
    threshold = np.zeros(pr, np.float32)
    duration_ms = np.full(pr, 1000, np.int64)
    burst = np.zeros(pr, np.float32)
    behavior = np.zeros(pr, np.int32)
    max_queue_us = np.zeros(pr, np.int64)
    item_hash = np.zeros((pr, MAX_ITEMS), np.uint32)
    item_count = np.zeros((pr, MAX_ITEMS), np.float32)
    cluster_mode = np.zeros(pr, bool)
    remote_mode = np.zeros(pr, bool)
    by_row: Dict[int, List[int]] = {}

    for i, r in enumerate(valid):
        row = registry.cluster_row(r.resource)
        res_row[i] = row
        param_idx[i] = r.param_idx
        grade[i] = r.grade
        threshold[i] = r.count
        duration_ms[i] = r.duration_in_sec * 1000
        burst[i] = r.burst_count
        behavior[i] = r.control_behavior
        max_queue_us[i] = r.max_queueing_time_ms * 1000
        cluster_mode[i] = r.cluster_mode
        remote_mode[i] = (r.cluster_mode
                          and (r.cluster_config or {}).get("flowId") is not None)
        for j, item in enumerate(r.items[:MAX_ITEMS]):
            item_hash[i, j] = hash_fn(item.object)
            item_count[i, j] = item.count
        if row >= 0:
            by_row.setdefault(row, []).append(i)

    # 0 when no rules: the per-slot loop then vanishes at trace time,
    # so rule-free deployments pay nothing for this family (the
    # dropped-index scatters of an empty table still cost ~0.1ms/step
    # per scatter at batch 8192 on TPU). ``min_slots`` is the engine's
    # ratchet: crossing 0 -> 1 slots is a SHAPE change that retraces the
    # fused step, so the engine floors this at the widest slot count it
    # has ever compiled — one retrace when a family is first used, none
    # on later pushes (including dropping back to zero rules).
    k = max(min_slots, max((len(v) for v in by_row.values()), default=0))
    rules_by_row = np.full((num_rows, k), -1, np.int32)
    for row, ids in by_row.items():
        rules_by_row[row, : len(ids)] = ids

    return ParamRuleTensors(
        resource_row=jnp.asarray(res_row),
        param_idx=jnp.asarray(param_idx),
        grade=jnp.asarray(grade),
        threshold=jnp.asarray(threshold),
        duration_ms=jnp.asarray(duration_ms),
        burst=jnp.asarray(burst),
        behavior=jnp.asarray(behavior),
        max_queue_us=jnp.asarray(max_queue_us),
        item_hash=jnp.asarray(item_hash),
        item_count=jnp.asarray(item_count),
        cluster_mode=jnp.asarray(cluster_mode),
        remote_mode=jnp.asarray(remote_mode),
        rules_by_row=jnp.asarray(rules_by_row),
    )


class ParamFlowRuleManager(RuleManager):
    """Wholesale-swap registry (reference: ``ParamFlowRuleManager``).

    Gateway-derived rules live in a separate partition so a user
    ``load_rules`` and a ``GatewayRuleManager.load_rules`` can't clobber
    each other; checkers see the union via ``get_rules``.
    """

    def __init__(self):
        super().__init__()
        self._gateway_rules: List[ParamFlowRule] = []

    def load_gateway_rules(self, rules: List[ParamFlowRule]) -> None:
        with self._lock:
            self._gateway_rules = [r for r in rules if r.is_valid()]
            self.version += 1
            listeners = list(self._listeners)
        for fn in listeners:
            fn()

    def get_rules(self) -> List[ParamFlowRule]:
        with self._lock:
            return list(self._rules) + list(self._gateway_rules)


class ParamVerdict(NamedTuple):
    blocked: jax.Array  # bool[N]
    wait_us: jax.Array  # int64[N] throttle-mode sleep-then-pass
    state: ParamFlowState
    slot: jax.Array  # int32[N] first-blocking rule slot (-1 = not blocked)


def _gather1(arr, idx, fill):
    return arr.at[W.oob(idx, arr.shape[0])].get(mode="fill", fill_value=fill)


def _gather2(arr, r, s, fill):
    ok = (r >= 0) & (r < arr.shape[0])
    return jnp.where(ok, arr[jnp.where(ok, r, 0), s], jnp.asarray(fill, arr.dtype))


def _cms_probe(cms: jax.Array, srule: jax.Array, pos: jax.Array) -> jax.Array:
    """Depth-wise readings ``cms[rule, d, pos[:, d]]``, [N, D]. ``srule`` < 0
    (no applicable rule) reads row 0."""
    r = jnp.where(srule >= 0, srule, 0)
    return cms[r[:, None], jnp.arange(cms.shape[1])[None, :], pos]


def _cms_min(vals: jax.Array, srule: jax.Array) -> jax.Array:
    """The CMS estimate from a probe's readings: their min over depth, 0
    where no rule applies."""
    return jnp.where(srule >= 0, vals.min(axis=1), 0.0)


class _SlotRead(NamedTuple):
    """What one rule slot's check reads from the rules and the state before
    any survivor set is known (see ``_read_slot``)."""

    srule: jax.Array       # int32[N] applicable rule id, -1 otherwise
    applicable: jax.Array  # bool[N]
    slot: jax.Array        # int32[N] bucket-table column
    gid: jax.Array         # int32[N] (rule, slot) group for the prefixes
    pv_hash: jax.Array     # uint32[N]
    stored_key: jax.Array  # uint32[N] the slot's owner
    fresh: jax.Array       # bool[N] value does not own its slot
    thr: jax.Array         # float32[N]
    is_thread: jax.Array   # bool[N]
    is_rl: jax.Array       # bool[N]
    windows: jax.Array     # int64[N] whole windows since the refill stamp
    avail: jax.Array       # float32[N] tokens the value may take
    gauge: jax.Array       # int32[N] thread gauge (0 when fresh)
    latest: jax.Array      # int64[N] leaky-bucket head after the idle clamp
    cost_us: jax.Array     # int64[N] per-token leaky-bucket cost
    max_queue_us: jax.Array  # int64[N]
    pos: jax.Array         # int32[N, D] sketch columns of the value
    at_min: jax.Array      # bool[N, D] admission-sketch cells at their min
    hot_min: jax.Array     # bool[N, D] promotion-sketch cells at their min
    hot_est: jax.Array     # float32[N] promotion estimate of the value
    owner_est: jax.Array   # float32[N] promotion estimate of the owner


def check_param_flow(
    rt: ParamRuleTensors,
    ps: ParamFlowState,
    batch: EntryBatch,
    now_ms: jax.Array,
    candidate: jax.Array,     # bool[N]
    extra_cms: Optional[jax.Array] = None,  # f32[PR, D, W] other devices' sketch
) -> ParamVerdict:
    """Vectorized ``ParamFlowChecker.passLocalCheck`` over the micro-batch.

    Survivor resolution follows check_flow's convention: uniform-count
    batches take the classic two passes (pass 1 with every candidate
    consuming bucket prefixes, pass 2 restricted to pass-1 survivors —
    exact, the serial-admitted set per value is then a prefix); MIXED
    acquire counts iterate the survivor set to fixpoint instead
    (ops/fixpoint.py — without it a mixed batch on one hot value
    over-admitted its bucket without bound, the same defect r5 found in
    the flow sweep: 32 tokens against a 9-token bucket). The final
    commit pass then evaluates + commits bucket state once.

    Everything a sweep reads that does not depend on the survivor set —
    rule vectors, bucket-table cells, sketch readings — is gathered once
    per step (``_read_slot``), so the sweeps and the commit only add the
    segmented prefix over the survivors.

    ``extra_cms`` (pod path): the psum of the OTHER devices' sketches.
    Sketch addition is the sketch of the union stream, so cluster-mode
    param rules admit every value against the POD-global window estimate —
    one-sided like the local sketch, with the same one-step staleness
    bound as cluster flow rules. Local-mode rules ignore it.
    """
    # Roll the sketch windows first so every pass sees one view (see
    # roll_sketch_windows; the pod wrapper also calls it BEFORE its psum so
    # the cross-device extra never carries a stale window).
    ps = roll_sketch_windows(rt, ps, now_ms)
    # Every slot reads before any slot commits. Exact: compile_param_rules
    # puts each rule at ONE (row, k), so the rule ids of slot k never occur
    # at another slot, and slot k's commit writes only rows of the state
    # that no other slot reads (lanes without a rule at k read row 0 but
    # their readings are masked or their writes dropped).
    reads = [_read_slot(rt, ps, batch, now_ms, candidate, k, extra_cms)
             for k in range(rt.slots)]

    def _blocked_for(survivors):
        blocked = jnp.zeros((batch.size,), bool)
        for rd in reads:
            ok, _ = _slot_ok(rd, batch, now_ms, survivors)
            blocked = blocked | (rd.applicable & (~ok))
        return blocked

    survivors = FX.survivor_fixpoint(candidate, _blocked_for, batch.count)
    return _commit(ps, reads, batch, now_ms, survivors)


def roll_sketch_windows(rt: ParamRuleTensors, ps: ParamFlowState,
                        now_ms: jax.Array) -> ParamFlowState:
    """Lazy per-rule sketch window roll. The ADMISSION sketch hard-resets
    each window (it estimates this-window usage only, so quotas refresh
    fully — one-sided); the PROMOTION sketch decays (halves per elapsed
    window) so a hot owner's history survives the boundary and the first
    cold request of a fresh window cannot steal its slot (a zeroed gate
    would be a no-op there). Idempotent within a window.
    """
    now64 = now_ms.astype(jnp.int64)
    dur = jnp.maximum(rt.duration_ms, 1)
    win_start = now64 - now64 % dur
    elapsed = jnp.clip((win_start - ps.cms_start) // dur, 0, 30)
    factor = jnp.exp2(-elapsed.astype(jnp.float32))
    # Active rules only: padded rows (duration 0 -> 1ms windows) "roll"
    # every step, but their sketches are identically zero — letting them
    # trigger the sweep would defeat the cond below.
    rolled = (elapsed > 0) & (rt.resource_row >= 0)

    def _sweep(ps_):
        return ps_._replace(
            cms=jnp.where(rolled[:, None, None], 0.0, ps_.cms),
            cms_hot=ps_.cms_hot * factor[:, None, None],
            cms_start=jnp.where(rolled, win_start, ps_.cms_start),
        )

    # The sweep reads+writes both [PR, D, W] sketches (tens of MB at
    # production rule counts) but changes anything only when some rule's
    # window actually rolled — a boundary crossing, ~1/sec/rule, not
    # 1/step. The cond makes the steady-state step skip it entirely
    # (measured ~5ms/step at PR=256 on the 2-core CPU bench host).
    return jax.lax.cond(jnp.any(rolled), _sweep, lambda p: p, ps)


def _read_slot(
    rt: ParamRuleTensors,
    ps: ParamFlowState,
    batch: EntryBatch,
    now_ms: jax.Array,
    candidate: jax.Array,
    k: int,
    extra_cms: Optional[jax.Array],
) -> _SlotRead:
    """Rule slot ``k``'s survivor-independent reads: the rule, the value's
    bucket cell, and the three sketch probes (admission sketch at the
    value's columns, promotion sketch at the value's and at the owner's)."""
    n = batch.size
    table_slots = ps.key.shape[1]
    rule_id = rt.rules_by_row.at[
        W.oob(batch.cluster_row, rt.rules_by_row.shape[0]), jnp.full((n,), k)
    ].get(mode="fill", fill_value=-1)
    has_rule = rule_id >= 0
    g = lambda a, fill=0: _gather1(a, rule_id, fill)

    pidx = g(rt.param_idx)
    pv_hash = jnp.take_along_axis(batch.param_hash, pidx[:, None], axis=1)[:, 0]
    pv_present = jnp.take_along_axis(batch.param_present, pidx[:, None], axis=1)[:, 0]
    applicable = has_rule & candidate & pv_present
    # Cluster-mode param rules already enforced remotely are skipped
    # (reference: ParamFlowChecker cluster branch replaces local check).
    applicable = applicable & ~(g(rt.remote_mode, False) & batch.skip_cluster)

    # Per-value exception items (exact hash match) override the rule count.
    items_h = rt.item_hash.at[W.oob(rule_id, rt.num_rules)].get(
        mode="fill", fill_value=0
    )  # [N, MAX_ITEMS]
    items_c = rt.item_count.at[W.oob(rule_id, rt.num_rules)].get(
        mode="fill", fill_value=0.0
    )
    item_match = (items_h == pv_hash[:, None]) & (items_h != 0)
    has_item = jnp.any(item_match, axis=1)
    item_thr = jnp.max(jnp.where(item_match, items_c, -1.0), axis=1)
    thr = jnp.where(has_item, item_thr, g(rt.threshold, 0.0))

    slot = (pv_hash % jnp.uint32(table_slots)).astype(jnp.int32)
    srule = jnp.where(applicable, rule_id, -1)
    stored_key = _gather2(ps.key, srule, slot, 0)
    fresh = (stored_key != pv_hash)  # empty or evicted -> full bucket

    grade = g(rt.grade)
    behavior = g(rt.behavior)
    dur_ms = g(rt.duration_ms, 1000).astype(jnp.int64)
    max_count = thr + g(rt.burst, 0.0)
    is_thread = grade == C.PARAM_FLOW_GRADE_THREAD
    is_rl = (~is_thread) & (behavior == C.CONTROL_BEHAVIOR_RATE_LIMITER)

    # Group identity for within-batch sequencing: same (rule, slot).
    gid = jnp.where(applicable, rule_id * table_slots + slot, -1)

    # --- QPS / DEFAULT: windowed token bucket (passDefaultLocalCheck)
    stored_tokens = _gather2(ps.tokens, srule, slot, 0.0)
    filled = _gather2(ps.filled_ms, srule, slot, 0)
    windows = jnp.maximum((now_ms.astype(jnp.int64) - filled) // jnp.maximum(dur_ms, 1), 0)
    refilled = jnp.minimum(
        stored_tokens + windows.astype(jnp.float32) * thr, max_count
    )
    # Cold tier: a key that does not own its slot admits against the
    # CMS estimate of its own window usage (one-sided: est >= truth,
    # so cold keys never over-admit). The hot owner keeps its exact
    # bucket.
    pos = _cms_positions(pv_hash)                    # [N, D]
    cms_vals = _cms_probe(ps.cms, srule, pos)
    est = _cms_min(cms_vals, srule)
    avail = jnp.where(fresh, jnp.maximum(max_count - est, 0.0), refilled)
    if extra_cms is not None:
        # Pod path: cluster-mode param rules admit EVERY value (owner
        # included) against the pod-global sketch — local + others'.
        est_global = _cms_min(
            cms_vals + _cms_probe(extra_cms, srule, pos), srule)
        cm = g(rt.cluster_mode, False)
        avail = jnp.where(cm, jnp.maximum(max_count - est_global, 0.0),
                          avail)
    # Promotion gate's two sides: BOTH probe the same promotion sketch
    # (see _commit).
    hot_vals = _cms_probe(ps.cms_hot, srule, pos)
    owner_vals = _cms_probe(ps.cms_hot, srule, _cms_positions(stored_key))

    # --- THREAD: concurrency gauge per value
    gauge = _gather2(ps.threads, srule, slot, 0)
    gauge = jnp.where(fresh, 0, gauge)

    # --- RATE_LIMITER (passThrottleLocalCheck): per-value leaky bucket,
    # cost = duration / threshold per token.
    cost_us = jnp.where(
        thr > 0,
        (dur_ms.astype(jnp.float32) * 1000.0 / jnp.maximum(thr, 1e-9)),
        jnp.float32(1e18),
    ).astype(jnp.int64)
    head0 = _gather2(ps.passed_us, srule, slot, 0)
    head0 = jnp.where(fresh, 0, head0)
    # Idle clamp scales with the acquire (whole multi-token acquire
    # free after idle, like the reference — see flow.py's RL note).
    now_us = now_ms.astype(jnp.int64) * 1000
    latest = jnp.maximum(head0,
                         now_us - cost_us * batch.count.astype(jnp.int64))

    return _SlotRead(
        srule=srule, applicable=applicable, slot=slot, gid=gid,
        pv_hash=pv_hash, stored_key=stored_key, fresh=fresh, thr=thr,
        is_thread=is_thread, is_rl=is_rl, windows=windows, avail=avail,
        gauge=gauge, latest=latest, cost_us=cost_us,
        max_queue_us=g(rt.max_queue_us, 0), pos=pos,
        # Conservative update: only cells at the current minimum grow.
        at_min=cms_vals <= cms_vals.min(axis=1, keepdims=True),
        hot_min=hot_vals <= hot_vals.min(axis=1, keepdims=True),
        hot_est=_cms_min(hot_vals, srule),
        owner_est=_cms_min(owner_vals, srule))


def _slot_ok(rd: _SlotRead, batch: EntryBatch, now_ms: jax.Array,
             survivors: jax.Array):
    """One sweep of one rule slot: the verdict of every lane given that
    ``survivors`` admit. Returns ``(ok, rl_wait)``."""
    acq = jnp.where(survivors & rd.applicable, batch.count, 0)
    pre2, _ = segmented_prefix_dense(
        rd.gid,
        jnp.stack([acq, jnp.where(survivors & rd.applicable, 1, 0)], axis=1).astype(jnp.float32),
    )
    tok_prefix, ent_prefix = pre2[:, 0], pre2[:, 1]

    acqf = batch.count.astype(jnp.float32)
    qps_ok = (rd.thr > 0) & (tok_prefix + acqf <= rd.avail)
    thread_ok = (rd.thr > 0) & (
        rd.gauge.astype(jnp.float32) + ent_prefix + 1.0 <= rd.thr
    )
    now_us = now_ms.astype(jnp.int64) * 1000
    expected = rd.latest + (tok_prefix + batch.count).astype(jnp.int64) * rd.cost_us
    rl_wait = jnp.maximum(expected - now_us, 0)
    rl_ok = (rd.thr > 0) & (rl_wait <= rd.max_queue_us)
    ok = jnp.where(rd.is_thread, thread_ok, jnp.where(rd.is_rl, rl_ok, qps_ok))
    return ok, rl_wait


def _commit(
    ps: ParamFlowState,
    reads: List[_SlotRead],
    batch: EntryBatch,
    now_ms: jax.Array,
    survivors: jax.Array,
) -> ParamVerdict:
    """Final sweep over the resolved survivors: verdicts, then each slot's
    bucket, gauge, head and sketch updates."""
    n = batch.size
    blocked = jnp.zeros((n,), bool)
    # First blocking rule slot per request (sequential chain's throw
    # site) for decision attribution; -1 while unblocked.
    first_slot = jnp.full((n,), -1, jnp.int32)
    wait_us = jnp.zeros((n,), jnp.int64)
    acqf = batch.count.astype(jnp.float32)
    pr = ps.key.shape[0]

    for k, rd in enumerate(reads):
        ok, rl_wait = _slot_ok(rd, batch, now_ms, survivors)
        applicable, srule, slot, fresh = rd.applicable, rd.srule, rd.slot, rd.fresh
        is_thread, is_rl = rd.is_thread, rd.is_rl
        slot_blocked = applicable & (~ok)
        first_slot = jnp.where(slot_blocked & (~blocked), k, first_slot)
        blocked = blocked | slot_blocked
        admitted = applicable & ok & survivors
        wait_us = jnp.maximum(wait_us, jnp.where(admitted & is_rl, rl_wait, 0))

        dflt = applicable & (~is_thread) & (~is_rl)
        # Promotion gate (space-saving top-k): an admitted cold key
        # takes the slot only when its window count has caught up with
        # the owner's — a cold-key storm can't evict a hot key's exact
        # bucket. Empty slots (key 0) are claimed directly. BOTH gate
        # sides probe the same promotion sketch: symmetric
        # collision inflation cancels in the comparison, which is what
        # keeps a 100k-key cold storm from out-scoring a hot owner
        # whose bar would otherwise stay at its small exact count.
        promoted = (admitted & dflt & fresh
                    & ((rd.stored_key == 0) | (rd.hot_est + acqf >= rd.owner_est)))
        # THREAD / RATE_LIMITER keep direct eviction (no windowed CMS
        # analog for gauges / leaky-bucket heads).
        claim_other = (admitted | (applicable & fresh)) & (is_thread | is_rl)
        claim = promoted | claim_other | (admitted & dflt & (~fresh))
        ridx = W.oob(jnp.where(claim, srule, -1), pr)
        ps = ps._replace(
            key=ps.key.at[ridx, slot].set(rd.pv_hash, mode="drop"),
        )
        need_stamp = dflt & (~fresh) & (rd.windows >= 1)
        stamp = need_stamp | promoted | (claim_other & fresh)

        # int64 scatter (emulated as hi/lo-u32 pairs on TPU — one of
        # the measured top-3 step costs); only window-boundary
        # crossings, promotions, and evictions stamp, so steady-state
        # batches skip it entirely via the cond.
        def _stamp_filled(filled_prev):
            tidx = W.oob(jnp.where(stamp, srule, -1), pr)
            return filled_prev.at[tidx, slot].set(
                now_ms.astype(jnp.int64), mode="drop")

        ps = ps._replace(filled_ms=jax.lax.cond(
            jnp.any(stamp), _stamp_filled, lambda f: f, ps.filled_ms))
        # Default-mode token accounting: owners (and freshly promoted
        # keys, seeded from the CMS-discounted level) get their bucket
        # set, then every admitted acquire is subtracted. Non-promoted
        # cold admits consume CMS only — they must not clobber the
        # owner's bucket.
        touch = dflt & ((~fresh) | promoted)
        didx = W.oob(jnp.where(touch, srule, -1), pr)
        tokens = ps.tokens.at[didx, slot].set(rd.avail, mode="drop")
        tokens = tokens.at[
            W.oob(jnp.where(admitted & touch, srule, -1), pr),
            slot,
        ].add(-acqf, mode="drop")
        ps = ps._replace(tokens=jnp.maximum(tokens, 0.0))
        # Every admitted default-grade acquire lands in the sketch (the
        # owner's too, keeping owner_est honest for promotion races).
        # Conservative update: only cells at the current minimum grow,
        # which tightens the one-sided over-estimate for colliding keys
        # (still never under-estimates).
        cidx = W.oob(jnp.where(admitted & dflt, srule, -1), pr)
        darange = jnp.arange(CMS_DEPTH)[None, :]
        inc = jnp.where((admitted & dflt)[:, None] & rd.at_min, acqf[:, None], 0.0)
        ps = ps._replace(cms=ps.cms.at[
            cidx[:, None], darange, rd.pos].add(inc, mode="drop"))
        hot_inc = jnp.where((admitted & dflt)[:, None] & rd.hot_min,
                            acqf[:, None], 0.0)
        ps = ps._replace(cms_hot=ps.cms_hot.at[
            cidx[:, None], darange, rd.pos].add(hot_inc, mode="drop"))
        # Throttle-mode head advance: head' = latest + consumed · cost.
        # Evicted slots first drop their stale head so .max starts fresh.
        # The whole block rides a lax.cond: the head table is int64
        # (epoch µs), whose scatter-set/-max lower to ~0.55ms/step of
        # emulated hi/lo-u32 scatter fusions on TPU EACH — measured as
        # the 3 hottest ops of the fused step — plus a dense-prefix
        # scan. With no rate-limiter param traffic in the batch (the
        # common case: QPS-reject param rules), every index is dropped
        # and the state provably unchanged, so the cond skips it all.
        def _advance_rl_heads(passed_prev):
            fresh_rl = W.oob(jnp.where(applicable & is_rl & fresh, srule, -1), pr)
            passed = passed_prev.at[fresh_rl, slot].set(0, mode="drop")
            rlidx = W.oob(jnp.where(admitted & is_rl, srule, -1), pr)
            consumed_after, _ = segmented_prefix_dense(
                rd.gid,
                jnp.where(admitted & is_rl, batch.count, 0)
                .astype(jnp.float32))
            last_total = consumed_after + jnp.where(
                admitted & is_rl, batch.count, 0)
            new_head = rd.latest + last_total.astype(jnp.int64) * rd.cost_us
            return passed.at[rlidx, slot].max(new_head, mode="drop")

        ps = ps._replace(passed_us=jax.lax.cond(
            jnp.any(applicable & is_rl), _advance_rl_heads,
            lambda p: p, ps.passed_us))

        # Thread gauge: reset evicted buckets, then increment admits.
        # Same skip for batches with no thread-grade param traffic.
        def _advance_threads(threads_prev):
            thidx = W.oob(jnp.where(applicable & fresh & is_thread, srule, -1), pr)
            threads = threads_prev.at[thidx, slot].set(0, mode="drop")
            return threads.at[
                W.oob(jnp.where(admitted & is_thread, srule, -1), pr), slot
            ].add(1, mode="drop")

        ps = ps._replace(threads=jax.lax.cond(
            jnp.any(applicable & is_thread), _advance_threads,
            lambda t: t, ps.threads))

    return ParamVerdict(blocked=blocked, wait_us=wait_us, state=ps,
                        slot=first_slot)


def feed_param_exit(
    rt: ParamRuleTensors,
    ps: ParamFlowState,
    batch: ExitBatch,
) -> ParamFlowState:
    """Decrement THREAD-grade gauges on completion (exit callback analog)."""
    n = batch.cluster_row.shape[0]
    table_slots = ps.key.shape[1]
    valid = batch.cluster_row >= 0

    for k in range(rt.slots):
        rule_id = rt.rules_by_row.at[
            W.oob(batch.cluster_row, rt.rules_by_row.shape[0]), jnp.full((n,), k)
        ].get(mode="fill", fill_value=-1)
        has_rule = rule_id >= 0
        grade = _gather1(rt.grade, rule_id, 0)
        pidx = _gather1(rt.param_idx, rule_id, 0)
        pv_hash = jnp.take_along_axis(batch.param_hash, pidx[:, None], axis=1)[:, 0]
        pv_present = jnp.take_along_axis(batch.param_present, pidx[:, None], axis=1)[:, 0]
        slot = (pv_hash % jnp.uint32(table_slots)).astype(jnp.int32)
        # Only decrement buckets this value still owns (evicted keys already
        # had their gauge reset).
        stored_key = _gather2(ps.key, jnp.where(has_rule, rule_id, -1), slot, 0)
        dec = (
            valid & has_rule & pv_present
            & (grade == C.PARAM_FLOW_GRADE_THREAD) & (stored_key == pv_hash)
        )
        # No THREAD-grade param traffic in this exit batch (the dominant
        # QPS-rules case) → the gauge is provably untouched; skip the
        # scatter (same no-traffic gating as the entry commit).
        def _dec_gauges(threads_prev):
            ridx = W.oob(jnp.where(dec, rule_id, -1), ps.key.shape[0])
            threads = threads_prev.at[ridx, slot].add(
                jnp.where(dec, -1, 0), mode="drop")
            return jnp.maximum(threads, 0)

        ps = ps._replace(threads=jax.lax.cond(
            jnp.any(dec), _dec_gauges, lambda t: t, ps.threads))
    return ps
