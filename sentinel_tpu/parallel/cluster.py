"""Pod-as-one-rate-limiter: mesh-parallel admission over ICI.

Reference architecture being replaced (SURVEY.md §2.4, §2.11, §3.3): the
``sentinel-cluster`` token server — a Netty TCP server owning the global
sliding window, with every client paying one RTT per ``requestToken`` and
degrading to local checks on failure (``FlowRuleChecker.passClusterCheck`` /
``fallbackToLocalOrPass``).

TPU-native design: there is no server process. Each device in the mesh holds
a full-capacity replica of the stats tensors carrying *its own* admitted
traffic (the reference's "every JVM holds its own full stats" replication,
§2.10), and the request stream is sharded over the device axis. Cluster-mode
flow rules admit against the POD-GLOBAL window: a ``psum`` over the mesh
axis folds every device's pass counts into one view, so the whole pod acts
as a single token server with zero RTTs — the collective rides ICI inside
one XLA program.

Exactness: within one micro-step a device sees other devices' counts as of
the step start, so overshoot is bounded by (devices − 1) × max per-device
batch admission for one rule — the quantified semantics delta of SURVEY.md
§7 (hard part #5). The reference's own cluster mode has an analogous window
(client-side batching + RTT staleness).

Multi-host pods work unchanged: ``jax.make_mesh`` over all devices spans
hosts, and XLA routes the same ``psum`` over ICI within a slice and DCN
across slices.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.core import constants as C
from sentinel_tpu.core.batch import Decisions, EntryBatch, ExitBatch
from sentinel_tpu.ops import step as S
from sentinel_tpu.ops import window as W

from jax import shard_map as _shard_map

from jax.sharding import Mesh, PartitionSpec as P

AXIS = "pod"


def _squeeze0(tree):
    return jax.tree.map(lambda x: jnp.squeeze(x, 0), tree)


def _expand0(tree):
    return jax.tree.map(lambda x: x[None], tree)


def make_pod_state(n_devices: int, one: S.SentinelState) -> S.SentinelState:
    """Per-device replicated-structure state: leaves shaped [D, ...].

    ``one`` is a freshly built single-device state whose geometry matches
    the rule pack (same capacity / rule counts on every device).
    """
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_devices,) + x.shape), one
    )


def global_pass_counts(w1: W.Window, axis: str) -> Tuple[jax.Array, jax.Array]:
    """(extra_pass[R], local_pass[R]): other-device / own pass totals."""
    local = W.all_totals(w1)[:, C.MetricEvent.PASS]
    total = jax.lax.psum(local, axis)
    return total - local, local


def global_next_window(w1: W.Window, occupied_next: jax.Array, now_ms: jax.Array,
                       axis: str) -> jax.Array:
    """extra_next[R]: other devices' NEXT-window usage (occupy borrows).

    A device's next-window usage is its window pass minus the bucket about
    to expire, plus its pending borrows. psum'd so prioritized occupy
    grants admit against the pod-global next window, not just the local
    slice (otherwise every device would lend up to the global threshold).
    """
    spec = S.SPEC_1S
    oldest_idx = jnp.mod(W.current_index(now_ms, spec) + 1, spec.buckets)
    oldest = w1.counts[oldest_idx, C.MetricEvent.PASS, :]
    local = (W.all_totals(w1)[:, C.MetricEvent.PASS] - oldest
             + occupied_next)
    return jax.lax.psum(local, axis) - local


def _pod_entry(state: S.SentinelState, rules: S.RulePack, batch: EntryBatch,
               now_ms: jax.Array, *, axis: str, cluster_param: bool,
               extra_checkers: tuple = (),
               occupy_timeout_ms: int = C.DEFAULT_OCCUPY_TIMEOUT_MS,
               shadow_rules=None, canary_bps=None, canary_salt=None,
               ) -> Tuple[S.SentinelState, Decisions]:
    local = _squeeze0(state)
    now_ms = jnp.asarray(now_ms, jnp.int64)
    w1 = W.rotate(local.w1, now_ms, S.SPEC_1S)
    extra_pass, _ = global_pass_counts(w1, axis)
    extra_next = global_next_window(w1, local.occupied_next, now_ms, axis)
    extra_cms = None
    if cluster_param:
        # Cluster-mode param rules admit against the pod-global sketch.
        # Roll the local sketch windows BEFORE the psum: every device
        # rolls at the same per-rule boundary, so the cross-device extra
        # never carries a stale window (which would zero the first step
        # of each fresh window).
        from sentinel_tpu.models import param_flow as PF

        local = local._replace(param=PF.roll_sketch_windows(
            rules.param, local.param, now_ms))
        extra_cms = jax.lax.psum(local.param.cms, axis) - local.param.cms
    shadow_extra_pass = None
    shadow_extra_cms = None
    if shadow_rules is not None and local.shadow is not None:
        # Shadow counters ride the same psum: the candidate's cluster-mode
        # rules admit against the POD-GLOBAL shadow window (other devices'
        # candidate-passed counts), so shadow-vs-live deltas are pod-exact
        # rather than per-slice. Rotate before the psum, same discipline
        # as the live window above.
        sh_w1 = W.rotate(local.shadow.w1, now_ms, S.SPEC_1S)
        shadow_extra_pass, _ = global_pass_counts(sh_w1, axis)
        local = local._replace(shadow=local.shadow._replace(w1=sh_w1))
        if cluster_param:
            sh_param = PF.roll_sketch_windows(
                shadow_rules.param, local.shadow.param, now_ms)
            local = local._replace(
                shadow=local.shadow._replace(param=sh_param))
            shadow_extra_cms = (jax.lax.psum(sh_param.cms, axis)
                                - sh_param.cms)
    # Hand the rotated window through so entry_step's own rotate hits the
    # cheap restamp branch instead of re-sweeping the counts tensor.
    new_local, dec = S.entry_step(local._replace(w1=w1), rules, batch, now_ms,
                                  extra_pass=extra_pass, extra_next=extra_next,
                                  extra_cms=extra_cms,
                                  extra_checkers=extra_checkers,
                                  occupy_timeout_ms=occupy_timeout_ms,
                                  shadow_rules=shadow_rules,
                                  canary_bps=canary_bps,
                                  canary_salt=canary_salt,
                                  shadow_extra_pass=shadow_extra_pass,
                                  shadow_extra_cms=shadow_extra_cms)
    return _expand0(new_local), dec


def _pod_exit(state: S.SentinelState, rules: S.RulePack, batch: ExitBatch,
              now_ms: jax.Array, *, axis: str,
              shadow_rules=None) -> S.SentinelState:
    del axis
    return _expand0(S.exit_step(_squeeze0(state), rules, batch, now_ms,
                                shadow_rules=shadow_rules))


def global_shadow_counts(state: S.SentinelState) -> Optional[jax.Array]:
    """Pod-global rollout counters from a [D, ...] pod state: the shadow
    counter tensor summed over the device axis (host-side read — every
    device accumulated only its own shard's lanes)."""
    if state.shadow is None:
        return None
    return jnp.sum(state.shadow.counts, axis=0)


def global_telemetry_counts(state: S.SentinelState) -> S.TelemetryState:
    """Pod-global decision attribution / RT histograms / totals from a
    [D, ...] pod state: each device's step accumulated only its own
    shard's lanes (the telemetry columns ride each device's local
    bincount), so the pod view is the device-axis sum — the same
    reduction the in-step psum applies to the shared window, applied at
    read time because cumulative counters are only read host-side
    (keeping every device's steady-state step free of an extra
    collective). The live staged second is folded in
    (``S.telemetry_view``), so the read is exact at any instant."""
    return jax.tree.map(lambda x: jnp.sum(x, axis=0),
                        S.telemetry_view(state))


def global_flight_recorder(state: S.SentinelState) -> Optional[S.FlightRecorder]:
    """Pod-global flight recorder from a [D, ...] pod state: per-slot
    stamps are clock-derived and identical on every device, so the
    global per-second deltas are the device-axis sum of the ring tensors
    (same read-time reduction as :func:`global_telemetry_counts`).
    None when recording is disabled."""
    fl = state.flight
    if fl is None:
        return None
    return S.FlightRecorder(
        stamps=fl.stamps[0],
        events=jnp.sum(fl.events, axis=0),
        attr=jnp.sum(fl.attr, axis=0),
        hist=jnp.sum(fl.hist, axis=0),
        slot_attr=jnp.sum(fl.slot_attr, axis=0),
    )


def make_pod_steps(mesh: Mesh, axis: str = AXIS, cluster_param: bool = True,
                   occupy_timeout_ms: int = C.DEFAULT_OCCUPY_TIMEOUT_MS,
                   shadow_rules=None, canary_bps=None, canary_salt=None):
    """Build (entry_step, exit_step) shard_mapped over ``mesh[axis]``.

    State leaves carry a leading device axis (sharded); batches are sharded
    over the request axis; rules and ``now_ms`` are replicated. The returned
    functions are jittable; callers wrap them in ``jax.jit`` with state
    donation.

    ``cluster_param=False`` drops the param-sketch all-reduce (a
    [PR, 4, 2048] f32 psum per step) for deployments with no cluster-mode
    param rules — a static choice, like rule compilation itself.
    ``occupy_timeout_ms`` is likewise build-static here (pod callers own
    their jit lifecycle); the single-engine paths take it as a traced
    runtime knob.

    SPI device checkers (core/spi.py) registered at BUILD time are spliced
    into the pod step like the single-device engine's; later registrations
    need a fresh ``make_pod_steps`` (pod callers own their jit lifecycle —
    watch ``spi.device_version()`` the way the engine does).

    ``shadow_rules`` / ``canary_bps`` / ``canary_salt`` stage a candidate
    ruleset pod-wide (sentinel_tpu/rollout/), build-static like the SPI
    splice: the pod state must carry a matching shadow world
    (``S.make_shadow_state`` broadcast by ``make_pod_state``), and the
    candidate's cluster-mode rules admit against the psum'd shadow
    window, so would-verdicts are pod-global like live verdicts.
    """
    from sentinel_tpu.core import spi as _spi

    entry = _shard_map(
        functools.partial(_pod_entry, axis=axis, cluster_param=cluster_param,
                          extra_checkers=_spi.device_checkers(),
                          occupy_timeout_ms=occupy_timeout_ms,
                          shadow_rules=shadow_rules, canary_bps=canary_bps,
                          canary_salt=canary_salt),
        mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P()),
        out_specs=(P(axis), P(axis)),
        # The r5 survivor-fixpoint (ops/fixpoint.py) is a lax.while_loop;
        # shard_map's varying-manual-axes checker has no rule for it
        # (mixed-acquire batches crashed with "No replication rule for
        # while"), so the static check is off. Collective correctness
        # is unaffected — psums are explicit in the step body.
        check_vma=False,
    )
    exit_ = _shard_map(
        functools.partial(_pod_exit, axis=axis, shadow_rules=shadow_rules),
        mesh=mesh,
        in_specs=(P(axis), P(), P(axis), P()),
        out_specs=P(axis),
        check_vma=False,
    )
    return entry, exit_
