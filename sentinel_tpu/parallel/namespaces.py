"""Cross-pod namespace sharding over a (dcn, ici) device mesh.

Reference architecture being replaced (SURVEY.md §2.10 "Namespace
sharding"): the token server groups clients/rules/limits by namespace
(``cluster-server:connection/ConnectionGroup.java`` +
``ClusterServerConfigManager``'s namespace set) — one server process owns
each namespace's global windows.

TPU-native design, two layers:

* **Device layer** — :func:`make_dcn_pod_steps` shard_maps the admission
  step over a 2-axis mesh ``("dcn", "ici")``: one ``ici`` row per pod
  (slice), the ``dcn`` axis spanning pods. Cluster rules choose their
  reduction scope per rule: default pod scope psums over ``ici`` only
  (each slice enforces its own quota — a sharded namespace), while
  ``cluster_config={"scope": "global"}`` rules psum over BOTH axes, so
  one quota spans every pod. On real hardware XLA routes the inner
  reduction over ICI and the outer one over DCN — exactly the
  "collectives ride ICI, cross-pod goes DCN" recipe; the virtual CPU
  mesh proves the same program shape.
* **Host layer** — :class:`NamespaceShardMap` assigns namespaces to pod
  slices (explicit pins or stable hashing) so host frontends (TCP token
  server, RLS, engines' cluster clients) route each namespace's acquire
  stream to the slice that owns its windows; reassignment on slice loss
  is a map update, mirroring the reference's ops-driven server flips.
"""

from __future__ import annotations

import functools
import hashlib
import threading
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.ops import step as S
from sentinel_tpu.ops import window as W
from sentinel_tpu.parallel.cluster import (
    _shard_map,
    global_next_window,
    global_pass_counts,
)

from jax.sharding import Mesh, PartitionSpec as P

DCN_AXIS = "dcn"
ICI_AXIS = "ici"


# ---------------------------------------------------------------------------
# Host layer: namespace -> pod-slice routing
# ---------------------------------------------------------------------------


class NamespaceShardMap:
    """namespace -> slice assignment (ConnectionGroup analog, host side)."""

    def __init__(self, n_slices: int):
        if n_slices <= 0:
            raise ValueError("need at least one slice")
        self.n_slices = n_slices
        self._lock = threading.Lock()
        self._pins: Dict[str, int] = {}
        self._down: set = set()

    def _hash_slice(self, namespace: str) -> int:
        digest = hashlib.sha1(namespace.encode("utf-8")).digest()
        return int.from_bytes(digest[:4], "big") % self.n_slices

    def slice_of(self, namespace: str) -> int:
        """Owning slice: explicit pin wins, else stable hash; a down slice
        fails over deterministically to the next live one."""
        with self._lock:
            s = self._pins.get(namespace, self._hash_slice(namespace))
            if s not in self._down:
                return s
            for step in range(1, self.n_slices):
                cand = (s + step) % self.n_slices
                if cand not in self._down:
                    return cand
            raise RuntimeError("all slices down")

    def pin(self, namespace: str, slice_id: int) -> None:
        if not (0 <= slice_id < self.n_slices):
            raise ValueError(f"slice {slice_id} out of range")
        with self._lock:
            self._pins[namespace] = slice_id

    def mark_down(self, slice_id: int) -> None:
        with self._lock:
            self._down.add(slice_id)

    def mark_up(self, slice_id: int) -> None:
        with self._lock:
            self._down.discard(slice_id)

    def assignments(self, namespaces: List[str]) -> Dict[str, int]:
        return {ns: self.slice_of(ns) for ns in namespaces}


# ---------------------------------------------------------------------------
# Device layer: 2-axis pod steps
# ---------------------------------------------------------------------------


def _squeeze2(tree):
    return jax.tree.map(lambda x: jnp.squeeze(jnp.squeeze(x, 0), 0), tree)


def _expand2(tree):
    return jax.tree.map(lambda x: x[None, None], tree)


def _dcn_entry(state, rules, batch, now_ms, *, cluster_param: bool,
               global_scope: bool, extra_checkers: tuple):
    # Inside shard_map each leaf carries leading [1, 1] (dcn, ici) axes.
    local = _squeeze2(state)
    now_ms = jnp.asarray(now_ms, jnp.int64)
    w1 = W.rotate(local.w1, now_ms, S.SPEC_1S)

    # Pod scope reduces over ICI only; global scope over both axes (psum
    # takes the axis tuple — same helpers as the 1-axis pod path, so the
    # window/borrow geometry cannot diverge between the two).
    extra_pass, _ = global_pass_counts(w1, ICI_AXIS)
    extra_next = global_next_window(w1, local.occupied_next, now_ms, ICI_AXIS)
    extra_pass_global = extra_next_global = None
    if global_scope:
        extra_pass_global, _ = global_pass_counts(w1, (DCN_AXIS, ICI_AXIS))
        extra_next_global = global_next_window(
            w1, local.occupied_next, now_ms, (DCN_AXIS, ICI_AXIS))

    extra_cms = None
    if cluster_param:
        from sentinel_tpu.models import param_flow as PF

        local = local._replace(param=PF.roll_sketch_windows(
            rules.param, local.param, now_ms))
        # Param sketches reduce pod-wide; global-scope param rules would
        # psum over DCN too — kept pod-scope until a rule asks for it.
        extra_cms = jax.lax.psum(local.param.cms, ICI_AXIS) - local.param.cms

    new_local, dec = S.entry_step(
        local._replace(w1=w1), rules, batch, now_ms,
        extra_pass=extra_pass, extra_next=extra_next, extra_cms=extra_cms,
        extra_checkers=extra_checkers,
        extra_pass_global=extra_pass_global,
        extra_next_global=extra_next_global)
    return _expand2(new_local), dec


def _dcn_exit(state, rules, batch, now_ms):
    return _expand2(S.exit_step(_squeeze2(state), rules, batch, now_ms))


def make_dcn_mesh(n_slices: int, per_slice: int,
                  devices: Optional[list] = None) -> Mesh:
    """(dcn, ici) mesh from the first n_slices*per_slice devices."""
    import numpy as np

    devices = devices if devices is not None else jax.devices()
    need = n_slices * per_slice
    if len(devices) < need:
        raise ValueError(f"need {need} devices, have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_slices, per_slice)
    return Mesh(grid, (DCN_AXIS, ICI_AXIS))


def make_dcn_pod_steps(mesh: Mesh, cluster_param: bool = True,
                       global_scope: bool = True):
    """(entry_step, exit_step) shard_mapped over a (dcn, ici) mesh.

    State leaves carry leading [n_slices, per_slice] axes
    (see :func:`make_dcn_pod_state`); batches shard over both axes
    flattened (request i goes to device i // per_dev — the host router
    places each namespace's requests on its owning slice's rows).

    ``global_scope=False`` drops the DCN-axis all-reduces (the slow
    inter-slice hop) for deployments whose cluster rules are all
    pod-scope — a static choice like ``cluster_param``.
    """
    from sentinel_tpu.core import spi as _spi

    entry = _shard_map(
        functools.partial(_dcn_entry, cluster_param=cluster_param,
                          global_scope=global_scope,
                          extra_checkers=_spi.device_checkers()),
        mesh=mesh,
        in_specs=(P(DCN_AXIS, ICI_AXIS), P(), P((DCN_AXIS, ICI_AXIS)), P()),
        out_specs=(P(DCN_AXIS, ICI_AXIS), P((DCN_AXIS, ICI_AXIS))),
        # No shard_map replication rule for the fixpoint while_loop —
        # see make_pod_steps (parallel/cluster.py) for the rationale.
        check_vma=False,
    )
    exit_ = _shard_map(
        _dcn_exit,
        mesh=mesh,
        in_specs=(P(DCN_AXIS, ICI_AXIS), P(), P((DCN_AXIS, ICI_AXIS)), P()),
        out_specs=P(DCN_AXIS, ICI_AXIS),
        check_vma=False,
    )
    return entry, exit_


def make_dcn_pod_state(n_slices: int, per_slice: int,
                       one: S.SentinelState) -> S.SentinelState:
    """Replicated-structure state with leading [n_slices, per_slice]."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None, None],
                                   (n_slices, per_slice) + x.shape), one)
