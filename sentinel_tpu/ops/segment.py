"""Within-batch segmented scans.

The reference admits each request against counters that every *earlier*
request has already updated (per-request exactness of ``DefaultController``
/ the token bucket CASes). A micro-batched device step sees N requests at
once, so to reproduce arrival-order semantics we compute, for every request,
the sum of candidate counts of earlier requests that target the same node
row / rule — a segmented exclusive prefix sum in arrival order.

Two implementations:

``segmented_prefix``       — stable sort + cumsum + cummax. O(N log N) and
                             exact for any integer magnitudes; the right
                             shape for host-side (CPU) callers such as the
                             cluster token server's micro-batcher.

``segmented_prefix_dense`` — the TPU-native path. On TPU, sorts lower to
                             bitonic networks and cumulative ops lower to
                             ``reduce-window``, which is both slow and blew
                             scoped VMEM inside the fused ``lax.scan`` step
                             (BENCH_r01: "scoped allocation 19.09M > 16.00M
                             limit"). Instead we compute the prefix as a
                             *blocked triangular masked matmul*: for a row
                             block I, ``prefix[i] = Σ_j  eq(id_i, id_j) ·
                             earlier(j, i) · v[j]`` — an [B, N] @ [N, M]
                             product that runs on the MXU with the mask
                             generated on the VPU. Total work is O(N²·M)
                             FLOPs, which for micro-batches (N ≤ 8192) is
                             microseconds of MXU time and, critically, has
                             a static, fusion-friendly memory footprint of
                             O(B·N) per scan block. Multiple value columns
                             (M) share one mask evaluation — flow needs
                             token + entry prefixes over the same rows.

Exactness: the mask is {0,1} and values are cast to bfloat16 with float32
accumulation, so results are exact for per-request counts ≤ 256 (bf16
integer range) — counts are 1 in every reference code path (`SphU.entry`
acquires batch=1; larger acquireCount stays far below 256).

Measured dead end (r4, real v5e chip): a two-level "bounded" variant —
per-block bincounts + cross-block cumsum + block-local triangular mask,
O(N·block) mask work instead of O(N²) — benched 0.60ms vs 0.53ms for
the dense form at N=8192/block=512 inside a 16-step scan: the per-block
bincount scan overhead eats the mask savings at these sizes. Don't
re-derive it below N≈32k.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.lax
import jax.numpy as jnp

_ID_SENTINEL = jnp.int32(-(2**31))


def prep_prefix_pair(ids: jnp.ndarray, values: jnp.ndarray, npad: int):
    """Shared prep for the dense-prefix implementations (XLA scan and the
    Pallas kernel): squeeze 1-D values, pad ids with the sentinel (padded
    rows match only each other and carry zero values), and append the
    ones column whose prefix is the earlier-same-id count that yields
    ``is_first`` for free. Returns ``(squeeze, m, ids_p, vals_p)`` with
    ``vals_p`` float32 [npad, m+1].
    """
    n = ids.shape[0]
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    m = values.shape[1]
    ids_p = jnp.pad(ids.astype(jnp.int32), (0, npad - n),
                    constant_values=_ID_SENTINEL)
    vals_p = jnp.pad(
        jnp.concatenate(
            [values.astype(jnp.float32), jnp.ones((n, 1), jnp.float32)],
            axis=1),
        ((0, npad - n), (0, 0)),
    )
    return squeeze, m, ids_p, vals_p


def segmented_prefix(ids: jnp.ndarray, values: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exclusive prefix sum of ``values`` within equal ``ids``, arrival order.

    Returns (prefix_excl, is_first) both aligned with the input order.
    ``is_first`` marks the first occurrence of each id (used e.g. to admit a
    single HALF_OPEN probe per breaker per batch).

    Sort-based host/CPU path; see ``segmented_prefix_dense`` for the device
    hot path.
    """
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sid = ids[order]
    sval = values[order]
    csum = jnp.cumsum(sval)
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    # Exclusive prefix at each segment head; propagate forward with a
    # running max (csum is nondecreasing for nonnegative values).
    head_base = jnp.where(first, csum - sval, -1)
    base = jax.lax.cummax(head_base)
    prefix_sorted = csum - sval - base
    inv = jnp.zeros((n,), order.dtype).at[order].set(jnp.arange(n, dtype=order.dtype))
    return prefix_sorted[inv], first[inv]


def segmented_prefix_dense(
    ids: jnp.ndarray,
    values: jnp.ndarray,
    block: int = 512,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked-matmul segmented exclusive prefix (the MXU path).

    ``ids``: int32[N] segment ids (< 0 entries form their own shared segment
    but their values are expected to be 0 by callers, so they contribute
    nothing). ``values``: [N] or [N, M] — M value columns computed against
    one shared mask. Returns ``(prefix, is_first)`` with ``prefix`` shaped
    like ``values`` (float32) and ``is_first`` bool[N].
    """
    (prefix, is_first), = segmented_prefix_dense_multi([(ids, values)],
                                                       block=block)
    return prefix, is_first


def _read_pallas_flag() -> bool:
    import os

    return os.environ.get("SENTINEL_TPU_PALLAS", "").lower() in (
        "1", "true", "yes", "on")


# Captured ONCE at import: jit caches traces, and a trace bakes in the
# routing decision — re-reading the env var per trace would let one
# process mix both prefix implementations across already-compiled vs
# freshly-traced batch widths (r4 advisory). Set SENTINEL_TPU_PALLAS
# before importing sentinel_tpu; later changes are intentionally inert.
_PALLAS_OPTED_IN = _read_pallas_flag()


def _read_force_dense_flag() -> bool:
    import os

    return os.environ.get("SENTINEL_TPU_FORCE_DENSE", "").lower() in (
        "1", "true", "yes", "on")


# Same capture-at-import discipline as the Pallas flag above.
_FORCE_DENSE = _read_force_dense_flag()


def _use_cpu_exact() -> bool:
    """Route prefix/bincount work through the sort/scatter forms on the
    CPU backend (trace-time decision, like ``_use_pallas``).

    The dense masked-matmul forms exist because TPU sorts lower to
    bitonic networks and TPU scatters serialize — neither is true on
    CPU, where the O(N²) mask materialization is the pathology instead:
    the 3-space flow prefix at N=8192 measured ~1.2 s/step on the CPU
    backend vs ~2 ms for stable-sort + cumsum, and the one-hot bincount
    ~0.4 s vs microseconds for a scatter-add. Tier-1 tests and the CPU
    bench path take this exact-integer route; real devices keep the MXU
    forms. ``SENTINEL_TPU_FORCE_DENSE=1`` (at import) pins the dense
    forms on CPU — used by the kernel-exactness tests.
    """
    if _FORCE_DENSE:
        return False
    try:
        return jax.default_backend() == "cpu"
    except Exception:  # pragma: no cover — uninitialized backend
        return False


def _sorted_prefix_multi(ids: jnp.ndarray, values: jnp.ndarray):
    """Multi-column twin of :func:`segmented_prefix` (sort + cumsum +
    cummax): exclusive per-segment prefix of ``values`` [N, M] in arrival
    order, plus ``is_first``. Exact for nonnegative integer values with
    segment sums < 2^24 (f32 cumsum) — the same bound the dense form
    carries."""
    n = ids.shape[0]
    order = jnp.argsort(ids, stable=True)
    sid = ids[order]
    sval = values[order].astype(jnp.float32)          # [N, M]
    csum = jnp.cumsum(sval, axis=0)
    first = jnp.concatenate([jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    # Exclusive prefix at each segment head; propagate with a running max
    # (csum is nondecreasing per column for nonnegative values).
    head_base = jnp.where(first[:, None], csum - sval, -1.0)
    base = jax.lax.cummax(head_base, axis=0)
    prefix_sorted = csum - sval - base
    inv = jnp.zeros((n,), order.dtype).at[order].set(
        jnp.arange(n, dtype=order.dtype))
    return prefix_sorted[inv], first[inv]


def _use_pallas() -> bool:
    """Opt-in routing of the dense prefix through the Pallas kernel
    (``SENTINEL_TPU_PALLAS=1`` at import time, on a real TPU). Standalone
    the kernel was once timed at 1.71x the XLA scan (ops/pallas_prefix.py),
    but embedded in the donated 16-step fused-step scan it crashed an
    earlier backend with a runtime panic — so the XLA path stays the
    default until the in-step embedding is proven on the chip (ROADMAP
    A2). The kernel is correctness-tested in interpret mode on CPU
    (test_pallas_prefix.py) and compiled for a described v5e
    (test_tpu_compile.py)."""
    if not _PALLAS_OPTED_IN:
        return False
    try:
        return jax.default_backend() == "tpu"
    except Exception:  # pragma: no cover — uninitialized backend
        return False


def segmented_prefix_dense_multi(pairs, block: int = 512):
    """K independent dense segmented prefixes fused into ONE scan loop.

    ``pairs``: list of ``(ids, values)`` as in ``segmented_prefix_dense``,
    all with the same leading length N. Every separate prefix call is its
    own ``lax.scan`` over mask/matmul blocks, and XLA does not CSE across
    scans — so callers that need several segmentations of the SAME batch
    (the flow sweep's cluster/dn/origin row spaces) fuse them here: one
    loop, K masks + K matmuls per block, one pass over the batch's VMEM
    working set. Returns a list of ``(prefix, is_first)``.

    With ``SENTINEL_TPU_PALLAS=1`` on a real TPU the work routes through
    the Pallas kernel instead (same contract, measured 1.71x standalone;
    opt-in pending an in-step backend-panic fix — see ``_use_pallas``).
    """
    n = pairs[0][0].shape[0]
    for ids_k, values_k in pairs:
        if ids_k.shape[0] != n or values_k.shape[0] != n:
            raise ValueError(
                "segmented_prefix_dense_multi: all pairs must share the "
                f"same leading length (got {ids_k.shape[0]} / "
                f"{values_k.shape[0]}, expected {n})")
    if n == 0:
        # Zero-width batches (empty pipeline flushes) must trace: the
        # blocked scan below still traces its body once, and indexing a
        # (0, block) array raises. Outputs derived from the inputs (not
        # literal zeros) keep shard_map varying-axes typing.
        out0 = []
        for ids, values in pairs:
            squeeze = values.ndim == 1
            v = values if not squeeze else values[:, None]
            p = v.astype(jnp.float32) * 0
            out0.append((p[:, 0] if squeeze else p, ids < jnp.int32(0)))
        return out0
    if _use_pallas():
        from sentinel_tpu.ops.pallas_prefix import prefix_pallas_multi

        return prefix_pallas_multi(pairs)
    if _use_cpu_exact():
        out = []
        for ids, values in pairs:
            squeeze = values.ndim == 1
            v = values[:, None] if squeeze else values
            prefix, is_first = _sorted_prefix_multi(ids, v)
            out.append((prefix[:, 0] if squeeze else prefix, is_first))
        return out
    nb = -(-n // block)
    npad = nb * block
    pos = jnp.arange(npad, dtype=jnp.int32)
    off = jnp.arange(block, dtype=jnp.int32)

    prepped = []
    for ids, values in pairs:
        squeeze, m, ids_p, vals_p = prep_prefix_pair(ids, values, npad)
        v16 = vals_p.astype(jnp.bfloat16)  # exact for integer counts ≤ 256
        prepped.append((squeeze, m, ids_p, ids_p.reshape(nb, block), v16))

    def body(_, b):
        my_pos = b * block + off                           # [B]
        outs = []
        for _sq, _m, ids_p, idsb, v16 in prepped:
            my_ids = idsb[b]                               # [B]
            mask = (my_ids[:, None] == ids_p[None, :]) & (
                pos[None, :] < my_pos[:, None])
            outs.append(jax.lax.dot_general(
                mask.astype(jnp.bfloat16), v16,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))                                             # [B, M_k+1]
        return _, tuple(outs)

    _, outs_all = jax.lax.scan(body, None, jnp.arange(nb, dtype=jnp.int32))
    results = []
    for (squeeze, m, _ids_p, _idsb, _v16), outs in zip(prepped, outs_all):
        outs = outs.reshape(npad, m + 1)[:n]
        prefix, earlier_count = outs[:, :m], outs[:, m]
        is_first = earlier_count == 0
        if squeeze:
            prefix = prefix[:, 0]
        results.append((prefix, is_first))
    return results


def bincount_matmul(
    ids: jnp.ndarray,
    values: jnp.ndarray,
    num_bins: int,
    lo: int = 128,
) -> jnp.ndarray:
    """Weighted bincount as a two-level one-hot outer product (MXU path).

    ``Σ_n values[n] into bin ids[n]`` without a scatter: decompose
    ``id = hi·lo + lo_part`` and compute ``out[hi, lo] = Aᵀ @ B`` with
    ``A[n, hi] = onehot_hi[n, hi]·v[n]`` and ``B[n, lo] = onehot_lo``. TPU
    scatters serialize (~7ns/update — measured 0.4ms for a 64k-update
    commit); this form is two [N, 128]-ish bf16 matmul operands and a tiny
    MXU contraction instead.

    ``ids``: int32[N], negative or >= num_bins dropped. ``values``: [N] or
    [N, M] — M columns share the one-hot operands. Returns float32
    [num_bins] or [M, num_bins]. Exact for integer |values| ≤ 256 (bf16);
    callers with wider integers split them into byte limbs.
    """
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    n, m = values.shape
    if _use_cpu_exact():
        # CPU scatter-add: exact f32 integer accumulation, no one-hot
        # materialization (see _use_cpu_exact for the measured gap).
        valid = (ids >= 0) & (ids < num_bins)
        idc = jnp.where(valid, ids, num_bins)  # spill bucket, sliced off
        v = jnp.where(valid[:, None], values.astype(jnp.float32), 0.0)
        out = jnp.zeros((num_bins + 1, m), jnp.float32).at[idc].add(v)
        out = out[:num_bins].T
        return out[0] if squeeze else out
    nb_hi = -(-num_bins // lo)
    valid = (ids >= 0) & (ids < num_bins)
    idc = jnp.where(valid, ids, 0)
    v = jnp.where(valid[:, None], values, 0).astype(jnp.bfloat16)  # [N, M]
    hi_id = idc // lo
    lo_id = idc % lo
    onehot_hi = (hi_id[:, None] == jnp.arange(nb_hi, dtype=jnp.int32)[None, :])
    onehot_lo = (lo_id[:, None] == jnp.arange(lo, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    # A: [N, M·nb_hi] — per-column weighted hi one-hots, stacked.
    a = (onehot_hi[:, None, :] & valid[:, None, None]).astype(jnp.bfloat16) * v[:, :, None]
    a = a.reshape(n, m * nb_hi)
    out = jax.lax.dot_general(
        a, onehot_lo, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [M·nb_hi, lo]
    out = out.reshape(m, nb_hi * lo)[:, :num_bins]
    return out[0] if squeeze else out


def first_in_segment(ids: jnp.ndarray, num_segments: int) -> jnp.ndarray:
    """bool[N]: is this the first occurrence of its (non-negative) id?

    Negative ids always return False. O(N) via a scatter-min of positions —
    far cheaper than a full prefix when only first-arrival matters (e.g. one
    HALF_OPEN probe per breaker per batch).
    """
    n = ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    oob = jnp.where((ids >= 0) & (ids < num_segments), ids, num_segments)
    first_pos = jnp.full((num_segments,), n, jnp.int32).at[oob].min(pos, mode="drop")
    return first_pos.at[oob].get(mode="fill", fill_value=-1) == pos
