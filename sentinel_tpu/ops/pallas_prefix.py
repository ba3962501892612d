"""Pallas TPU kernel for the dense segmented prefix (ops/segment.py).

The XLA path (`segmented_prefix_dense_multi`) runs one `lax.scan` over
row blocks, generating each [block, N] comparison mask on the VPU and
contracting it on the MXU — with the mask and value operands bouncing
through HBM between scan steps. This kernel keeps everything in VMEM:
one grid step per row block, the mask generated tile-by-tile and fed
straight to the MXU, the accumulator never leaving the core. An earlier
round timed it at 1.71x the XLA scan at bench shapes (N=8192, M=2); it
is not measured on the chip at HEAD (ROADMAP A2).

Exactness: the mask is {0,1} f32 and values are f32, so results are
exact for integer counts < 2^24 — strictly wider than the XLA path's
bf16 (≤ 256) envelope.

Backend quirks (measured, this image's mosaic lowering):
- i64 anywhere in the kernel (or its index maps) sends lowering into
  infinite `_convert_helper` recursion. sentinel_tpu enables jax x64,
  under which python-int constants trace as i64 — so the call is traced
  under ``enable_x64(False)``; all kernel I/O is int32/f32, making
  that semantics-free.
- bool→bf16 converts recurse the same way (bool→f32 select is fine).
- A shape-free ``BlockSpec(memory_space=VMEM)`` recurses too; explicit
  full-array block shapes work.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_BLOCK = 512   # rows per grid step
_JTILE = 512   # mask tile width fed to the MXU per inner iteration


def _make_kernel(npad: int, m1: int):
    def kernel(ids_col_ref, ids_row_ref, vals_ref, out_ref):
        b = pl.program_id(0)
        my_ids = ids_col_ref[...]                          # [BLOCK, 1]
        my_pos = (b * _BLOCK
                  + jax.lax.broadcasted_iota(jnp.int32, (_BLOCK, 1), 0))

        def body(j, acc):
            jids = ids_row_ref[:, pl.ds(j * _JTILE, _JTILE)]
            jpos = (j * _JTILE
                    + jax.lax.broadcasted_iota(jnp.int32, (1, _JTILE), 1))
            mask = (my_ids == jids) & (jpos < my_pos)
            maskf = jnp.where(mask, jnp.float32(1), jnp.float32(0))
            v = vals_ref[pl.ds(j * _JTILE, _JTILE), :]
            return acc + jax.lax.dot_general(
                maskf, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        out_ref[...] = jax.lax.fori_loop(
            0, npad // _JTILE, body,
            jnp.zeros((_BLOCK, m1), jnp.float32))

    return kernel


def prefix_pallas(ids: jnp.ndarray, values: jnp.ndarray,
                  interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dense segmented exclusive prefix on the TPU (or interpreted).

    Same contract as ``segment.segmented_prefix_dense``: ``ids`` int[N]
    (< 0 forms a shared segment whose values callers keep at 0),
    ``values`` [N] or [N, M]; returns (prefix float32 like values,
    is_first bool[N]).
    """
    from sentinel_tpu.ops.segment import prep_prefix_pair

    n = ids.shape[0]
    npad = -(-n // _BLOCK) * _BLOCK
    squeeze, m, ids32, vals1 = prep_prefix_pair(ids, values, npad)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            _make_kernel(npad, m + 1),
            grid=(npad // _BLOCK,),
            in_specs=[
                pl.BlockSpec((_BLOCK, 1), lambda b: (b, 0)),
                pl.BlockSpec((1, npad), lambda b: (0, 0)),
                pl.BlockSpec((npad, m + 1), lambda b: (0, 0)),
            ],
            out_specs=pl.BlockSpec((_BLOCK, m + 1), lambda b: (b, 0)),
            out_shape=jax.ShapeDtypeStruct((npad, m + 1), jnp.float32),
            interpret=interpret,
        )(ids32[:, None], ids32[None, :], vals1)
    out = out[:n]
    prefix, earlier = out[:, :m], out[:, m]
    is_first = earlier == 0
    if squeeze:
        prefix = prefix[:, 0]
    return prefix, is_first


def prefix_pallas_multi(pairs: List[Tuple[jnp.ndarray, jnp.ndarray]],
                        interpret: bool = False):
    """K independent prefixes (the ``segmented_prefix_dense_multi``
    contract) as K kernel launches — each launch already saturates the
    MXU from VMEM, so unlike the XLA scans there is nothing to fuse."""
    return [prefix_pallas(ids, values, interpret=interpret)
            for ids, values in pairs]
