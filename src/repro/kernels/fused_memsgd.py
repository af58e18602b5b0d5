"""Pallas TPU kernels: fused Mem-SGD memory update + compression.

The unfused sequence reads/writes the full (param-sized) tensors many
times per step (u = m + eta*g; k select passes over |u|; a densify of the
selection; m' = u - selection). At k << C the tensors are HBM-bandwidth
bound, so fusing them into a single pass over each VMEM tile cuts the HBM
traffic of the compression stage to the 3 unavoidable transfers (read m,
read g, write m').

Per grid step (one (ROW_BLOCK, C) tile of m and g resident in VMEM):
    u     = m + eta * g           # elementwise, VPU
    v,i   = row_topk(u, k)        # k select passes, in VMEM only
    m'    = u zeroed at selected  # written over m (aliased)

``fused_memsgd_pallas`` (the loop select) transposes the tile in VMEM so
each bucket row lies along one LANE and its C columns along sublanes.
A select pass is then elementwise work: it walks the columns ``CHUNK``
sublane rows at a time, keeping a running (max |u|, key) per sublane row
and lane, and one sublane reduction per pass finishes it; no pass needs a
cross-lane reduction. The key of an entry is ``2 * column + sign bit``:
the smallest key among the maxima is the lowest column (the contract's
tie-break), and the value comes back from the magnitude and that bit, so
no pass extracts it. A selected entry is masked to -inf in the next
pass. The selection leaves as (k, rows) and is transposed by XLA (k*rows
words, against the kernel's 3*C*rows). The grid covers the rows with a
``pl.cdiv`` grid, so no padded copy of the bucket is made; m' is aliased
to m, so the caller holds no second bucket-sized buffer when m is
donated. g is read as (R*C/128, 128), the layout of a bucket packed flat
on a TPU, and strided loads gather each tile's rows back, so a flat g
reaches the kernel without a relaid copy.

``fused_memsgd_threshold_pallas`` keeps the row layout and runs the
k-independent threshold select of ``repro.kernels.topk_select`` (for k
above the loop select's cutover). Both emit the contract of
``repro.kernels.ref.fused_memsgd_ref`` bitwise.

eta arrives as a (1, 1) f32 operand in SMEM (scalar memory), so the same
compiled kernel serves every step of a stepsize schedule.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_select import (
    DEFAULT_ROW_BLOCK,
    _IDX_SENTINEL,
    _auto_interpret,
    _out_width,
    _select_rows,
)

Array = jax.Array

# bucket rows per grid step of the loop kernel: the lanes of the
# transposed tile (two vregs wide), 1 MiB of f32 per (ROW_BLOCK, 1024)
# tile of m, g and m' each
ROW_BLOCK = 256
# columns (sublane rows of the transposed tile) per step of a select
# pass: four vregs of running maxima per 128 rows, independent chains
CHUNK = 32
# m, g and m' tiles double-buffered plus the transposed |u| and key
# scratch: 8 tiles of (ROW_BLOCK, 1024) f32 = 8 MiB, and room for the
# compiler's own copies of the transposes
VMEM_LIMIT = 64 * 2**20
NAME = "memsgd_fused"
# lanes of a vreg: g is read in rows of this many columns
LANES = 128


def _loop_kernel(eta_ref, m_ref, g_ref, newm_ref, vals_ref, idx_ref,
                 mag_ref, key_ref, *, k: int, chunk: int, folds: int):
    m = m_ref[...]
    if folds > 1:  # row r of g is rows r*folds ... r*folds + folds-1 here
        g = jnp.concatenate(
            [g_ref[pl.ds(j, m.shape[0], stride=folds), :]
             for j in range(folds)], axis=1)
    else:
        g = g_ref[...]
    u = m + eta_ref[0, 0].astype(m.dtype) * g.astype(m.dtype)
    # (C, rows): a bucket row on each lane. Transposed before the f32
    # cast, so u is rounded to its own dtype on every backend
    ut = u.T.astype(jnp.float32)
    C, rows = ut.shape
    col = jax.lax.broadcasted_iota(jnp.int32, ut.shape, 0)
    key_ref[...] = col * 2 + jax.lax.shift_right_logical(
        jax.lax.bitcast_convert_type(ut, jnp.int32), 31)
    mag_ref[...] = jnp.abs(ut)
    neg_inf = jnp.float32(-jnp.inf)
    slot = jax.lax.broadcasted_iota(jnp.int32, (k, rows), 0)

    def select(t, carry):
        vals, idxs, prev = carry

        def scan(i, best):
            bm, bk = best
            at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
            ky = key_ref[at, :]
            mg = jnp.where(ky == prev, neg_inf, mag_ref[at, :])
            mag_ref[at, :] = mg
            gt = mg > bm  # strict: the lower column keeps a tie
            return jnp.where(gt, mg, bm), jnp.where(gt, ky, bk)

        bm, bk = jax.lax.fori_loop(
            0, C // chunk, scan,
            (jnp.full((chunk, rows), neg_inf, jnp.float32),
             jnp.full((chunk, rows), _IDX_SENTINEL, jnp.int32)),
            unroll=True)
        mx = jnp.max(bm, axis=0, keepdims=True)
        j = jnp.min(jnp.where(bm == mx, bk, _IDX_SENTINEL), axis=0,
                    keepdims=True)
        v = jnp.where((j & 1) == 1, -mx, mx)
        vals = jnp.where(slot == t, v, vals)
        idxs = jnp.where(slot == t, jax.lax.shift_right_logical(j, 1), idxs)
        return vals, idxs, j

    vals, idxs, last = jax.lax.fori_loop(
        0, k, select,
        (jnp.zeros((k, rows), jnp.float32), jnp.zeros((k, rows), jnp.int32),
         jnp.full((1, rows), -1, jnp.int32)))
    # u again from |u| and the sign bit that the key keeps, so that u
    # itself need not stay in VMEM through the passes
    mag, key = mag_ref[...], key_ref[...]
    ut = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(mag, jnp.int32) | (key << 31),
        jnp.float32)
    picked = (mag == neg_inf) | (key == last)
    newm_ref[...] = jnp.where(picked, 0.0, ut).T.astype(newm_ref.dtype)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idxs


def fused_memsgd_pallas(
    m: Array, g: Array, eta, k: int, *, interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """(m, g): (R, C); eta scalar. Returns (new_m (R, C) aliased to m,
    vals (R, k), idx (R, k)) in the loop select's contract order."""
    R, C = m.shape
    assert m.shape == g.shape
    assert k <= C, (k, C)
    rb = min(ROW_BLOCK, R)
    chunk = math.gcd(C, CHUNK)
    # g is read as (R * C / 128, 128) where C allows: on a TPU that view
    # of a bucket packed flat (``core.buckets.pack``) is the flat
    # buffer's own layout, where an (R, C) operand would be a relaid copy
    folds = C // LANES if C % LANES == 0 else 1
    g = g.reshape(R * folds, C // folds)
    kernel = functools.partial(_loop_kernel, k=k, chunk=chunk, folds=folds)
    eta_arr = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    new_m, vals, idx = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(R, rb),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # eta, whole (1, 1)
            pl.BlockSpec((rb, C), lambda i: (i, 0)),
            pl.BlockSpec((rb * folds, C // folds), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rb, C), lambda i: (i, 0)),
            pl.BlockSpec((k, rb), lambda i: (0, i)),
            pl.BlockSpec((k, rb), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), m.dtype),
            jax.ShapeDtypeStruct((k, R), m.dtype),
            jax.ShapeDtypeStruct((k, R), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((C, rb), jnp.float32),
                        pltpu.VMEM((C, rb), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=_auto_interpret(interpret),
        name=NAME,
    )(eta_arr, m, g)
    return new_m, vals.T, idx.T


def _threshold_kernel(eta_ref, m_ref, g_ref, newm_ref, vals_ref, idx_ref, *,
                      k: int):
    m = m_ref[...]
    u = m + eta_ref[0, 0].astype(m.dtype) * g_ref[...].astype(m.dtype)
    vals, idxs, selected = _select_rows(u, k, "threshold")
    newm_ref[...] = jnp.where(selected, jnp.zeros_like(u), u)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def fused_memsgd_threshold_pallas(
    m: Array, g: Array, eta, k: int, *,
    row_block: int = DEFAULT_ROW_BLOCK, interpret: Optional[bool] = None,
) -> Tuple[Array, Array, Array]:
    """The threshold select in the row layout. (m, g): (R, C) with
    R % row_block == 0. Returns (new_m (R,C), vals (R,k), idx (R,k))."""
    R, C = m.shape
    assert m.shape == g.shape
    assert R % row_block == 0, (R, row_block)
    assert k <= C, (k, C)
    width = _out_width(k, "threshold")
    eta_arr = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    new_m, vals, idx = pl.pallas_call(
        functools.partial(_threshold_kernel, k=k),
        grid=(R // row_block,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # eta, whole (1, 1)
            pl.BlockSpec((row_block, C), lambda i: (i, 0)),
            pl.BlockSpec((row_block, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((row_block, C), lambda i: (i, 0)),
            pl.BlockSpec((row_block, width), lambda i: (i, 0)),
            pl.BlockSpec((row_block, width), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, C), m.dtype),
            jax.ShapeDtypeStruct((R, width), m.dtype),
            jax.ShapeDtypeStruct((R, width), jnp.int32),
        ],
        interpret=_auto_interpret(interpret),
    )(eta_arr, m, g)
    return new_m, vals[:, :k], idx[:, :k]
