"""Jitted public wrappers for the Pallas kernels.

On CPU the kernels execute in interpret mode — the kernel body runs as
Python/jnp over the same BlockSpec tiling, which is what the tests
validate against ``ref.py``. On a TPU ``interpret=None`` auto-detects the
backend and compiles through Mosaic (``tests/test_tpu_compile.py``
compiles each one for a described v5e chip; ``chip_smoke.py`` runs them
on one).

``method`` picks the selection algorithm:

* ``"loop"``      — k masked row-max passes, whole row in one VMEM tile.
* ``"threshold"`` — single-pass bisection select, column-tiled grid so C
  is not limited by VMEM (see ``topk_select.row_topk_tiled_pallas``).
* ``"auto"``      — threshold for k above the backend's measured cutover
  (``repro.utils.platform.topk_loop_cutover``), loop otherwise (tiny k:
  the k dependent passes are cheaper than the fixed 32 bisection sweeps).

All methods emit bitwise-identical (value, index) outputs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.fused_memsgd import fused_memsgd_pallas
from repro.kernels.topk_select import (
    DEFAULT_COL_BLOCK,
    DEFAULT_ROW_BLOCK,
    row_topk_pallas,
    row_topk_tiled_pallas,
)

Array = jax.Array


def _resolve_method(method: str, k: int) -> str:
    if method == "auto":
        from repro.utils.platform import topk_loop_cutover

        return "threshold" if k > topk_loop_cutover() else "loop"
    if method not in ("loop", "threshold"):
        raise ValueError(f"unknown top-k method {method!r}")
    return method


def _pad_rows(x: Array, row_block: int) -> Tuple[Array, int]:
    R = x.shape[0]
    pad = (-R) % row_block
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    return x, pad


@functools.partial(
    jax.jit,
    static_argnames=("k", "row_block", "col_block", "interpret", "method"),
)
def row_topk(x: Array, k: int, row_block: int = DEFAULT_ROW_BLOCK,
             interpret: Optional[bool] = None, method: str = "auto",
             col_block: int = DEFAULT_COL_BLOCK) -> Tuple[Array, Array]:
    """Per-row top-|.|-k of x (R, C) -> (vals (R,k), idx (R,k))."""
    xp, pad = _pad_rows(x, row_block)
    if _resolve_method(method, k) == "threshold":
        vals, idx = row_topk_tiled_pallas(
            xp, k, row_block=row_block, col_block=col_block,
            interpret=interpret,
        )
    else:
        vals, idx = row_topk_pallas(
            xp, k, row_block=row_block, interpret=interpret,
        )
    if pad:
        vals, idx = vals[: x.shape[0]], idx[: x.shape[0]]
    return vals, idx


@functools.partial(
    jax.jit, static_argnames=("k", "row_block", "interpret", "method")
)
def fused_memsgd_update(
    m: Array, g: Array, eta, k: int, row_block: int = DEFAULT_ROW_BLOCK,
    interpret: Optional[bool] = None, method: str = "auto",
) -> Tuple[Array, Array, Array]:
    """Fused u = m + eta*g -> top-k -> residual memory.

    Returns (new_m (R,C), vals (R,k), idx (R,k)).
    """
    mp, pad = _pad_rows(m, row_block)
    gp, _ = _pad_rows(g, row_block)
    new_m, vals, idx = fused_memsgd_pallas(
        mp, gp, eta, k, row_block=row_block, interpret=interpret,
        selection=_resolve_method(method, k),
    )
    if pad:
        new_m = new_m[: m.shape[0]]
        vals, idx = vals[: m.shape[0]], idx[: m.shape[0]]
    return new_m, vals, idx


# re-export oracles for test convenience
row_topk_ref = ref.row_topk_ref
fused_memsgd_ref = ref.fused_memsgd_ref
