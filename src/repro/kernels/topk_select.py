"""Pallas TPU kernels: per-row top-k selection (the compression hot-spot).

Two selection algorithms share one output contract (top-|.|-k per row,
emitted in decreasing-magnitude order, magnitude ties broken by LOWEST
index — identical to ``repro.kernels.ref``):

* ``loop`` — k passes of masked row-max, then min-index-of-max, over an
  in-VMEM tile. O(k*C) VPU work.

* ``threshold`` — per-row bisection on the *bit patterns* of the f32
  magnitudes finds the exact k-th magnitude threshold in <= 32
  compare+count sweeps, and a second bisection over the column index
  keeps the lowest-index threshold ties, giving an exactly-k selection
  mask (int32 bitcasts of non-negative floats are monotone, so the mask
  is exact). A log-step compaction then moves the k selected pairs to a
  narrow front of W lanes (k rounded up to a power of two, at least
  128): a shift-and-add prefix count gives each kept entry its
  displacement, and the entries move left by its bits, lowest first.
  A bitonic network of lane rotations sorts the front into the contract
  order. Nothing runs k times: the row takes O((32 + log2(C)) * C)
  sweeps and the front O(log2(W)^2) compare-exchange stages, whatever
  k is. Outputs are bitwise-equal to ``loop``; the kernels write all W
  slots (the first k hold the selection) and the wrappers slice.

Every step in a kernel body is an operation Mosaic lowers: elementwise
compare/select against iotas, static lane rotations (``pltpu.roll``),
aligned lane slices and row max/min/sum reductions. There is no gather,
scatter, sort or cumsum in a kernel body: a value is read out of a row
as the max of ``where(index == j, v, -inf)``, which returns v exactly
(sign of zero included), and counts are f32 sums of 0/1 (exact below
2**24).

The threshold kernel also comes in a COLUMN-TILED form with grid
``(R // RB, C // CB)``: each (RB, CB) tile is merged into a running
(RB, k) candidate buffer kept in the revisited output block (VMEM), so C
no longer has to fit in a single VMEM tile and the whole selection remains
a single pass over HBM. The candidate buffer is W lanes wide, so
candidates and the tile's compacted front join at an aligned lane
boundary and one 2W-lane sort orders them; each entry carries its global
column index, and ties break on that index, not on position.

Grid/BlockSpec layout (tiled form):
  grid  = (R // RB, C_padded // CB)         # last dim innermost
  x     : BlockSpec((RB, CB), (i, j) -> (i, j))
  vals  : BlockSpec((RB, W),  (i, j) -> (i, 0))   # revisited accumulator
  idx   : BlockSpec((RB, W),  (i, j) -> (i, 0))
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

DEFAULT_ROW_BLOCK = 8
# an (8, 4096) f32 tile is 128 KB — far under VMEM; wider tiles amortize
# the per-merge fixed cost (bisection, compaction) over more columns.
DEFAULT_COL_BLOCK = 4096
# columns appended by jnp.pad / sentinel candidate slots carry this index;
# larger than any real column index so they lose every magnitude tie.
_IDX_SENTINEL = 2**30  # python int: kernels must not capture traced consts
# |x| bitcasts are >= 0; bisection over [-1, max_bits] converges in <= 32
# halvings (the f32 magnitude bit range is < 2^31).
_N_BISECT = 32
# up to this k the k-pass argmax loop beats the fixed-cost threshold
# select. Historical default — the per-backend MEASURED table in
# ``repro.utils.platform.topk_loop_cutover`` supersedes it.
LOOP_MAX_K = 8

# one selection input: (values, global column index, valid mask | None)
Part = Tuple[Array, Array, Optional[Array]]
# lane width of a TPU vreg: the threshold select's front is at least this
_LANES = 128


def _auto_interpret(interpret: Optional[bool]) -> bool:
    """Resolve ``interpret=None``: compiled lowering on TPU and GPU
    (Mosaic / Triton), interpret mode on CPU — with the
    ``REPRO_PALLAS_INTERPRET=0/1`` env override taking priority either
    way (see ``repro.utils.platform.pallas_interpret_default``). An
    explicit ``interpret=`` argument always wins."""
    if interpret is None:
        from repro.utils.platform import pallas_interpret_default

        return pallas_interpret_default()
    return interpret


# ---------------------------------------------------------------------------
# shared row math (Mosaic-lowerable; also runs as plain jnp on any rank)
# ---------------------------------------------------------------------------


def _count(mask: Array) -> Array:
    """Per-row number of True entries, shape (..., 1) int32."""
    return jnp.sum(mask.astype(jnp.float32), axis=-1,
                   keepdims=True).astype(jnp.int32)


def _mag_bits(v: Array, valid: Optional[Array] = None) -> Array:
    """Monotone int32 ordering key for |v| (f32 bitcast); invalid -> -1."""
    bits = jax.lax.bitcast_convert_type(
        jnp.abs(v).astype(jnp.float32), jnp.int32
    )
    if valid is not None:
        bits = jnp.where(valid, bits, jnp.int32(-1))
    return bits


def _rcount(masks: Sequence[Array]) -> Array:
    return functools.reduce(jnp.add, [_count(m) for m in masks])


def _emit_ordered(V: Array, I: Array, k: int
                  ) -> Tuple[Array, Array, Array]:
    """The loop selection: k passes of row max of |V|, the lowest index
    I holding it, that entry's value; the entry is then masked to -inf.
    Returns (vals (..., k) in V's dtype, idx (..., k) int32, the mask of
    the emitted entries)."""
    lead = V.shape[:-1]
    slot = jax.lax.broadcasted_iota(jnp.int32, lead + (k,), len(lead))
    neg_inf = jnp.float32(-jnp.inf)
    Vf = V.astype(jnp.float32)

    def step(s, carry):
        vals, idxs, mag = carry
        m = jnp.max(mag, axis=-1, keepdims=True)
        j = jnp.min(jnp.where(mag == m, I, _IDX_SENTINEL), axis=-1,
                    keepdims=True)
        v = jnp.max(jnp.where(I == j, Vf, neg_inf), axis=-1, keepdims=True)
        vals = jnp.where(slot == s, v, vals)
        idxs = jnp.where(slot == s, j, idxs)
        return vals, idxs, jnp.where(I == j, neg_inf, mag)

    vals0 = jnp.zeros(lead + (k,), jnp.float32)
    idxs0 = jnp.zeros(lead + (k,), jnp.int32)
    vals, idxs, mag = jax.lax.fori_loop(
        0, k, step, (vals0, idxs0, jnp.abs(V).astype(jnp.float32)))
    return vals.astype(V.dtype), idxs, mag == neg_inf


def _kth_largest_bits(bits: Sequence[Array], k: int) -> Array:
    """Exact k-th largest of the ``bits`` parts along the last axis via
    bisection: the largest t with count(bits >= t) >= k, shape (..., 1).
    Requires at least k entries per row with bits > -1."""
    lo = jnp.full(bits[0].shape[:-1] + (1,), -1, jnp.int32)
    hi = functools.reduce(
        jnp.maximum, [jnp.max(b, axis=-1, keepdims=True) for b in bits])

    def body(_, carry):
        lo, hi = carry
        mid = lo + jax.lax.shift_right_logical(hi - lo + 1, 1)
        ge = _rcount([b >= mid for b in bits]) >= k
        return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid - 1)

    lo, _ = jax.lax.fori_loop(0, _N_BISECT, body, (lo, hi))
    return lo


def _threshold_mask(bits: Sequence[Array], idx: Sequence[Array], k: int,
                    idx_bound: int) -> List[Array]:
    """Exactly-k per-row masks: every entry above the k-th magnitude,
    plus the lowest-index ties at it. The tie cut is the smallest index
    t with count(tie & index < t) == k - #above, found by bisection over
    [0, idx_bound] (every real index is below ``idx_bound``)."""
    tau = _kth_largest_bits(bits, k)
    need = k - _rcount([b > tau for b in bits])
    ties = [b == tau for b in bits]
    lo = jnp.zeros_like(tau)
    hi = jnp.full_like(tau, idx_bound)

    def body(_, carry):
        lo, hi = carry
        mid = jax.lax.shift_right_logical(lo + hi, 1)
        ge = _rcount([t & (I < mid) for t, I in zip(ties, idx)]) >= need
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    cut, _ = jax.lax.fori_loop(
        0, max(1, idx_bound.bit_length()), body, (lo, hi))
    return [(b > tau) | (t & (I < cut))
            for b, t, I in zip(bits, ties, idx)]


def _roll(x: Array, shift) -> Array:
    """``jnp.roll`` along the last axis by ``shift`` in [0, N) (a Python
    int or a traced int32 scalar)."""
    return pltpu.roll(x, shift, x.ndim - 1)


def _pow2(e) -> Array:
    """2 ** e for a traced int32 exponent."""
    return jax.lax.shift_left(jnp.int32(1), e)


def _prefix_count(mask: Array) -> Array:
    """Inclusive running count of ``mask`` along the last axis, int32:
    ceil(log2(N)) shift-and-add steps (Hillis-Steele), no cumsum."""
    N = mask.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, mask.shape, mask.ndim - 1)

    def body(b, c):
        s = _pow2(b)
        return c + jnp.where(lane >= s, _roll(c, s), 0)

    return jax.lax.fori_loop(0, (N - 1).bit_length(), body,
                             mask.astype(jnp.int32))


def _compact_front(sel: Array, payload: Sequence[Array], width: int
                   ) -> List[Array]:
    """The ``payload`` entries marked by ``sel`` moved to the front of
    each row in index order, first ``width`` lanes kept (lanes past the
    row's count hold leftovers).

    A kept entry moves left by the number of dropped entries before it,
    one bit of that displacement per step, lowest bit first: kept
    entries keep their order and their displacements do not decrease
    along the row, so no two ever land on one lane."""
    N = sel.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, sel.shape, sel.ndim - 1)

    def body(b, carry):
        d, payload = carry
        s = _pow2(b)
        d_in = _roll(d, N - s)  # the entry s lanes to the right
        move = (lane < N - s) & (d_in >= 0) & (
            (jax.lax.shift_right_logical(d_in, b) & 1) == 1)
        stay = (d >= 0) & ((jax.lax.shift_right_logical(d, b) & 1) == 0)
        d = jnp.where(move, d_in, jnp.where(stay, d, -1))
        return d, [jnp.where(move, _roll(x, N - s), x) for x in payload]

    # displacement of each kept entry; -1 marks an empty lane
    d = jnp.where(sel, lane + 1 - _prefix_count(sel), -1)
    _, payload = jax.lax.fori_loop(0, (N - 1).bit_length(), body,
                                   (d, list(payload)))
    return [x[..., :width] for x in payload]


def _front_width(k: int) -> int:
    """Lanes of the threshold select's sorted front: k rounded up to a
    power of two, at least one vreg row (128 lanes)."""
    return max(_LANES, 1 << (k - 1).bit_length())


def _out_width(k: int, selection: str) -> int:
    """Slots a kernel writes per row: k for the loop, the whole front for
    the threshold select (its first k slots hold the selection)."""
    return _front_width(k) if selection == "threshold" else k


def _front(sel: Array, V: Array, I: Array, B: Array, width: int
           ) -> Tuple[Array, Array, Array]:
    """The entries marked by ``sel`` on ``width`` lanes (compacted when
    the row is wider, padded when narrower); every other lane is made
    invalid: bits -1, index ``_IDX_SENTINEL``."""
    N = sel.shape[-1]
    if N > width:
        V, I, B = _compact_front(sel, [V, I, B], width)
        lane = jax.lax.broadcasted_iota(jnp.int32, I.shape, I.ndim - 1)
        sel = lane < _count(sel)
    elif N < width:  # rows narrower than one front
        pad = [(0, 0)] * (sel.ndim - 1) + [(0, width - N)]
        V, I, B, sel = (jnp.pad(x, pad) for x in (V, I, B, sel))
    return (V, jnp.where(sel, I, _IDX_SENTINEL),
            jnp.where(sel, B, jnp.int32(-1)))


def _sort_front(V: Array, I: Array, B: Array) -> Tuple[Array, Array, Array]:
    """Bitonic sort of each row (a power-of-two number of lanes) into the
    contract order: magnitude bits descending, then index ascending, so
    invalid entries (bits -1) go last. Compare-exchange with the partner
    lane ``lane ^ j`` read through two lane rotations."""
    W = B.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, B.shape, B.ndim - 1)

    def exchange(s, r, carry):
        V, I, B = carry
        j = _pow2(s - 1 - r)
        up = (lane & _pow2(s)) == 0  # this block sorts in contract order
        lower = (lane & j) == 0

        def partner(x):
            return jnp.where(lower, _roll(x, W - j), _roll(x, j))

        pV, pI, pB = partner(V), partner(I), partner(B)
        first = (B > pB) | ((B == pB) & ((I < pI) | ((I == pI) & lower)))
        keep = first == (lower == up)
        return (jnp.where(keep, V, pV), jnp.where(keep, I, pI),
                jnp.where(keep, B, pB))

    carry = (V, I, B)
    for s in range(1, W.bit_length()):  # block sizes 2, 4, ..., W
        carry = jax.lax.fori_loop(
            0, s, functools.partial(exchange, jnp.int32(s)), carry)
    return carry


def _threshold_select(parts: Sequence[Part], k: int, idx_bound: int
                      ) -> Tuple[Array, Array, List[Array]]:
    """Threshold top-|.|-k over the union of ``parts`` (every index below
    ``idx_bound``) in contract order.

    Returns (vals in the first part's dtype and idx, both
    ``_front_width(k)`` wide, the first k slots holding the selection;
    one mask per part marking the selected entries). Needs at least k
    valid entries per row."""
    bits = [_mag_bits(V, valid) for V, _, valid in parts]
    sel = _threshold_mask(bits, [I for _, I, _ in parts], k, idx_bound)
    width = _front_width(k)
    fronts = [_front(s, V.astype(jnp.float32), I, B, width)
              for (V, I, _), B, s in zip(parts, bits, sel)]
    V, I, B = (jnp.concatenate(xs, axis=-1) if len(xs) > 1 else xs[0]
               for xs in zip(*fronts))
    V, I, _ = _sort_front(V, I, B)
    return V[..., :width].astype(parts[0][0].dtype), I[..., :width], sel


def _select_rows(x: Array, k: int, selection: str
                 ) -> Tuple[Array, Array, Array]:
    """Top-k of every row of ``x`` (..., C), C >= k. Returns (vals, idx,
    ``_out_width(k, selection)`` slots per row with the selection in the
    first k, and the selected mask shaped like x)."""
    I = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    if selection == "loop":
        return _emit_ordered(x, I, k)
    if selection != "threshold":
        raise ValueError(f"unknown selection {selection!r}")
    vals, idx, (selected,) = _threshold_select([(x, I, None)], k,
                                               x.shape[-1])
    return vals, idx, selected


# ---------------------------------------------------------------------------
# jnp twin of the threshold select (plain XLA, never a kernel body)
# ---------------------------------------------------------------------------


def _select_mask(bits: Array, tau: Array, k: int) -> Array:
    """Exactly-k per-row mask: all > tau, plus the first (k - #gt) ties in
    axis order. Correct iff equal magnitudes appear in ascending-index
    order along the axis."""
    gt = bits > tau
    eq = bits == tau
    n_gt = jnp.sum(gt.astype(jnp.int32), axis=-1, keepdims=True)
    tie_rank = jnp.cumsum(eq.astype(jnp.int32), axis=-1) - 1
    return gt | (eq & (tie_rank < (k - n_gt)))


def _compact_selected(sel: Array, k: int) -> Array:
    """Positions (along the last axis) of the k selected entries, in axis
    order: a masked-cumsum compaction realised as a vectorized binary
    search over the running count — slot s is the first n with
    cumsum(sel)[n] == s+1. Gathers only (no scatter, no sort): scatters
    serialize on CPU and replicate under GSPMD."""
    N = sel.shape[-1]
    cums = jnp.cumsum(sel.astype(jnp.int32), axis=-1)
    targets = 1 + jax.lax.broadcasted_iota(
        jnp.int32, sel.shape[:-1] + (k,), sel.ndim - 1
    )
    lo = jnp.zeros(targets.shape, jnp.int32)
    hi = jnp.full(targets.shape, N - 1, jnp.int32)
    n_steps = max(1, (N - 1).bit_length())
    for _ in range(n_steps):  # static unroll: ceil(log2(N)) halvings
        mid = (lo + hi) // 2
        ge = jnp.take_along_axis(cums, mid, axis=-1) >= targets
        lo = jnp.where(ge, lo, mid + 1)
        hi = jnp.where(ge, mid, hi)
    return lo


def _order_pairs(cv: Array, ci: Array, cb: Array) -> Tuple[Array, Array]:
    """Permute compacted (..., k) pairs into the contract's (-|v|, index)
    order: O(k^2) rank + one-hot permutation (exact — each output sums
    exactly one nonzero term, so even bf16 values pass through bitwise)."""
    k = cv.shape[-1]
    prec = (cb[..., None, :] > cb[..., :, None]) | (
        (cb[..., None, :] == cb[..., :, None])
        & (ci[..., None, :] < ci[..., :, None])
    )
    rank = jnp.sum(prec.astype(jnp.int32), axis=-1)  # (..., k) permutation
    slots = jnp.arange(k, dtype=jnp.int32)
    perm = (rank[..., None] == slots).astype(jnp.int32)  # (..., src, dst)
    out_v = jnp.einsum(
        "...sd,...s->...d", perm.astype(cv.dtype), cv
    )
    out_i = jnp.sum(perm * ci[..., None], axis=-2)
    return out_v, out_i


def threshold_topk_jnp(x: Array, k: int) -> Tuple[Array, Array]:
    """Threshold top-k of every row of ``x`` (..., C) in plain jnp: the
    same exact bisection threshold as the kernels (O(32*C), independent
    of k), then one masked-cumsum compaction (gathers along the row) and
    an O(k^2) rank pass into the contract order. For XLA outside Mosaic,
    where the partition-safe ``core.distributed`` selection runs it."""
    I = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    bits = _mag_bits(x)
    tau = _kth_largest_bits([bits], k)
    n_sel = _compact_selected(_select_mask(bits, tau, k), k)
    cv = jnp.take_along_axis(x, n_sel, axis=-1)
    ci = jnp.take_along_axis(I, n_sel, axis=-1)
    cb = jnp.take_along_axis(bits, n_sel, axis=-1)
    return _order_pairs(cv, ci, cb)


def mask_live_k(vals: Array, idx: Array, k_live) -> Tuple[Array, Array]:
    """Restrict a contract-ordered top-``k_max`` selection to a RUNTIME
    ``k_live <= k_max`` without changing shapes: slots ``>= k_live``
    become (-0.0, 0), which densify/scatter as EXACT no-ops.

    Because every selector here emits pairs sorted by (-|v|, index), the
    first ``k_live`` slots of a top-``k_max`` selection ARE the
    top-``k_live`` selection — so masking the tail of one static-shape
    select is exactly equivalent to selecting at ``k_live``, for any
    traced ``k_live``. (The bisection threshold in ``_kth_largest_bits``
    is itself count-parameterized — ``k`` appears only in arithmetic
    comparisons — but the compaction/ordering stages need a static slot
    count, so the runtime-k path selects at the static ``k_max`` and
    masks.) This is what lets the distributed pod stage move its k at
    runtime while every buffer, wire message and all-gather stays shaped
    at the compile-time ``k_max``.

    The padded value is NEGATIVE zero on purpose: -0.0 is the additive
    identity of IEEE float addition (``x + -0.0 == x`` bitwise for every
    x, including both signed zeros), so a scatter-add densify over the
    padded slots is an exact no-op and the error-feedback memory stays
    BITWISE identical to the static-k computation (a +0.0 fill flips
    -0.0 entries: ``-0.0 + 0.0 == +0.0``). One caveat survives: XLA
    compiles a k=1 one-hot-einsum densify without a reduce (keeping
    ``0*v`` signed zeros) while any multi-slot reduce inits at +0.0, so
    the RAW update of a masked k_max select can differ from a static
    k_live=1 compile in the sign of all-zero columns. That transient
    ±0.0 cancels at application — ``p - (+/-0.0) == p`` for every
    nonzero parameter — so applied params (and memory) remain bitwise
    identical; compare those, not the raw update's zero signs."""
    slot = jax.lax.broadcasted_iota(jnp.int32, idx.shape, idx.ndim - 1)
    live = slot < jnp.asarray(k_live, jnp.int32)
    return (
        jnp.where(live, vals, jnp.full_like(vals, -0.0)),
        jnp.where(live, idx, jnp.zeros_like(idx)),
    )


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int, selection: str):
    vals, idxs, _ = _select_rows(x_ref[...], k, selection)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def _topk_tiled_kernel(x_ref, vals_ref, idx_ref, *, k: int, col_block: int,
                       n_cols: int):
    """Merge one (RB, CB) tile into the (RB, W) candidate accumulator."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.zeros_like(vals_ref)
        idx_ref[...] = jnp.full(idx_ref.shape, _IDX_SENTINEL, jnp.int32)

    x = x_ref[...]
    tile_i = j * col_block + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 1
    )
    cand_v, cand_i = vals_ref[...], idx_ref[...]
    # sentinel candidate slots never compete
    parts = [(cand_v, cand_i, cand_i < _IDX_SENTINEL), (x, tile_i, None)]
    vals, idxs, _ = _threshold_select(parts, k, n_cols)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def row_topk_pallas(
    x: Array, k: int, *, row_block: int = DEFAULT_ROW_BLOCK,
    interpret: Optional[bool] = None, selection: str = "loop",
) -> Tuple[Array, Array]:
    """Per-row top-|.|-k with the full row as one VMEM tile.

    x: (R, C) with R % row_block == 0 and k <= C. ``selection`` in
    {"loop", "threshold"}.
    """
    R, C = x.shape
    assert R % row_block == 0, (R, row_block)
    assert k <= C, (k, C)
    grid = (R // row_block,)
    width = _out_width(k, selection)
    kernel = functools.partial(_topk_kernel, k=k, selection=selection)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((row_block, C), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((row_block, width), lambda i: (i, 0)),
            pl.BlockSpec((row_block, width), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, width), x.dtype),
            jax.ShapeDtypeStruct((R, width), jnp.int32),
        ],
        interpret=_auto_interpret(interpret),
    )(x)
    return vals[:, :k], idx[:, :k]


def row_topk_tiled_pallas(
    x: Array, k: int, *, row_block: int = DEFAULT_ROW_BLOCK,
    col_block: int = DEFAULT_COL_BLOCK, interpret: Optional[bool] = None,
) -> Tuple[Array, Array]:
    """Single-pass column-tiled threshold top-k.

    x: (R, C) with R % row_block == 0 and k <= C. C is padded up to a
    multiple of the column block with zeros; padded columns carry indices
    >= C and (with C >= k real entries available) are never selected.
    """
    R, C = x.shape
    assert R % row_block == 0, (R, row_block)
    assert k <= C, (k, C)
    cb = max(k, min(col_block, C))  # merge needs >= k entries per tile
    pad = (-C) % cb
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    grid = (R // row_block, (C + pad) // cb)
    width = _front_width(k)
    kernel = functools.partial(_topk_tiled_kernel, k=k, col_block=cb,
                               n_cols=C + pad)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((row_block, cb), lambda i, j: (i, j))],
        out_specs=[
            pl.BlockSpec((row_block, width), lambda i, j: (i, 0)),
            pl.BlockSpec((row_block, width), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, width), x.dtype),
            jax.ShapeDtypeStruct((R, width), jnp.int32),
        ],
        interpret=_auto_interpret(interpret),
    )(x)
    return vals[:, :k], idx[:, :k]
