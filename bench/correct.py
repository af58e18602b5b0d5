"""The comparison that decides ``correct`` for a training cell.

The window's own step, through its first ``checked_steps`` steps from
the seed, against a plain float32 reference of the same configuration
and the same Mem-SGD (or SGD) arithmetic, written here without the
program: the bucket layout, per-row top-k of ``m + eta*g``, the error
memory, the mean over workers and the parameter update.

Three numbers are compared, each against a limit of its own:

* ``loss<t>_gap``: the relative gap of step t's loss, one number a step.
* ``grad_gap``: the first gradient as the optimizer gets it, worked out
  from the program's state after one step (``eta*g = p0 - p1 + mean_w
  m1``), against the reference's; per leaf the gap between the two
  norms over the reference's norm of that leaf or of the median leaf,
  whichever is larger; the worst leaf.
* ``delta_gap``: the same measure on the change of the parameters over
  the checked steps (``p3 - p0``).

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both norms' measures: under Mem-SGD they move by
round-off alone.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

NEGLIGIBLE = 1e-3  # share of the median leaf's gradient norm
DENSE_BELOW = 16_384  # leaves smaller than this go to the uncompressed bucket


# ---------------------------------------------------------------------------
# bucket layout (the Mem-SGD paper's blockwise top-k over the flat vector)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Layout:
    """Leaves, in flatten order, laid into one compressed and one dense
    flat buffer; the compressed one viewed as rows of ``cols``."""

    treedef: object
    placements: tuple  # per leaf: (bucket, offset, size, shape)
    buckets: tuple  # per bucket: (kind, rows, cols, size)

    def unpack_np(self, bufs) -> list:
        flats = [np.asarray(b).reshape(-1) for b in bufs]
        return [flats[b][off:off + size].reshape(shape)
                for b, off, size, shape in self.placements]


def layout(tree, cols: int) -> Layout:
    leaves, treedef = jax.tree.flatten(tree)
    kinds = ["dense" if math.prod(x.shape) < DENSE_BELOW else "sparse"
             for x in leaves]
    order = list(dict.fromkeys(kinds))  # buckets in order of first use
    offsets = [0] * len(order)
    placements = []
    for x, kind in zip(leaves, kinds):
        b = order.index(kind)
        size = math.prod(x.shape)
        placements.append((b, offsets[b], size, tuple(x.shape)))
        offsets[b] += size
    buckets = tuple(
        (kind, -(-offsets[b] // cols), cols, offsets[b]) if kind == "sparse"
        else (kind, 1, offsets[b], offsets[b])
        for b, kind in enumerate(order))
    return Layout(treedef, tuple(placements), buckets)


# ---------------------------------------------------------------------------
# lower precision for the control
# ---------------------------------------------------------------------------


def _fp8_round(a, dtype, top):
    amax = jnp.max(jnp.abs(a))
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return (a / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def fp8(a):
    """Round through float8 as fp8 training does: the value in e4m3 and
    its cotangent in e5m2, each with a per-tensor scale (amax to the
    format's largest finite value)."""
    return _fp8_round(a, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(a):
    return fp8(a), None


def _fp8_bwd(_, g):
    return (_fp8_round(g, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def identity(a):
    return a


# ---------------------------------------------------------------------------
# the reference run
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0, 1))
def _select(m, g, eta, k):
    """Per-row top-k of u = m + eta*g (ties to the lower index), for one
    leaf whose rows are whole bucket rows. Returns (selected, new memory)."""
    u = m + eta * g.reshape(m.shape)
    _, idx = jax.lax.top_k(jnp.abs(u), k)
    rows = jnp.arange(u.shape[0])[:, None]
    sel = jnp.zeros_like(u).at[rows, idx].set(u[rows, idx])
    return sel.reshape(g.shape), u - sel


@functools.partial(jax.jit, donate_argnums=(0,))
def _add(acc, x):
    return jax.tree.map(jnp.add, acc, x)


@functools.partial(jax.jit, donate_argnums=(0,))
def _sub(p, u):
    return jax.tree.map(jnp.subtract, p, u)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scale(x, c):
    return jax.tree.map(lambda a: a * c, x)


def _leaf_norms(leaves) -> list:
    return [float(np.linalg.norm(np.asarray(x, np.float32).reshape(-1)))
            for x in leaves]


def reference_run(ref, m: dict, traffic: dict, pool: np.ndarray, init_key,
                  devices, rnd: Callable = identity,
                  fault: Optional[str] = None) -> dict:
    """Run the checked steps of the traffic's optimizer in float32 (or in
    ``rnd``'s precision) on the first of ``devices``, one worker after
    another, each worker's error memory kept on the host between turns.

    ``pool``: (n, workers * batch, seq + 1) token ids from the traffic
    generator; step t trains on ``pool[t]``. ``fault`` plants one fault
    in the reference put in the program's place: ``"half_batch"`` (each
    worker's mean over half its rows), ``"no_exchange"`` (worker 0 applies
    its own selection, not the mean).
    Returns per-step losses and per-leaf norms of the first gradient and
    of the parameter change.
    """
    W, B = traffic["data_parallel"], traffic["batch_per_worker"]
    eta = np.float32(traffic["eta"])
    steps = traffic["checked_steps"]
    rows = m["reference_rows"]
    sparse = traffic["optimizer"] == "memsgd"
    cols = traffic["sync"].get("bucket_cols", 1024)
    k = max(1, int(round(traffic["sync"]["ratio"] * cols))) if sparse else 0
    dev = list(devices)[0]  # the workers take turns on one chip

    params = jax.jit(lambda key: ref.init(m, key),
                     out_shardings=SingleDeviceSharding(dev))(init_key)
    lay = layout(params, cols)
    p0 = [np.asarray(x) for x in jax.tree.leaves(params)]
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, t, l: ref.loss(p, t, l, m, rnd)))
    compressed = [sparse and lay.buckets[b][0] == "sparse" for b, *_ in lay.placements]
    for (b, off, size, _), c in zip(lay.placements, compressed):
        if c and (off % cols or size % cols):
            raise ValueError("the reference selects leaf by leaf: every compressed "
                             f"leaf must hold whole rows of {cols}")
    memory = [None] * W  # per worker; on the host between its turns when W > 1
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for t in range(steps):
            update, loss_w, g_mean = None, [], None
            for w in range(W):
                rows_w = pool[t, w * B:(w + 1) * B]
                if fault == "half_batch":
                    rows_w = rows_w[:B // 2]
                chunks = [rows_w[i:i + rows] for i in range(0, len(rows_w), rows)]
                g_w, l_w = None, 0.0
                for c in chunks:
                    l_c, g_c = grad_fn(params, jax.device_put(c[:, :-1], dev),
                                       jax.device_put(c[:, 1:], dev))
                    g_w = g_c if g_w is None else _add(g_w, g_c)
                    l_w = l_w + l_c / len(chunks)
                del g_c
                g_leaves = jax.tree.leaves(_scale(g_w, np.float32(1 / len(chunks))))
                loss_w.append(l_w)
                if t == 0:
                    g_host = [np.asarray(x) for x in g_leaves]
                    g_mean = g_host if g_mean is None else [
                        a + b for a, b in zip(g_mean, g_host)]
                    del g_host
                mem = memory[w] or [jnp.zeros((x.size // cols, cols), jnp.float32, device=dev)
                                    if c else None for x, c in zip(g_leaves, compressed)]
                mem = [jax.device_put(x, dev) if x is not None else None for x in mem]
                sel_w = []
                for i, (g, c) in enumerate(zip(g_leaves, compressed)):
                    if c:  # error feedback and top-k of each bucket row
                        s, mem[i] = _select(mem[i], g, eta, k)
                    else:  # sent whole: nothing left in memory
                        s = _scale(g, eta)
                    sel_w.append(s)
                memory[w] = mem if W == 1 else [
                    np.asarray(x) if x is not None else None for x in mem]
                del g_leaves, mem
                if fault == "no_exchange" and w > 0:
                    continue
                update = sel_w if update is None else _add(update, sel_w)
                del sel_w
            losses.append(sum(float(x) for x in loss_w) / len(loss_w))
            if t == 0:
                grad_norms = _leaf_norms([g / np.float32(W) for g in g_mean])
                del g_mean
            if fault != "no_exchange":
                update = _scale(update, np.float32(1 / W))
            leaves = _sub(jax.tree.leaves(params), update)
            params = jax.tree.unflatten(jax.tree.structure(params), leaves)
            del update, leaves
    p3 = [np.asarray(x) for x in jax.tree.leaves(params)]
    delta_norms = _leaf_norms([b - a for a, b in zip(p0, p3)])
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta_norms,
            "p0_host": p0}


def program_numbers(tap, lay: Optional[Layout], eta: float) -> dict:
    """The same readings from the program's own state, as the step tap
    kept it: losses of the checked steps, the first gradient worked out
    from the state after one step, and the parameter change."""
    p0, p1, p3 = tap.p0, tap.p1, tap.p3
    m1 = lay.unpack_np(tap.m1) if lay is not None else tap.m1
    g = [(a - b + c) / np.float32(eta) for a, b, c in zip(p0, p1, m1)]
    return {"losses": [float(x) for x in tap.losses],
            "grad_norms": _leaf_norms(g),
            "delta_norms": _leaf_norms([b - a for a, b in zip(p0, p3)])}


def _leaf_gaps(prog: list, ref: list, keep: list) -> list:
    """Per kept leaf: the gap between the two norms over the reference's
    norm of that leaf or of the median kept leaf, whichever is larger."""
    median = float(np.median([r for r, k in zip(ref, keep) if k]))
    return [abs(p - r) / max(r, median) if k else None
            for p, r, k in zip(prog, ref, keep)]


def compare(prog: dict, ref: dict, leaf_names: list) -> dict:
    """Every number the check can compare; a cell's limits file names
    the ones it holds to a limit. ``*_gap`` is the worst leaf's gap,
    ``*_gap_median`` the median leaf's."""
    med = float(np.median(ref["grad_norms"]))
    keep = [r >= NEGLIGIBLE * med for r in ref["grad_norms"]]
    loss_gaps = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
                 for a, b in zip(prog["losses"], ref["losses"])]
    out = {f"loss{t + 1}_gap": g for t, g in enumerate(loss_gaps)}
    where = {"left_out": [n for n, k in zip(leaf_names, keep) if not k]}
    for kind in ("grad", "delta"):
        gaps = _leaf_gaps(prog[f"{kind}_norms"], ref[f"{kind}_norms"], keep)
        kept = [g for g in gaps if g is not None]
        worst = max(range(len(gaps)), key=lambda i: -1.0 if gaps[i] is None else gaps[i])
        out[f"{kind}_gap"] = gaps[worst]
        out[f"{kind}_gap_median"] = float(np.median(kept))
        where[f"{kind}_leaf"] = leaf_names[worst]
        where[f"{kind}_leaf_gaps"] = {n: g for n, g in zip(leaf_names, gaps) if g is not None}
    out["where"] = where
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) over the numbers the cell's
    limits file names; a number that is not finite fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        v = float(numbers[name])
        checks[name] = {"value": v, "limit": limit}
        ok = ok and math.isfinite(v) and v <= limit
    return ok, checks
