"""Shared diagnostic harness for the two-level pod sync invariants.

Synthetic probes used by BOTH the bench subprocesses (`hierarchy`,
`refresh`) and the slow property tests — the invariant definitions live
here once instead of in embedded script string literals.
``two_level_selfcheck`` runs the two-level bucketed sync on a tiny
2-bucket tree over a real ``(pod, data)`` mesh, once per wire format,
and reports everything the scheme guarantees; ``dynamic_k_selfcheck``
does the same for the RUNTIME pod-k (k-padded wire) path:

* **conservation_max_err** — exact two-level mass conservation:
  ``mean_w(u) == update + mean_w(new_memory)`` (both residual levels
  fold back into bucket memory; float-sum association is the only
  slack).
* **bit_identical** — packed and unpacked wires produce bitwise equal
  updates AND memories.
* **accounting_exact** — the bytes the sync realizes equal the static
  ``bucketed_message_bytes`` prediction, per wire.

Every probe runs each sync as one ``jax.jit``-compiled ``shard_map``
program, the form the train step runs in, so the bitwise claims are made
on compiled code; a runtime-k step takes ``pod_ks`` as an argument and is
compiled once for all live ks. Must run under enough host devices for
the mesh (see ``tests/_multidevice.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import buckets as bk
from repro.core.distributed import (
    SyncConfig,
    bucketed_message_bytes,
    bucketed_sync_gradients,
)
from repro.utils.compat import shard_map


def bitwise_equal(a, b) -> bool:
    """True iff the two pytrees have the same number of leaves and every
    leaf pair is BYTE-identical (uint8 view — float ``==`` would miss
    -0.0 vs +0.0 and NaN payloads). The one comparator every probe,
    bench script and test should share — a truncating ``zip`` over
    mismatched leaf lists silently passes."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    if len(la) != len(lb):
        return False
    return all(
        np.array_equal(np.asarray(x).view(np.uint8),
                       np.asarray(y).view(np.uint8))
        for x, y in zip(la, lb)
    )


def _probe_inputs(mesh):
    """The tiny 2-bucket tree every probe shares (bucket 0 dense, bucket
    1 sparse at cols=128), with per-worker gradients and memories for
    the ``(pod, data)`` workers. Returns ``(tree, plan, gs, mem)``."""
    W = int(np.prod([mesh.shape[a] for a in ("pod", "data")]))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(0), (16, 384)),
            "b": jax.random.normal(jax.random.PRNGKey(1), (40,))}
    plan = bk.make_plan(tree, cols=128, dense_below=64)
    gs = jax.tree.map(lambda x: jnp.stack(
        [x * (1 + 0.1 * i) + 0.01 * i for i in range(W)]), tree)
    mem = tuple(
        jax.random.normal(jax.random.PRNGKey(9 + b), (W,) + s.shape)
        * (0.1 if s.kind == "sparse" else 0.0)
        for b, s in enumerate(plan.buckets))
    return tree, plan, gs, mem


def compiled_sync(mesh, cfg, plan, eta, *, accumulate=False,
                  quant_key=None, realized=None, tag=None):
    """One jitted bucketed sync (any strategy) over the ``(pod, data)``
    workers: ``step(mem, gs[, pod_ks]) -> (update, new_mem)``, with
    ``mem``/``gs`` stacked per worker and ``pod_ks`` a replicated
    runtime argument. ``accumulate`` routes the gradient through the
    local-steps accumulator (``grad_bufs=``, eta 1). The realized bytes
    (a static int) land in ``realized[tag]`` when the step is traced."""
    worker = P(("pod", "data"))

    def sync(m_, g_, *pod_ks):
        kw = {"pod_ks": pod_ks[0]} if pod_ks else {}
        if quant_key is not None:
            kw["quant_key"] = quant_key
        m0 = jax.tree.map(lambda m: m[0], m_)
        g0 = jax.tree.map(lambda x: x[0], g_)
        if accumulate:
            acc = bk.accumulate_local(plan, bk.init_local_accum(plan), g0,
                                      jnp.float32(eta))
            upd, new_mem, nbytes = bucketed_sync_gradients(
                cfg, plan, m0, None, jnp.float32(1.0), grad_bufs=acc, **kw)
        else:
            upd, new_mem, nbytes = bucketed_sync_gradients(
                cfg, plan, m0, g0, jnp.float32(eta), **kw)
        if tag is not None:
            realized[tag] = nbytes
        return upd, jax.tree.map(lambda m: m[None], new_mem)

    @jax.jit
    def step(mem, gs, *pod_ks):
        wspec = jax.tree.map(lambda _: worker, mem)
        return shard_map(
            sync, mesh=mesh,
            in_specs=(wspec, jax.tree.map(lambda _: worker, gs))
            + (P(),) * len(pod_ks),
            out_specs=(jax.tree.map(lambda _: P(), gs), wspec),
        )(mem, gs, *pod_ks)

    return step


def _conservation_err(plan, mem, gs, eta, upd, new_mem) -> float:
    """Two-level mass conservation residual, max over buckets:
    ``|mean_w(mem + eta * g) - (update + mean_w(new_mem))|``."""
    W = mem[0].shape[0]
    g_bufs = [bk.pack(plan, jax.tree.map(lambda x, w=w: x[w], gs),
                      dtype=jnp.float32) for w in range(W)]
    upd_bufs = bk.pack(plan, upd, dtype=jnp.float32)
    err = 0.0
    for b in range(len(plan.buckets)):
        u_w = jnp.stack([mem[b][w] + eta * g_bufs[w][b] for w in range(W)])
        lhs = jnp.mean(u_w, axis=0)
        rhs = upd_bufs[b] + jnp.mean(new_mem[b], axis=0)
        err = max(err, float(jnp.max(jnp.abs(lhs - rhs))))
    return err


def _applied(tree, upd):
    """The params a step leaves behind (``params - update``)."""
    return jax.tree.map(lambda t, u: t - u, tree, upd)


def two_level_selfcheck(mesh, ratio: float = 0.05, pod_ratio: float = 0.1,
                        eta: float = 0.3) -> dict:
    """Probe the two-level sync invariants on ``mesh`` (must have axes
    ``("pod", "data")``). Returns a dict of the three invariant
    measurements plus the per-wire byte accounting."""
    _, plan, gs, mem = _probe_inputs(mesh)
    realized, out, acc = {}, {}, {}
    for wire in ("packed", "unpacked"):
        cfg = SyncConfig(ratio=ratio, strategy="hierarchical",
                         data_axes=("data",), pod_axis="pod",
                         bucketed=True, bucket_cols=128, wire=wire,
                         pod_ratios=(1.0, pod_ratio))
        out[wire] = compiled_sync(mesh, cfg, plan, eta, realized=realized,
                                  tag=wire)(mem, gs)
        acc[wire] = bucketed_message_bytes(cfg, plan)
    bit = all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(jax.tree.leaves(out["packed"]),
                              jax.tree.leaves(out["unpacked"])))
    return {
        "bit_identical": bool(bit),
        "conservation_max_err": _conservation_err(plan, mem, gs, eta,
                                                  *out["packed"]),
        "accounting_exact": realized == acc,
        "realized_bytes": realized,
        "accounted_bytes": acc,
    }


def dynamic_k_selfcheck(mesh, ratio: float = 0.05, eta: float = 0.3,
                        ks=(9, 4)) -> dict:
    """Probe the RUNTIME pod-k (k-padded wire) invariants on ``mesh``
    (axes ``("pod", "data")``). Same tiny 2-bucket tree as
    ``two_level_selfcheck``. Reports:

    * **dynamic_matches_static** — for each wire format and each live k
      in ``ks``, the dynamic path fed that k as a runtime value (one
      compiled step for every k) is BITWISE identical to the static path
      compiled at that k, compared on the APPLIED update (params -
      update) and the new memory: padding the selection to k_max and
      masking the tail reproduces the static computation. (The raw
      update may differ in the SIGN of all-zero columns at k_live=1 —
      XLA's no-reduce special case — a transient ±0.0 that cancels at
      application; see ``kernels.topk_select.mask_live_k``.)
    * **conservation_max_err** — two-level mass conservation under a
      SWITCHED live k (the refresh-boundary invariant): for every live
      k, ``mean_w(u) == update + mean_w(new_memory)``.
    * **accounting_exact** — the realized gather bytes of the dynamic
      path equal the k_max-padded ``bucketed_message_bytes`` prediction
      (the padded buffer IS what the jitted step ships).
    """
    import dataclasses

    tree, plan, gs, mem = _probe_inputs(mesh)
    n_data = int(mesh.shape["data"])
    realized = {}
    matches = True
    cons_err = 0.0
    acc_ok = True
    for wire in ("packed", "unpacked"):
        dyn = SyncConfig(ratio=ratio, strategy="hierarchical",
                         data_axes=("data",), pod_axis="pod",
                         bucketed=True, bucket_cols=128, wire=wire,
                         pod_ratios=(1.0, ks[0] / 128), pod_dynamic=True)
        dyn_step = compiled_sync(mesh, dyn, plan, eta, realized=realized,
                                 tag=wire)
        for k_live in ks:
            static = dataclasses.replace(
                dyn, pod_dynamic=False, pod_ratios=(1.0, k_live / 128))
            out_s = compiled_sync(mesh, static, plan, eta)(mem, gs)
            out_d = dyn_step(mem, gs, jnp.asarray([1, k_live], jnp.int32))
            matches = matches and bitwise_equal(
                (_applied(tree, out_s[0]), out_s[1]),
                (_applied(tree, out_d[0]), out_d[1]))
            acc_ok = acc_ok and realized[wire] == bucketed_message_bytes(
                dyn, plan, n_data=n_data)
            # conservation at this live k (the refresh-boundary invariant)
            cons_err = max(cons_err,
                           _conservation_err(plan, mem, gs, eta, *out_d))
    return {
        "dynamic_matches_static": bool(matches),
        "conservation_max_err": cons_err,
        "accounting_exact": bool(acc_ok),
        "live_ks": list(ks),
    }


def overlap_selfcheck(mesh, ratio: float = 0.05, eta: float = 0.3,
                      wire: str = "packed") -> dict:
    """Probe the pipelined-schedule bit-identity guarantee on ``mesh``
    (axes ``("pod", "data")``): for each sync path — flat
    ``sparse_allgather``, static two-level ``hierarchical``, and the
    runtime-k ``pod_dynamic`` path INCLUDING a mid-run live-k switch —
    ``SyncConfig.overlap`` in {None, False, True} must produce BITWISE
    equal applied params and memory (the pipeline only reorders
    emission and adds ``optimization_barrier`` edges, never a
    value-changing op; see repro.core.pipeline). Same tiny 2-bucket
    tree as ``two_level_selfcheck``."""
    import dataclasses

    tree, plan, gs, mem0 = _probe_inputs(mesh)
    base = dict(ratio=ratio, data_axes=("data",), pod_axis="pod",
                bucketed=True, bucket_cols=128, wire=wire)
    paths = {
        "flat": SyncConfig(strategy="sparse_allgather", **base),
        "hierarchical": SyncConfig(strategy="hierarchical",
                                   pod_ratios=(1.0, 0.1), **base),
        "pod_dynamic": SyncConfig(strategy="hierarchical",
                                  pod_ratios=(1.0, 9 / 128),
                                  pod_dynamic=True, **base),
    }
    out = {}
    for name, cfg in paths.items():
        # pod_dynamic: two chained steps across a live-k REFRESH (9 -> 4)
        # — the schedule must stay bit-identical through the switch, not
        # just at a fixed k
        schedule = ([(jnp.asarray([1, 9], jnp.int32),),
                     (jnp.asarray([1, 4], jnp.int32),)]
                    if name == "pod_dynamic" else [()])
        per_overlap = {}
        for ov in (None, False, True):
            step = compiled_sync(
                mesh, dataclasses.replace(cfg, overlap=ov), plan, eta)
            mem_ = mem0
            applied = []
            for pod_ks in schedule:
                upd, mem_ = step(mem_, gs, *pod_ks)
                applied.append(_applied(tree, upd))
            per_overlap[ov] = (applied, mem_)
        out[f"{name}_bitwise"] = bool(
            bitwise_equal(per_overlap[None], per_overlap[False])
            and bitwise_equal(per_overlap[None], per_overlap[True]))
    out["bitwise_all"] = all(out.values())
    return out


def local_quant_selfcheck(mesh, ratio: float = 0.05, eta: float = 0.3,
                          quant: int = 15, hs=(2, 4, 8)) -> dict:
    """Probe the Qsparse-local-SGD invariants on ``mesh`` (axes
    ``("pod", "data")``). Same tiny 2-bucket tree as
    ``two_level_selfcheck``. Reports:

    * **h1_accum_bitwise** — the local-step ACCUMULATOR path (pack each
      step's scaled gradient into bucket space via
      ``buckets.accumulate_local``, then sync the accumulator with
      ``grad_bufs=``/``eta=1.0``) is BITWISE identical to the direct
      per-step sync at H=1, on flat, hierarchical AND runtime-k
      (pod_dynamic) strategies: packing is elementwise-linear, so
      ``1.0 * (eta*pack(g))`` reproduces ``eta*pack(g)`` exactly. This
      is the acceptance invariant that lets the train driver keep H=1
      on the literal per-step path.
    * **quant_conservation_max_err** — with the QSGD wire tier
      (``WireConfig.quant``) mass conservation stays EXACT (float-sum
      association is the only slack): the memory absorbs the
      quantization error because the sender's own contribution uses the
      dequantized values, on both flat and two-level strategies.
    * **quant_bit_identical** — packed and unpacked wires produce
      bitwise equal updates and memories under quantization: both ship
      ``encoding.dequantize_rows`` of the same codes.
    * **quant_accounting_exact** — realized sync bytes equal the
      ``bucketed_message_bytes(..., quant)`` prediction (code words +
      row-norm words, exact).
    * **amortized_ratio_exact** — ``amortized_bytes_per_step`` scales
      exactly 1/H for every H in ``hs``.
    """
    from repro.core.distributed import (
        PodConfig,
        WireConfig,
        amortized_bytes_per_step,
    )

    _, plan, gs, mem0 = _probe_inputs(mesh)
    realized = {}

    def run(cfg, *pod_ks, accumulate=False, tag=None):
        qk = jax.random.PRNGKey(5) if cfg.quant is not None else None
        return compiled_sync(mesh, cfg, plan, eta, accumulate=accumulate,
                             quant_key=qk, realized=realized,
                             tag=tag)(mem0, gs, *pod_ks)

    base = dict(ratio=ratio, data_axes=("data",), bucketed=True,
                bucket_cols=128)

    # 1) H=1 accumulator routing is bitwise-invisible on every strategy
    paths = {
        "flat": SyncConfig(strategy="sparse_allgather",
                           pod=PodConfig(axis="pod"),
                           wire=WireConfig(wire="packed"), **base),
        "hierarchical": SyncConfig(strategy="hierarchical",
                                   pod=PodConfig(axis="pod",
                                                 ratios=(1.0, 0.1)),
                                   wire=WireConfig(wire="packed"), **base),
        "pod_dynamic": SyncConfig(strategy="hierarchical",
                                  pod=PodConfig(axis="pod", dynamic=True,
                                                ratios=(1.0, 9 / 128)),
                                  wire=WireConfig(wire="packed"), **base),
    }
    h1_ok = True
    for name, cfg in paths.items():
        pk = ((jnp.asarray([1, 9], jnp.int32),) if name == "pod_dynamic"
              else ())
        direct = run(cfg, *pk)
        accum = run(cfg, *pk, accumulate=True)
        h1_ok = h1_ok and bitwise_equal(direct, accum)

    # 2) quantized tier: conservation + packed/unpacked identity + bytes
    cons_err = 0.0
    bit_ok = True
    acc_ok = True
    for name, mk in (
        ("flat", lambda w: SyncConfig(
            strategy="sparse_allgather", pod=PodConfig(axis="pod"),
            wire=WireConfig(wire=w, quant=quant), **base)),
        ("hier", lambda w: SyncConfig(
            strategy="hierarchical",
            pod=PodConfig(axis="pod", ratios=(1.0, 0.1)),
            wire=WireConfig(wire=w, quant=quant), **base)),
    ):
        out_p = run(mk("packed"), tag=f"{name}-packed")
        out_u = run(mk("unpacked"))
        bit_ok = bit_ok and bitwise_equal(out_p, out_u)
        acc_ok = acc_ok and realized[f"{name}-packed"] == (
            bucketed_message_bytes(mk("packed"), plan))
        cons_err = max(cons_err,
                       _conservation_err(plan, mem0, gs, eta, *out_p))

    # 3) amortized byte accounting scales exactly 1/H
    q = SyncConfig(strategy="sparse_allgather", pod=PodConfig(axis="pod"),
                   wire=WireConfig(wire="packed", quant=quant), **base)
    full = bucketed_message_bytes(q, plan)
    ratio_ok = all(
        amortized_bytes_per_step(
            SyncConfig(strategy="sparse_allgather",
                       pod=PodConfig(axis="pod"),
                       wire=WireConfig(wire="packed", quant=quant),
                       local_steps=h, **base),
            plan) == full / h
        for h in hs)
    return {
        "h1_accum_bitwise": bool(h1_ok),
        "quant_conservation_max_err": cons_err,
        "quant_bit_identical": bool(bit_ok),
        "quant_accounting_exact": bool(acc_ok),
        "amortized_ratio_exact": bool(ratio_ok),
    }


def repack_selfcheck(mesh, ratio: float = 0.05, eta: float = 0.3,
                     ks=(9, 4)) -> dict:
    """Probe the header-aware repack transport invariants on ``mesh``
    (axes ``("pod", "data")``). Same tiny 2-bucket tree as
    ``two_level_selfcheck`` (bucket 0 dense, bucket 1 sparse at
    cols=128). Reports:

    * **repack_bitwise** — on the runtime-k path, ``SyncConfig.repack``
      on/off x overlap in {None, False, True}, chained across a mid-run
      live-k switch (``ks[0] -> ks[1]``), all produce BITWISE equal
      applied params and memory: the in-jit R stage is the identity and
      only grows the schedule (invariants 10 + 11).
    * **transport_roundtrip_bitwise** — host-side
      ``distributed.repack_transport`` (inline and over an
      ``EmulatedLink`` future) returns the k_max-padded buffer BITWISE
      unchanged: repack -> link -> repad is invisible to the consumer.
    * **transport_accounting_exact** — the bytes the transport puts on
      the wire equal ``encoding.message_nbytes(..., live_k)`` AND the
      sparse cross-pod term of ``bucketed_message_bytes(...,
      pod_ks=...)``: realized cross-pod bytes == live-k accounting
      (invariant 11).
    * **padded_vs_live_bytes** — the (padded, live) cross-pod byte pair
      for the probe's sparse bucket, the gap the transport closes.
    """
    import dataclasses

    from repro.core import encoding as enc
    from repro.core import pipeline
    from repro.core.distributed import repack_transport
    from repro.kernels.topk_select import mask_live_k

    tree, plan, gs, mem0 = _probe_inputs(mesh)
    n_data = int(mesh.shape["data"])
    dyn = SyncConfig(ratio=ratio, strategy="hierarchical",
                     data_axes=("data",), pod_axis="pod", bucketed=True,
                     bucket_cols=128, wire="packed",
                     pod_ratios=(1.0, ks[0] / 128), pod_dynamic=True)
    outs = {}
    for rp in (False, True):
        for ov in (None, False, True):
            step = compiled_sync(
                mesh, dataclasses.replace(dyn, repack=rp, overlap=ov),
                plan, eta)
            mem_, applied = mem0, []
            for k_live in ks:  # chained steps across the live-k switch
                upd, mem_ = step(mem_, gs,
                                 jnp.asarray([1, k_live], jnp.int32))
                applied.append(_applied(tree, upd))
            outs[(rp, ov)] = (applied, mem_)
    ref = outs[(False, None)]
    repack_bitwise = all(bitwise_equal(ref, v) for v in outs.values())

    # host transport on a real k_max-padded pod summary: bucket 1 (the
    # sparse one), tail masked to (-0.0, 0) past the live k
    spec = plan.buckets[1]
    k_max = dyn.pod_k_max_for_bucket(1, spec.cols, n_data)
    k_live = int(ks[-1])
    u = jax.random.normal(jax.random.PRNGKey(3), (spec.rows, spec.cols))
    _, idx = jax.lax.top_k(jnp.abs(u), k_max)
    vals = jnp.take_along_axis(u, idx, axis=-1)
    vals, idx = mask_live_k(vals, idx.astype(jnp.int32), k_live)
    wspec = enc.WireSpec(spec.rows, spec.cols, k_max)
    buf = enc.encode(wspec, vals, idx, live_n=k_live)
    out_inline, nb_inline = repack_transport(wspec, buf)
    link = pipeline.EmulatedLink(latency_s=0.0)
    fut, nb_link = repack_transport(wspec, buf, link=link)
    roundtrip = (bitwise_equal(out_inline, buf)
                 and bitwise_equal(fut.result(), buf))
    live_bytes = enc.message_nbytes(
        spec.rows, spec.cols, k_live, "float32", "packed")
    lv = bucketed_message_bytes(dyn, plan, by_level=True, n_data=n_data,
                                pod_ks=[1, k_live])
    dense_cross = plan.buckets[0].rows * plan.buckets[0].cols * 4
    acc_ok = (nb_inline == live_bytes and nb_link == live_bytes
              and lv["cross"] - dense_cross == live_bytes)
    return {
        "repack_bitwise": bool(repack_bitwise),
        "transport_roundtrip_bitwise": bool(roundtrip),
        "transport_accounting_exact": bool(acc_ok),
        "padded_vs_live_bytes": [wspec.nbytes, live_bytes],
    }
