#!/usr/bin/env python3
"""Chip smoke: data-parallel Mem-SGD training of rwkv6-3b at its published
widths on a TPU, plus every Pallas kernel compiled for the chip.

    python chip_smoke.py             # one chip: train + kernel phases
    python chip_smoke.py --chips 4   # (4, 1) data mesh: topk vs dense only
    python chip_smoke.py --kernel-sweep  # one chip: fused kernel times only

One chip (default):

1. ``train`` — rwkv6-3b at published widths (d_model 2560, d_ff 8960,
   vocab 65536, head_dim 64), depth cut to ``LAYERS``, trained for
   ``STEPS`` steps through ``make_train_step`` (the step ``train()`` and
   the CLI use) with the ``topk`` preset (bucketed, packed wire) on a
   (1, 1) data mesh. Checks: every loss finite, the last below the
   first, and Mem-SGD's mass conservation on step 1 — against a
   separately jitted ``jax.grad`` (the step's f32 twin on a probe batch)
   and between two runs of the trained step itself (the selection).
2. ``kernels`` — each Pallas kernel at the bucket shape of that model
   (``bucket_cols=1024``), compiled (never interpreted), run through
   ``repro.kernels.ops`` and compared bitwise with ``repro.kernels.ref``.

``--chips 4`` trains the same config on a (4, 1) data mesh at global
batch 32 with the ``topk`` preset and then the ``dense`` preset on the
same batches, and checks where the state lives.

``--kernel-sweep`` (one chip) times the fused Mem-SGD kernel alone at
the sparse bucket of granite-3-8b (published widths, 2 layers: the
benchmark's configuration), over k, with the loop select against the
threshold select: the data for the TPU entry of
``repro.utils.platform.TOPK_LOOP_CUTOVER``. Nothing is trained.

The last stdout line is one JSON object naming the device. The script
exits non-zero, with no such line, when JAX finds no TPU, when
``REPRO_PALLAS_INTERPRET=1`` is set, outside a checkout of the repo, or
when any check fails. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "rwkv6-3b"
SIZE = "full"  # the published config
LAYERS = 2  # depth cut; every width stays published
SEQ = 512
BATCH_PER_CHIP = 8
STEPS = 6
RATIO = 0.01  # k = 10 of every 1024-column bucket row
ETA = 0.3
KERNEL_ETA = 0.37
TIMED_CALLS = 5  # timed kernel calls after the first
SWEEP_ARCH = "granite-3-8b"  # --kernel-sweep: whose bucket it times
SWEEP_KS = (10, 32, 64)
# Step-1 conservation, gated twice (see _gradient_gate, _conserved_step1).
# 1. Against a separately jitted jax.grad: the same make_train_step with
#    the model in f32 at HIGHEST matmul precision, on the first
#    PROBE_BATCH sequences of batch 1 (an f32 step at batch 8 needs
#    4.1 + 12.1 GB on a 16 GB chip; at 2, 4.1 + 7.9 GB). In f32 the two
#    compilations differ only by f32 rounding (~6e-8) times this model's
#    sensitivity of the gradient to its inputs (~1e3, measured in f32 on
#    the CPU), so 1e-2 leaves 100x headroom; a dropped selection moves
#    the residual by >= 0.5, a half batch or a wrong mean by ~1. At the
#    config's bf16 the same comparison cannot be tight: rwkv6-3b's bf16
#    gradient at init is 65-160 % per leaf from its f32 gradient on a
#    v5e (see PERF.md), so it is printed against the f32 one, not gated.
GRAD_RTOL = 1e-2
PROBE_BATCH = 2
# 2. Selection: the bf16 step run twice from the same params and batch
#    (zero and seeded random memory) holds the same eta * g bitwise, so
#    the two residuals differ only by f32 rounding — of the applied
#    update read back as p0 - p1 (half an ulp of p0 per selected entry,
#    ~1e-6 of an entry of eta * g at init) and of u = m0 + eta * g.
SELECTION_RTOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _refuse_environment() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        sys.exit(f"chip_smoke: no repro package under {SRC}; run it from a "
                 "checkout of the repository")
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        sys.exit("chip_smoke: REPRO_PALLAS_INTERPRET=1 would run the Pallas "
                 "kernels in interpret mode; the smoke runs them compiled")


def _device_info(jax, chips: int) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {d0.platform!r})")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


def _peak_bytes(jax) -> dict:
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()}


def _setup_train(n_chips: int, preset: str):
    """Model, mesh, train config, sharded state and the jitted step."""
    import jax

    from repro.configs import sized_config
    from repro.core.distributed import SyncConfig
    from repro.launch.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
        state_shardings,
    )
    from repro.models import build_model
    from repro.utils import compat

    cfg = sized_config(ARCH, SIZE, LAYERS)
    model = build_model(cfg)
    mesh = compat.make_mesh((n_chips, 1), ("data", "model"),
                            devices=jax.devices()[:n_chips])
    tc = TrainConfig(optimizer="memsgd", eta=ETA,
                     sync=SyncConfig.preset(preset, ratio=RATIO))
    shardings = state_shardings(model, mesh, tc)
    # built straight into its shardings: no (W, ...) memory on one chip
    state = jax.jit(
        lambda: init_train_state(model, mesh, tc,
                                 rng=jax.random.PRNGKey(0)),
        out_shardings=shardings,
    )()
    step = make_train_step(model, mesh, tc)
    return cfg, model, mesh, tc, state, step


def _batches(mesh, vocab: int, global_batch: int, n: int):
    from repro.data import token_batches
    from repro.data.pipeline import ShardedBatcher, take

    stream = ShardedBatcher(
        mesh, token_batches(vocab, global_batch, SEQ, seed=0),
        batch_axes=("data",))
    return list(take(stream, n))


def _compile(step, state, batch, label: str):
    """AOT-compile the jitted step for these shapes, timed on its own."""
    t0 = time.perf_counter()
    compiled = step.lower(*state, batch).compile()
    compile_s = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    if mem is not None:
        log(f"[{label}] compiled step: arguments "
            f"{mem.argument_size_in_bytes} B, temporaries "
            f"{mem.temp_size_in_bytes} B, outputs "
            f"{mem.output_size_in_bytes} B, aliased "
            f"{mem.alias_size_in_bytes} B")
    log(f"[{label}] compile_s {compile_s:.3f}")
    return compiled, compile_s


def _step(compiled, state, batch):
    """One step; returns (new state, loss, seconds)."""
    import jax

    t0 = time.perf_counter()
    *state, metrics = compiled(*state, batch)
    loss = float(jax.block_until_ready(metrics["loss"]))
    return tuple(state), loss, time.perf_counter() - t0


def _train(compiled, state, batches, label: str, first: int = 1):
    losses, step_s = [], []
    for i, batch in enumerate(batches):
        state, loss, dt = _step(compiled, state, batch)
        losses.append(loss)
        step_s.append(dt)
        log(f"[{label}] step {first + i} loss {loss!r} step_s {dt:.4f}")
    return state, losses, step_s


def _check_losses(losses, label: str) -> None:
    import math

    check(all(math.isfinite(x) for x in losses),
          f"[{label}] non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"[{label}] loss did not fall: first {losses[0]!r}, last "
          f"{losses[-1]!r}")


def _pack_host(plan, tree):
    """``buckets.pack`` on the host (numpy f32): leaf-flat, zero tail."""
    import numpy as np

    bufs = [np.zeros(s.rows * s.cols, np.float32) for s in plan.buckets]
    for leaf, pl in zip(plan.treedef.flatten_up_to(tree), plan.placements):
        bufs[pl.bucket][pl.offset:pl.offset + pl.size] = np.ravel(leaf)
    return [b.reshape(s.shape) for b, s in zip(bufs, plan.buckets)]


def _norm(bufs) -> float:
    import numpy as np

    return float(np.sqrt(sum(np.sum(np.square(b, dtype=np.float64))
                             for b in bufs)))


def _scaled_rel(a, b) -> tuple:
    """(||a - b|| / ||b||, least-squares scale <a, b> / ||b||^2)."""
    import numpy as np

    nb = _norm(b)
    dot = sum(float(np.vdot(x.astype(np.float64), y)) for x, y in zip(a, b))
    return _norm([x - y for x, y in zip(a, b)]) / nb, dot / nb ** 2


def _leaf(bufs, placement) -> list:
    """One parameter's entries out of packed buckets, as a one-buffer
    list (f64)."""
    import numpy as np

    x = np.ravel(bufs[placement.bucket])
    return [x[placement.offset:placement.offset + placement.size]
            .astype(np.float64)]


def _residual(plan, p0, p1, m1, m0):
    """Mem-SGD's applied update + new memory - old memory, packed (host),
    and the norm of the applied update."""
    import jax
    import numpy as np

    p1, m1 = jax.device_get((p1, m1))
    applied = _pack_host(plan, jax.tree.map(np.subtract, p0, p1))
    return ([a + m[0] - z for a, m, z in zip(applied, m1, m0)],
            _norm(applied))


def _grad_fn(model, plan):
    """Separately jitted (loss, tokens, packed f32 gradient) of
    ``model.loss``."""
    import jax
    import jax.numpy as jnp

    from repro.core import buckets as bk

    def f(params, batch):
        (loss, met), g = jax.value_and_grad(
            lambda q: model.loss(q, batch), has_aux=True)(params)
        return loss, met["n_tokens"], bk.pack(plan, g, dtype=jnp.float32)

    return jax.jit(f)


def _gradient_gate(model32, mesh, tc, plan, state, batch):
    """Step 1 of the f32 twin of the trained step against a separately
    jitted f32 jax.grad, both at HIGHEST matmul precision, on a probe
    batch: applied update + new memory - 0 == eta * g (GRAD_RTOL).

    Returns the state as it came in (its buffers were donated to the
    probe and are rebuilt) and the f32 gradient function, reused for the
    reference gradient of the bf16 step."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.train import make_train_step

    params, memory, opt, count = state
    p0 = jax.device_get(params)
    probe = {k: jax.device_put(np.asarray(x)[:PROBE_BATCH], x.sharding)
             for k, x in batch.items()}
    with jax.default_matmul_precision("highest"):
        step32 = make_train_step(model32, mesh, tc)
        t0 = time.perf_counter()
        compiled32 = step32.lower(*state, probe).compile()
        grad32 = _grad_fn(model32, plan)
        loss_g, _, g = grad32(params, probe)
        g = [np.asarray(x) for x in jax.device_get(g)]
    log(f"[train] f32 probe (batch {PROBE_BATCH}, HIGHEST): step and "
        f"jax.grad compiled in {time.perf_counter() - t0:.3f} s")
    eta = np.float32(tc.eta)
    mem_sh = [m.sharding for m in memory]
    p_sh = jax.tree.map(lambda a: a.sharding, params)
    p1, m1, _, _, met = compiled32(params, memory, opt, count, probe)
    r, applied = _residual(plan, p0, p1, m1, [0.0] * len(g))
    del p1, m1, compiled32
    eg = [eta * x for x in g]
    err, scale = _scaled_rel(r, eg)
    margin = applied / _norm(eg)
    log(f"[train] gradient gate (f32 probe): ||applied + m1 - eta*g|| / "
        f"||eta*g|| = {err!r} (rtol {GRAD_RTOL}), least-squares scale "
        f"{scale!r}; a dropped selection would give >= {margin!r}; loss "
        f"step {float(met['loss'])!r} jax.grad {float(loss_g)!r}")
    check(err <= GRAD_RTOL, f"gradient gate broken: {err!r}")
    check(margin >= 10 * GRAD_RTOL,
          f"gradient gate has no margin: {margin!r}")
    params = jax.device_put(p0, p_sh)
    memory = tuple(jnp.zeros(m.shape, m.dtype, device=sh)
                   for m, sh in zip(memory, mem_sh))
    return (params, memory, opt, count), grad32


def _f32_reference(grad32, params, batch):
    """The f32 HIGHEST gradient of the whole batch, from PROBE_BATCH
    slices weighted by their token counts (host f64)."""
    import jax
    import numpy as np

    n = next(iter(batch.values())).shape[0]
    acc, total = None, 0.0
    with jax.default_matmul_precision("highest"):
        for i in range(0, n, PROBE_BATCH):
            sl = {k: jax.device_put(np.asarray(x)[i:i + PROBE_BATCH],
                                    x.sharding) for k, x in batch.items()}
            _, ntok, g = grad32(params, sl)
            w = float(ntok)
            g = [w * np.asarray(x, np.float64) for x in jax.device_get(g)]
            acc = g if acc is None else [a + x for a, x in zip(acc, g)]
            total += w
    return [a / total for a in acc]


def _conserved_step1(compiled, model, tc, plan, state, batch, grad32):
    """Step 1 of the trained (bf16) step with the selection check.

    The same compiled step runs twice from the same params and batch:
    run B from a seeded random memory M (a probe, discarded), run A from
    the zero memory of a fresh start (the real step 1, training goes on
    from it). Both runs compute the same eta * g, bitwise (same
    executable, same inputs), but select different entries, so
        (p0 - p1_A) + m1_A - 0  ==  (p0 - p1_B) + m1_B - M
    up to f32 rounding; a dropped or doubled selection in either run
    breaks it by ||selection|| / ||eta g||, printed as the margin.

    Then printed, not gated: the step's eta * g against a separately
    jitted bf16 jax.grad and both against the f32 HIGHEST gradient of
    the same batch, in total and per leaf.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    params, memory, opt, count = state
    check(not any(bool(jnp.any(m != 0)) for m in memory),
          "memory is not zero before step 1")
    eta = np.float32(tc.eta)
    p0 = jax.device_get(params)
    loss_sep, _, g_sep = _grad_fn(model, plan)(params, batch)
    g_sep = [np.asarray(b) for b in jax.device_get(g_sep)]
    g_ref = _f32_reference(grad32, params, batch)
    rng = np.random.default_rng(0)
    M = [(rng.standard_normal(g.shape, dtype=np.float32)
          * np.float32(np.sqrt(np.mean(np.square(eta * g))) or 1.0))
         for g in g_sep]
    mem_sh = [m.sharding for m in memory]
    p_sh = jax.tree.map(lambda a: a.sharding, params)

    # run B: the probe from memory M (params and M are donated)
    m_probe = tuple(jax.device_put(x[None], sh) for x, sh in zip(M, mem_sh))
    (p1, m1, _, _), _, _ = _step(compiled, (params, m_probe, opt, count),
                                 batch)
    r_b, applied_b = _residual(plan, p0, p1, m1, M)
    del p1, m1, m_probe
    # run A: the real step 1 from a fresh start
    params = jax.device_put(p0, p_sh)
    memory = tuple(jnp.zeros(m.shape, m.dtype, device=sh)
                   for m, sh in zip(memory, mem_sh))
    state, loss, dt = _step(compiled, (params, memory, opt, count), batch)
    r_a, applied_a = _residual(plan, p0, state[0], state[1], [0.0] * len(M))
    ref = _norm(r_a)
    err = _norm([a - b for a, b in zip(r_a, r_b)]) / ref
    margin = min(applied_a, applied_b) / ref
    log(f"[train] selection check step 1: ||r_A - r_B|| / ||r_A|| = "
        f"{err!r} (rtol {SELECTION_RTOL}); a dropped or doubled selection "
        f"would give >= {margin!r}")
    check(err <= SELECTION_RTOL, f"selection conservation broken: {err!r}")
    check(margin >= 100 * SELECTION_RTOL,
          f"selection check has no margin: {margin!r}")

    g_step = [a / eta for a in r_a]
    rel, scale = _scaled_rel(g_step, g_sep)
    log(f"[train] step-1 gradient (batch {BATCH_PER_CHIP}, not gated): "
        f"loss step {loss!r} jax.grad bf16 {float(loss_sep)!r}; step vs "
        f"separately jitted bf16 jax.grad: relative difference {rel!r}, "
        f"least-squares scale {scale!r}")
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(p0)[0]]
    for label, g in (("step", g_step), ("jax.grad bf16", g_sep)):
        rel, scale = _scaled_rel(g, g_ref)
        log(f"[train]   {label} vs f32 HIGHEST jax.grad: relative "
            f"difference {rel!r}, least-squares scale {scale!r}")
    log("[train]   per leaf, relative difference to the f32 gradient "
        "(step | jax.grad bf16 | ||g_f32||):")
    for name, pl in zip(names, plan.placements):
        ref_leaf = _leaf(g_ref, pl)
        log(f"[train]     {name} "
            f"{_scaled_rel(_leaf(g_step, pl), ref_leaf)[0]:.4g} | "
            f"{_scaled_rel(_leaf(g_sep, pl), ref_leaf)[0]:.4g} | "
            f"{_norm(ref_leaf):.4g}")
    return state, loss, dt


def phase_train_one_chip(jax) -> dict:
    import numpy as np

    from repro.core import buckets as bk
    from repro.models import build_model

    cfg, model, mesh, tc, state, step = _setup_train(1, "topk")
    log(f"[train] {ARCH}: d_model {cfg.d_model} d_ff {cfg.d_ff} vocab "
        f"{cfg.vocab_size} heads {cfg.n_heads} head_dim "
        f"{cfg.rwkv.head_dim} layers {cfg.n_layers} params "
        f"{model.n_params()} | preset topk (bucketed, wire "
        f"{tc.sync.wire}) ratio {RATIO} eta {ETA} | batch "
        f"{BATCH_PER_CHIP} x seq {SEQ} on mesh {dict(mesh.shape)}")
    check(tc.sync.bucketed and tc.sync.wire == "packed",
          "topk preset is not bucketed over the packed wire")
    plan = bk.make_plan(model.param_shapes(), cols=tc.sync.bucket_cols,
                        dense_below=tc.sync.dense_below)
    batches = _batches(mesh, cfg.vocab_size, BATCH_PER_CHIP, STEPS)
    compiled, compile_s = _compile(step, state, batches[0], "train")
    model32 = build_model(cfg.replace(compute_dtype="float32"))
    state, grad32 = _gradient_gate(model32, mesh, tc, plan, state,
                                   batches[0])
    state, loss1, dt1 = _conserved_step1(compiled, model, tc, plan, state,
                                         batches[0], grad32)
    del grad32
    log(f"[train] step 1 loss {loss1!r} step_s {dt1:.4f}")
    state, losses, step_s = _train(compiled, state, batches[1:], "train",
                                   first=2)
    losses = [loss1] + losses
    _check_losses(losses, "train")
    params, memory, _, _ = state
    sparse = [b for b, s in enumerate(plan.buckets) if s.kind == "sparse"]
    check(len(sparse) == 1, f"expected one f32 sparse bucket: {plan.buckets}")
    b = sparse[0]
    out = {"m": memory[b][0], "k": tc.sync.k_for(plan.buckets[b].cols),
           "shape": plan.buckets[b].shape}
    del params, memory, state, batches
    log(f"[train] peak_bytes_in_use {_peak_bytes(jax)}")
    log(f"[train] losses {losses} compile_s {compile_s:.3f} median step_s "
        f"(steps 2-{STEPS}) {float(np.median(step_s)):.4f}")
    return out


def phase_kernels(jax, inputs: dict) -> None:
    import jax.numpy as jnp

    from repro.kernels import ops, ref
    from repro.kernels.topk_select import row_topk_pallas

    k = inputs["k"]
    # whole 8-row blocks (the kernels' row block), so no padded copy of
    # the 2 GB bucket has to fit beside it
    m = inputs.pop("m")
    r8 = m.shape[0] // 8 * 8
    m = m[:r8]
    # a gradient-sized input for the fused kernel, made on the device
    g = jax.random.normal(jax.random.PRNGKey(1), m.shape, m.dtype) * jnp.std(m)
    log(f"[kernels] bucket {inputs['shape']} (first {r8} rows) k {k} eta "
        f"{KERNEL_ETA}")

    def bitwise(a, b) -> bool:
        if a.dtype == jnp.float32:
            a = jax.lax.bitcast_convert_type(a, jnp.int32)
            b = jax.lax.bitcast_convert_type(b, jnp.int32)
        return bool(jnp.array_equal(a, b))

    def run(name, fn, static, args, want):
        compiled = fn.lower(*args, **static).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"[kernels] {name}: no tpu_custom_call in the compiled text")
        got, first, median = _timed(jax, compiled, args)
        same = [bitwise(a, b) for a, b in zip(got, want)]
        log(f"[kernels] {name}: compiled (tpu_custom_call), bitwise equal "
            f"to ref: {all(same)} {same}, first call {first:.4f} s, median "
            f"of {TIMED_CALLS} more {median:.6f} s")
        check(all(same), f"[kernels] {name} differs from ref.py")

    want = jax.block_until_ready(ref.row_topk_ref(m, k))
    for method in ("loop", "threshold"):
        run(f"ops.row_topk(method={method!r})", ops.row_topk,
            {"k": k, "method": method}, (m,), want)
    # the whole-row threshold kernel (ops routes threshold to the tiled
    # form; its in-tile selection is the one the fused kernel runs)
    run("row_topk_pallas(selection='threshold')",
        jax.jit(row_topk_pallas, static_argnames=("k", "selection")),
        {"k": k, "selection": "threshold"}, (m,), want)
    del want
    eta = jnp.float32(KERNEL_ETA)
    want = jax.block_until_ready(ref.fused_memsgd_ref(m, g, eta, k))
    for method in ("loop", "threshold"):
        run(f"ops.fused_memsgd_update(method={method!r})",
            ops.fused_memsgd_update, {"k": k, "method": method},
            (m, g, eta), want)
    log(f"[kernels] peak_bytes_in_use {_peak_bytes(jax)}")


def _timed(jax, compiled, args):
    """(outputs, first-call seconds, median seconds of TIMED_CALLS more)."""
    import numpy as np

    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    first = time.perf_counter() - t0
    ts = []
    for _ in range(TIMED_CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append(time.perf_counter() - t0)
    return out, first, float(np.median(ts))


def phase_kernel_sweep(jax) -> None:
    """The fused kernel (``ops.fused_memsgd_update``) at SWEEP_ARCH's
    sparse bucket, loop against threshold select, for each k of
    SWEEP_KS; both must agree bitwise."""
    import jax.numpy as jnp

    from repro.configs import sized_config
    from repro.core import buckets as bk
    from repro.kernels import ops
    from repro.models import build_model

    cfg = sized_config(SWEEP_ARCH, SIZE, LAYERS).replace(tie_embeddings=True)
    plan = bk.make_plan(build_model(cfg).param_shapes(), cols=1024)
    (spec,) = [s for s in plan.buckets if s.kind == "sparse"]
    key = jax.random.PRNGKey(0)
    m = jax.random.normal(key, spec.shape, jnp.float32) * 1e-3
    # g flat, as the train step packs it (core.buckets.pack)
    g = jax.random.normal(jax.random.fold_in(key, 1),
                          (spec.rows * spec.cols,), jnp.float32)
    eta = jnp.float32(KERNEL_ETA)

    @jax.jit
    def same_bits(a, b):  # one fused reduction: no bucket-sized copy
        if a.dtype == jnp.float32:
            a = jax.lax.bitcast_convert_type(a, jnp.int32)
            b = jax.lax.bitcast_convert_type(b, jnp.int32)
        return jnp.array_equal(a, b)

    # the bucket's bytes through HBM once (read m and g, write m'), as
    # the floor every fused kernel is read against
    floor = jax.jit(lambda m, g, e: m + e * g.reshape(m.shape)).lower(
        m, g, eta).compile()
    _, _, floor_s = _timed(jax, floor, (m, g, eta))
    log(f"[sweep] {SWEEP_ARCH} bucket {spec.shape}: m + eta*g alone "
        f"{floor_s:.6f} s (median of {TIMED_CALLS})")
    for k in SWEEP_KS:
        got, sec = {}, {}
        for method in ("loop", "threshold"):
            compiled = jax.jit(lambda m, g, e, method=method: (
                ops.fused_memsgd_update(m, g.reshape(m.shape), e, k,
                                        method=method))).lower(
                m, g, eta).compile()
            got[method], _, sec[method] = _timed(jax, compiled, (m, g, eta))
        same = all(bool(same_bits(a, b)) for a, b in zip(got["loop"],
                                                          got["threshold"]))
        del got
        log(f"[sweep] {spec.shape[0]} x {spec.shape[1]} k {k}: loop "
            f"{sec['loop']:.6f} s threshold {sec['threshold']:.6f} s "
            f"(median of {TIMED_CALLS}); loop/threshold "
            f"{sec['loop'] / sec['threshold']:.3f}; bitwise equal {same}")
        check(same, f"[sweep] loop and threshold differ at k {k}")


def phase_four_chips(jax) -> None:
    import numpy as np

    devs = jax.devices()[:4]
    results = {}
    batches = None
    for preset in ("topk", "dense"):
        cfg, model, mesh, tc, state, step = _setup_train(4, preset)
        if batches is None:
            batches = _batches(mesh, cfg.vocab_size, 4 * BATCH_PER_CHIP,
                               STEPS)
            log(f"[4chip] {ARCH}: d_model {cfg.d_model} vocab "
                f"{cfg.vocab_size} layers {cfg.n_layers} | global batch "
                f"{4 * BATCH_PER_CHIP} x seq {SEQ} on mesh "
                f"{dict(mesh.shape)}")
        for i, bt in enumerate(batches):
            for name, x in bt.items():
                check(x.sharding.device_set == set(devs)
                      and not x.sharding.is_fully_replicated,
                      f"batch {i} {name!r} is not split over 4 devices")
        params, memory = state[0], state[1]
        for p in jax.tree.leaves(params):
            check(p.sharding.device_set == set(devs)
                  and p.sharding.is_fully_replicated,
                  f"[{preset}] a param is not replicated on 4 devices")
        for m in jax.tree.leaves(memory):
            check(m.sharding.device_set == set(devs)
                  and not m.sharding.is_fully_replicated
                  and len({s.device for s in m.addressable_shards}) == 4,
                  f"[{preset}] a memory buffer is not spread over 4 "
                  f"devices")
        log(f"[4chip/{preset}] params replicated on devices "
            f"{sorted(d.id for d in devs)}; {len(jax.tree.leaves(memory))} "
            f"memory buffers and the batch split over all 4 "
            f"(memory spec {jax.tree.leaves(memory)[0].sharding.spec})")
        del params, memory
        compiled, compile_s = _compile(step, state, batches[0],
                                       f"4chip/{preset}")
        state, losses, step_s = _train(compiled, state, batches,
                                       f"4chip/{preset}")
        _check_losses(losses, f"4chip/{preset}")
        params, memory = state[0], state[1]
        for m in jax.tree.leaves(memory):
            check(m.sharding.device_set == set(devs)
                  and not m.sharding.is_fully_replicated,
                  f"[{preset}] memory left the 4-device split after "
                  f"training")
        for p in jax.tree.leaves(params):
            check(p.sharding.is_fully_replicated
                  and p.sharding.device_set == set(devs),
                  f"[{preset}] params not replicated after training")
        results[preset] = losses
        log(f"[4chip/{preset}] losses {losses} compile_s {compile_s:.3f} "
            f"median step_s {float(np.median(step_s[1:])):.4f}")
        del state, params, memory, step, compiled
        log(f"[4chip/{preset}] peak_bytes_in_use {_peak_bytes(jax)}")
    log(f"[4chip] final loss topk {results['topk'][-1]!r} dense "
        f"{results['dense'][-1]!r}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--kernel-sweep", action="store_true",
                    help="one chip: time only the fused Mem-SGD kernel, "
                    "loop against threshold select over k, at "
                    f"{SWEEP_ARCH}'s bucket")
    args = ap.parse_args()
    _refuse_environment()
    sys.path.insert(0, SRC)
    from repro.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    device = _device_info(jax, args.chips)
    log(f"device kind {device['kind']!r} count {device['count']} "
        f"(platform {device['platform']}); compile cache {cache}")
    if args.chips == 4:
        phase_four_chips(jax)
    elif args.kernel_sweep:
        phase_kernel_sweep(jax)
    else:
        phase_kernels(jax, phase_train_one_chip(jax))
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
