"""Distributed training step + driver (PARALLEL-MEM-SGD on a TPU mesh).

``make_train_step`` builds the jitted step:

  * OUTER: ``jax.jit`` with NamedShardings (params tensor-parallel over
    "model", batch + per-worker memory over the data axes).
  * INNER: ``jax.shard_map`` manual over the data axes, auto over "model".
    Each data shard computes its own gradient (GSPMD handles the model
    axis inside), applies error-feedback memory + row-block top-k, and the
    shards exchange only (values, indices) pairs (sparse all-gather). See
    ``repro.core.distributed``.

Optimizer modes:
  * ``memsgd``       — paper Algorithm 1/2: update = comp_k(m + eta*g),
    params -= mean_w(update). eta consumed at memory insertion.
  * ``memsgd_momentum`` — beyond-paper: heavy-ball momentum applied to the
    synced sparse update.
  * ``adam_compressed`` — beyond-paper: the sync (with eta=1) produces the
    averaged sparse gradient; Adam consumes it. Memory semantics preserved.
  * ``dense``        — vanilla data-parallel baseline (dense all-reduce),
    for communication comparisons.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import PodRefreshConfig
from repro.core import buckets as bk
from repro.core.distributed import (
    SyncConfig,
    bucketed_sync_gradients,
    sparse_sync_gradients,
)
from repro.launch import sharding as shd
from repro.utils import compat
from repro.utils.telemetry import NonFiniteLossError, Telemetry

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "memsgd"  # memsgd | memsgd_momentum | adam_compressed | dense
    eta: float = 0.1  # base stepsize (or peak LR for adam)
    eta_shift: float = 0.0  # a>0 enables eta_t = eta/(1 + t/a) style decay
    momentum: float = 0.9
    sync: SyncConfig = dataclasses.field(default_factory=SyncConfig)
    # Perf levers (see EXPERIMENTS.md §Perf):
    seq_shard_activations: bool = False  # Megatron-style sequence parallel
    microbatch: int = 1  # gradient accumulation over the local batch
    moe_ep_constraints: bool = False  # expert-parallel a2a dispatch
    # Emit the applied update as packed per-bucket delta messages for
    # serving replicas (repro.launch.delta_stream). Requires
    # sync.bucketed and optimizer="memsgd"/"dense" (the only modes whose
    # parameter delta equals the synced update). The step then returns a
    # sixth output: a tuple of uint32 wire buffers.
    emit_deltas: bool = False
    delta_value_dtype: str = "float32"  # bf16 halves the stream (lossy)
    # Two-level pod sync: autotune per-bucket pod re-compression ratios
    # (SyncConfig.pod_ratios) from the first batch's realized gradient
    # mass capture when training hierarchical+bucketed on a pod mesh and
    # no explicit ratios were given (see
    # repro.core.distributed.autotune_pod_ratios).
    pod_autotune: bool = True
    # Live pod-ratio refresh (configs.PodRefreshConfig): re-run the
    # autotune every N steps on the live memory+gradient bucket buffers
    # and feed the new per-bucket pod ks into the RUNNING jitted step —
    # the k-padded wire (SyncConfig.pod_dynamic, forced on when enabled)
    # makes the live k a plain data input, so no step ever re-jits.
    pod_refresh: Optional[PodRefreshConfig] = None
    # Base seed for the QSGD stochastic-rounding PRNG (WireConfig.quant):
    # the step folds the step count in, each quantize stage folds its
    # bucket/level/axis indices — two runs with the same seed draw the
    # same rounding noise (reproducible quantized training).
    quant_seed: int = 0


def _eta_schedule(tc: TrainConfig):
    if tc.eta_shift > 0:
        a = tc.eta_shift
        return lambda t: tc.eta * a / (a + t.astype(jnp.float32))
    return lambda t: jnp.asarray(tc.eta, jnp.float32)


def _eta_at(tc: TrainConfig, t: int) -> float:
    """Host-side mirror of ``_eta_schedule`` for the refresh path.

    The calibration branch needs eta_t on host; syncing the device step
    count with ``float(...)`` mid-loop would stall the dispatch queue
    (RL001), and the loop index is the same value already on host —
    ``count`` starts at 0 in ``train()`` and every step variant
    (step/sync/accum) increments it exactly once per dispatched step.
    """
    if tc.eta_shift > 0:
        return float(tc.eta * tc.eta_shift / (tc.eta_shift + float(t)))
    return float(tc.eta)


def _worker_count(mesh, data_axes) -> int:
    n = 1
    for a in data_axes:
        n *= mesh.shape[a]
    return n


def _bucket_plan(tc: TrainConfig, pshapes):
    """BucketPlan for the flat-buffer sync path (None when disabled)."""
    if not tc.sync.bucketed:
        return None
    return bk.make_plan(
        pshapes, cols=tc.sync.bucket_cols, dense_below=tc.sync.dense_below
    )


def init_train_state(model, mesh, tc: TrainConfig, rng=None, abstract=False):
    """Returns (params, memory, opt_state, count) — concrete or abstract.

    With ``tc.sync.bucketed`` the per-worker error-feedback memory is a
    tuple of (W, rows, cols) bucket buffers instead of a param-shaped
    pytree (see ``repro.core.buckets``).
    """
    data_axes = (("pod",) if "pod" in mesh.axis_names else ()) + ("data",)
    W = _worker_count(mesh, data_axes)
    pshapes = model.param_shapes()
    plan = _bucket_plan(tc, pshapes)

    def make():
        params = model.init(rng if rng is not None else jax.random.PRNGKey(0))
        if plan is not None:
            memory = tuple(
                jnp.zeros((W,) + spec.shape, jnp.float32)
                for spec in plan.buckets
            )
        else:
            memory = jax.tree.map(
                lambda p: jnp.zeros((W,) + p.shape, jnp.float32), params
            )
        if tc.optimizer == "memsgd_momentum":
            opt = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        elif tc.optimizer == "adam_compressed":
            opt = {
                "mu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
                "nu": jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            }
        else:
            opt = ()
        return params, memory, opt, jnp.zeros((), jnp.int32)

    if abstract:
        return jax.eval_shape(make)
    return make()


def state_shardings(model, mesh, tc: TrainConfig):
    """NamedSharding pytrees for (params, memory, opt, count)."""
    data_axes = (("pod",) if "pod" in mesh.axis_names else ()) + ("data",)
    pshapes = model.param_shapes()
    pspecs = shd.drop_undivisible(shd.param_specs(pshapes), pshapes, mesh)
    worker = data_axes if len(data_axes) > 1 else data_axes[0]
    plan = _bucket_plan(tc, pshapes)
    if plan is not None:
        mspecs = tuple(P(worker) for _ in plan.buckets)
    else:
        mspecs = jax.tree.map(lambda s: P(worker, *s), pspecs,
                              is_leaf=lambda x: isinstance(x, P))
    if tc.optimizer == "memsgd_momentum":
        ospecs = pspecs
    elif tc.optimizer == "adam_compressed":
        ospecs = {"mu": pspecs, "nu": pspecs}
    else:
        ospecs = ()
    to_sharding = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t, is_leaf=lambda x: isinstance(x, P)
    )
    return (
        to_sharding(pspecs),
        to_sharding(mspecs),
        to_sharding(ospecs),
        NamedSharding(mesh, P()),
    )


def make_train_step(model, mesh, tc: TrainConfig):
    """Builds the jitted train step:

        (params, memory, opt, count, batch) ->
            (params, memory, opt, count, metrics)

    With ``tc.sync.pod_dynamic`` (runtime pod k — the live-refresh
    path) the step takes a sixth argument ``pod_ks``: an (n_buckets,)
    int32 array of live per-bucket pod ks, replicated. Its SHAPE is
    fixed by the bucket plan, so feeding a new schedule is a pure data
    change — the step never re-traces (``step._cache_size()`` stays 1).
    The static padded ceilings are exposed as ``step.pod_k_max``.

    With ``tc.sync.local_steps = H > 1`` (Qsparse-local-SGD) the state
    gains a bucket-space accumulator between memory and opt:

        (params, memory, acc, opt, count, batch) -> (... same ...)

    and TWO jitted functions come back: the returned ``step`` is the
    sync step (communicates once, resets ``acc``) and ``step.accum``
    is the local step (``acc += eta_t * pack(g_t)``, zero
    communication). Call ``step.accum`` H-1 times, then ``step``.
    With H == 1 the per-step path is returned literally unchanged —
    bitwise identical to previous behavior when quantization is off.
    """
    cfg = model.cfg
    data_axes = (("pod",) if "pod" in mesh.axis_names else ()) + ("data",)
    W = _worker_count(mesh, data_axes)
    pshapes = model.param_shapes()
    pspecs = shd.drop_undivisible(shd.param_specs(pshapes), pshapes, mesh)
    col_axes = shd.sync_col_axes(pshapes)
    plan = _bucket_plan(tc, pshapes)
    eta_fn = _eta_schedule(tc)
    sync_cfg = dataclasses.replace(
        tc.sync.with_pod(axis="pod" if "pod" in mesh.axis_names else None),
        data_axes=("data",),
        strategy="dense" if tc.optimizer == "dense" else tc.sync.strategy,
    )
    worker = data_axes if len(data_axes) > 1 else data_axes[0]
    batch_spec = P(worker)
    dyn = bool(sync_cfg.pod_dynamic)
    if dyn and (plan is None or sync_cfg.strategy != "hierarchical"
                or sync_cfg.pod_axis is None):
        raise ValueError(
            "sync.pod_dynamic (runtime pod k) requires sync.bucketed, "
            "strategy='hierarchical' and a (pod, data) mesh"
        )
    H = max(1, int(sync_cfg.local_steps))
    if H > 1 and plan is None:
        raise ValueError(
            "sync.local_steps > 1 requires sync.bucketed (the local "
            "accumulator lives in bucket space)"
        )
    quant = sync_cfg.quant
    sync_cfg.validate(plan) if plan is not None else sync_cfg.validate()
    pod_k_max = None
    if dyn:
        n_data_mesh = int(mesh.shape["data"])
        pod_k_max = tuple(
            sync_cfg.pod_k_max_for_bucket(b, s.cols, n_data_mesh)
            if s.kind == "sparse" else 1
            for b, s in enumerate(plan.buckets)
        )
    dspec = None
    if tc.emit_deltas:
        if plan is None or tc.optimizer not in ("memsgd", "dense"):
            raise ValueError(
                "emit_deltas requires sync.bucketed and a plain memsgd/"
                "dense optimizer (the parameter delta must equal the "
                "synced update)"
            )
        from repro.launch import delta_stream as ds

        dspec = ds.make_delta_spec(
            plan, sync_cfg, workers=W,
            n_pods=dict(mesh.shape).get("pod", 1),
            value_dtype=tc.delta_value_dtype,
        )

    def local_loss(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def _constrain_params(params):
        # params: full (model-auto) view; memory leaves (1, *shape) local
        return jax.tree.map(
            lambda p, s: jax.lax.with_sharding_constraint(p, s),
            params, pspecs, is_leaf=None,
        )

    def compute_grads(params, count, batch):
        tok = None
        moe_tok = None
        if tc.seq_shard_activations:
            tok = shd.set_activation_sharding(
                NamedSharding(mesh, P(None, "model", None))
            )
        if tc.moe_ep_constraints and cfg.moe is not None:
            moe_tok = shd.set_moe_sharding(
                NamedSharding(mesh, P(None, "model", None, None)),
                NamedSharding(mesh, P(None, None, None, None)),
                pre=None,  # token-pinning measured WORSE (§Perf C2)
            )
        try:
            if tc.microbatch > 1:
                M = tc.microbatch

                def split(x):
                    return x.reshape((M, x.shape[0] // M) + x.shape[1:])

                chunks = jax.tree.map(split, batch)
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params
                )

                def acc(carry, mb):
                    g, met = jax.grad(
                        lambda p: local_loss(p, mb), has_aux=True
                    )(params)
                    carry = jax.tree.map(
                        lambda c, gg: c + gg.astype(jnp.float32) / M, carry, g
                    )
                    return carry, met

                from repro.models.layers import layer_scan_unroll

                grads, mets = jax.lax.scan(
                    acc, zeros, chunks,
                    unroll=M if layer_scan_unroll() else 1,
                )
                metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), mets)
            else:
                grads, metrics = jax.grad(
                    lambda p: local_loss(p, batch), has_aux=True
                )(params)
        finally:
            if tok is not None:
                shd.reset_activation_sharding(tok)
            if moe_tok is not None:
                shd.reset_moe_sharding(moe_tok)
        if tc.optimizer in ("memsgd", "memsgd_momentum", "dense"):
            eta = eta_fn(count)
        else:  # adam_compressed: memory accumulates raw gradients
            eta = jnp.asarray(1.0, jnp.float32)
        return grads, eta, metrics

    def _quant_key(count):
        # per-step rounding-noise key; the sync stages fold in bucket /
        # level / axis indices on top (see distributed._fold_axes)
        if quant is None:
            return None
        return jax.random.fold_in(
            jax.random.PRNGKey(tc.quant_seed), count)

    def _mean_metrics(metrics):
        ax = data_axes if len(data_axes) > 1 else data_axes[0]
        return {
            "loss": jax.lax.pmean(metrics["xent"], ax),
            "aux": jax.lax.pmean(metrics["aux"], ax),
        }

    def apply_optimizer(params, opt, count, update):
        if tc.optimizer in ("memsgd", "dense"):
            new_params = jax.tree.map(
                lambda p, u: (p - u.astype(p.dtype)), params, update
            )
            new_opt = opt
        elif tc.optimizer == "memsgd_momentum":
            new_opt = jax.tree.map(
                lambda v, u: tc.momentum * v + u.astype(jnp.float32),
                opt, update,
            )
            new_params = jax.tree.map(
                lambda p, v: (p - v).astype(p.dtype), params, new_opt
            )
        elif tc.optimizer == "adam_compressed":
            t = count + 1
            b1, b2, eps = 0.9, 0.999, 1e-8
            mu = jax.tree.map(
                lambda m_, u: b1 * m_ + (1 - b1) * u.astype(jnp.float32),
                opt["mu"], update,
            )
            nu = jax.tree.map(
                lambda v, u: b2 * v + (1 - b2) * jnp.square(u.astype(jnp.float32)),
                opt["nu"], update,
            )
            bc1 = 1 - b1 ** t.astype(jnp.float32)
            bc2 = 1 - b2 ** t.astype(jnp.float32)
            lr = eta_fn(count)
            new_params = jax.tree.map(
                lambda p, m_, v: (
                    p - lr * (m_ / bc1) / (jnp.sqrt(v / bc2) + eps)
                ).astype(p.dtype),
                params, mu, nu,
            )
            new_opt = {"mu": mu, "nu": nu}
        else:
            raise ValueError(tc.optimizer)
        return new_params, new_opt

    def step_body(params, memory, opt, count, batch, pod_ks=None):
        params = _constrain_params(params)
        mem_local = jax.tree.map(lambda m_: m_[0], memory)
        grads, eta, metrics = compute_grads(params, count, batch)
        qkey = _quant_key(count)
        up_bufs = None
        if plan is not None and dspec is not None:
            update, new_mem, _, up_bufs = bucketed_sync_gradients(
                sync_cfg, plan, mem_local, grads, eta, return_bufs=True,
                pod_ks=pod_ks, quant_key=qkey,
            )
        elif plan is not None:
            update, new_mem, _ = bucketed_sync_gradients(
                sync_cfg, plan, mem_local, grads, eta, pod_ks=pod_ks,
                quant_key=qkey,
            )
        else:
            update, new_mem, _ = sparse_sync_gradients(
                sync_cfg, mem_local, grads, eta, col_axes,
                specs=pspecs, mesh=mesh,
            )
        new_params, new_opt = apply_optimizer(params, opt, count, update)
        new_memory = jax.tree.map(lambda m_: m_[None], new_mem)
        ret = (new_params, new_memory, new_opt, count + 1,
               _mean_metrics(metrics))
        if dspec is not None:
            # the gathered update is identical on every worker, so the
            # encoded wire buffers are replicated outputs (out_spec P())
            from repro.launch import delta_stream as ds

            ret += (tuple(ds.encode_delta_bufs(dspec, up_bufs)),)
        return ret

    def accum_body(params, memory, acc, opt, count, batch):
        # local step h < H: fold eta_t * g_t into the bucket-space
        # accumulator; no communication, params/memory/opt untouched
        params = _constrain_params(params)
        grads, eta, metrics = compute_grads(params, count, batch)
        acc_local = tuple(a[0] for a in acc)
        new_acc = tuple(
            a[None]
            for a in bk.accumulate_local(plan, acc_local, grads, eta)
        )
        return (params, memory, new_acc, opt, count + 1,
                _mean_metrics(metrics))

    def sync_body(params, memory, acc, opt, count, batch, pod_ks=None):
        # local step h == H: finish the accumulator, then one sync of
        # u = m + sum_h eta_h*g_h through top-k (-> QSGD quantize ->)
        # the packed wire; memory absorbs BOTH the sparsification
        # residual and the quantization error; accumulator resets
        params = _constrain_params(params)
        mem_local = jax.tree.map(lambda m_: m_[0], memory)
        grads, eta, metrics = compute_grads(params, count, batch)
        acc_local = tuple(a[0] for a in acc)
        u_bufs = bk.accumulate_local(plan, acc_local, grads, eta)
        qkey = _quant_key(count)
        one = jnp.asarray(1.0, jnp.float32)
        up_bufs = None
        if dspec is not None:
            update, new_mem, _, up_bufs = bucketed_sync_gradients(
                sync_cfg, plan, mem_local, grads, one, return_bufs=True,
                pod_ks=pod_ks, grad_bufs=u_bufs, quant_key=qkey,
            )
        else:
            update, new_mem, _ = bucketed_sync_gradients(
                sync_cfg, plan, mem_local, grads, one, pod_ks=pod_ks,
                grad_bufs=u_bufs, quant_key=qkey,
            )
        new_params, new_opt = apply_optimizer(params, opt, count, update)
        new_memory = jax.tree.map(lambda m_: m_[None], new_mem)
        zero_acc = tuple(jnp.zeros_like(a) for a in acc)
        ret = (new_params, new_memory, zero_acc, new_opt, count + 1,
               _mean_metrics(metrics))
        if dspec is not None:
            from repro.launch import delta_stream as ds

            ret += (tuple(ds.encode_delta_bufs(dspec, up_bufs)),)
        return ret

    pspec_P0 = jax.tree.map(lambda s: P(), pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    if plan is not None:
        mem_manual = tuple(P(worker) for _ in plan.buckets)
    else:
        mem_manual = jax.tree.map(lambda s: P(worker), pspecs,
                                  is_leaf=lambda x: isinstance(x, P))
    opt_P0 = jax.tree.map(lambda s: P(), shd.param_specs(pshapes),
                          is_leaf=lambda x: isinstance(x, P))
    if tc.optimizer == "memsgd_momentum":
        opt_in = opt_P0
    elif tc.optimizer == "adam_compressed":
        opt_in = {"mu": opt_P0, "nu": opt_P0}
    else:
        opt_in = ()

    model_specs = model.input_specs  # unused; batch spec built per leaf

    def batch_specs(batch_tree):
        return jax.tree.map(lambda _: batch_spec, batch_tree)

    out_specs = (pspec_P0, mem_manual, opt_in, P(),
                 {"loss": P(), "aux": P()})
    if dspec is not None:
        out_specs += (tuple(P() for _ in dspec.wires),)

    if H == 1:
        def step(params, memory, opt, count, batch, *pod_ks):
            # *pod_ks: exactly one (n_buckets,) int32 array on the
            # dynamic path, nothing otherwise — one closure serves both
            # so the specs can never diverge between them
            sm = compat.shard_map(
                step_body,
                mesh=mesh,
                in_specs=(pspec_P0, mem_manual, opt_in, P(),
                          batch_specs(batch)) + ((P(),) if dyn else ()),
                out_specs=out_specs,
                axis_names=set(data_axes),
                check_vma=False,
            )
            return sm(params, memory, opt, count, batch, *pod_ks)

        step = jax.jit(step, donate_argnums=(0, 1, 2))
    else:
        # Qsparse-local-SGD: two jitted steps over shared closures. The
        # accumulator rides next to the memory — same (W, rows, cols)
        # bucket layout, same per-worker sharding — so the sync step's
        # u = m + acc is plain bucket arithmetic.
        acc_manual = tuple(P(worker) for _ in plan.buckets)
        local_out = (pspec_P0, mem_manual, acc_manual, opt_in, P(),
                     {"loss": P(), "aux": P()})
        sync_out = local_out
        if dspec is not None:
            sync_out += (tuple(P() for _ in dspec.wires),)

        def sync_step(params, memory, acc, opt, count, batch, *pod_ks):
            sm = compat.shard_map(
                sync_body,
                mesh=mesh,
                in_specs=(pspec_P0, mem_manual, acc_manual, opt_in, P(),
                          batch_specs(batch)) + ((P(),) if dyn else ()),
                out_specs=sync_out,
                axis_names=set(data_axes),
                check_vma=False,
            )
            return sm(params, memory, acc, opt, count, batch, *pod_ks)

        def accum_step(params, memory, acc, opt, count, batch):
            sm = compat.shard_map(
                accum_body,
                mesh=mesh,
                in_specs=(pspec_P0, mem_manual, acc_manual, opt_in, P(),
                          batch_specs(batch)),
                out_specs=local_out,
                axis_names=set(data_axes),
                check_vma=False,
            )
            return sm(params, memory, acc, opt, count, batch)

        step = jax.jit(sync_step, donate_argnums=(0, 1, 2, 3))
        step.accum = jax.jit(accum_step, donate_argnums=(0, 1, 2, 3))
    step.local_steps = H
    if dspec is not None:
        step.delta_spec = dspec  # static wire layout for replica decoders
    if pod_k_max is not None:
        step.pod_k_max = pod_k_max  # static padded pod-k ceilings
    return step


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


class PodRatioCalibrator:
    """Host-side mass-capture calibration for the two-level pod sync.

    ONE jitted grad fn serves both the first-batch calibration (zero
    memory: u = eta*g) and every live refresh (u = m + eta*g on the
    current batch and the live per-worker memory buffers), so a mid-run
    refresh compiles nothing new — everything after step 1 is pure data
    flow. When the global batch splits evenly into ``n_data`` shards the
    per-shard buffers let ``autotune_pod_ratios`` simulate the realized
    pod mean (overlapping shard selections shrink k); otherwise the
    single global buffer's tail curve is the conservative proxy. For
    per-worker memory the first ``n_data`` workers (pod 0) stand in on
    the shard path, the worker mean on the global path.
    """

    def __init__(self, model, plan, n_data: int):
        self.plan = plan
        self.n_data = n_data
        self._gfn = jax.jit(
            jax.grad(lambda p, b: model.loss(p, b), has_aux=True)
        )

    def u_bufs(self, params, batch, eta, memory=None):
        """Concrete per-bucket u = m + eta*g buffers for
        ``autotune_pod_ratios`` — (n_data, rows, cols) per-shard stacks
        when the batch divides, (rows, cols) otherwise."""
        plan, n_data = self.plan, self.n_data
        B = jax.tree.leaves(batch)[0].shape[0]

        def u_of(bt):
            g, _ = self._gfn(params, bt)
            return bk.pack(
                plan,
                jax.tree.map(lambda x: eta * x.astype(jnp.float32), g),
                dtype=jnp.float32,
            )

        if B % n_data == 0 and n_data > 1:
            per_shard = [
                u_of(jax.tree.map(
                    lambda x: x[i * (B // n_data):(i + 1) * (B // n_data)],
                    batch))
                for i in range(n_data)
            ]
            return [
                jnp.stack([s[b] for s in per_shard])
                + (memory[b][:n_data] if memory is not None else 0.0)
                for b in range(len(plan.buckets))
            ]
        u = u_of(batch)
        if memory is not None:
            u = [ub + jnp.mean(memory[b], axis=0)
                 for b, ub in enumerate(u)]
        return u


def _calibrate_pod_ratios(sync_cfg, plan, u_bufs, n_data,
                          mass_target=None, k_caps=None, byte_budget=None):
    """One calibration entry for both pod-k sizing modes: a byte budget
    (argument override, else ``SyncConfig.byte_budget``) water-fills the
    global cross-pod allowance across buckets via
    ``core.budget.BudgetController``; otherwise the historical
    mass-target autotune sizes each bucket independently. Returns
    per-bucket pod ratios."""
    budget = (byte_budget if byte_budget is not None
              else sync_cfg.byte_budget)
    if budget is not None:
        from repro.core.budget import BudgetController

        ctl = BudgetController(sync_cfg, plan, n_data, k_caps=k_caps)
        ks = ctl.allocate(u_bufs, byte_budget=budget)
        return ctl.ratios_of(ks)
    from repro.core.distributed import autotune_pod_ratios

    return autotune_pod_ratios(sync_cfg, plan, u_bufs, n_data=n_data,
                               mass_target=mass_target, k_caps=k_caps)


def _maybe_autotune_pod_ratios(model, mesh, tc: TrainConfig, plan, params,
                               batches, calib=None):
    """Calibration pass for the two-level pod sync: when training
    hierarchical + bucketed on a pod mesh with no explicit
    ``SyncConfig.pod_ratios``, peek the first batch, measure each
    bucket's realized gradient mass capture (u = eta*g at zero memory),
    and bake per-bucket pod ratios into the static sync config before
    the jitted step is built (wire layouts need static k). Returns
    ``(tc, batches)`` with the peeked batch pushed back. Pass ``calib``
    (a ``PodRatioCalibrator``) to share its jitted grad fn with the
    live refresh loop."""
    import itertools

    if not (tc.pod_autotune and plan is not None
            and tc.sync.strategy == "hierarchical"
            and "pod" in mesh.axis_names
            and tc.sync.pod_ratios is None):
        return tc, batches
    first = next(batches, None)
    if first is None:
        return tc, batches
    n_data = int(mesh.shape["data"])
    calib = calib or PodRatioCalibrator(model, plan, n_data)
    u_bufs = calib.u_bufs(params, first, tc.eta)
    ratios = _calibrate_pod_ratios(tc.sync, plan, u_bufs, n_data)
    tc = dataclasses.replace(tc, sync=tc.sync.with_pod(ratios=ratios))
    from repro.core.distributed import bucketed_message_bytes

    lv = bucketed_message_bytes(
        tc.sync.with_pod(axis="pod"), plan, by_level=True,
        n_data=n_data,
    )
    print(
        "pod autotune: ratios="
        + ",".join(f"{r:.4g}" for r in ratios)
        + f"  intra-pod {lv['intra']}B cross-pod {lv['cross']}B /step/worker"
    )
    return tc, itertools.chain([first], batches)


def _cache_sizes(step, H: int):
    """Combined jit-cache population of the step fn(s): the sync step
    plus (at H > 1) its ``step.accum`` sibling. None when the runtime
    doesn't expose ``_cache_size``."""
    sizes = [step] + ([step.accum] if H > 1 else [])
    total = 0
    for f in sizes:
        c = getattr(f, "_cache_size", None)
        if not callable(c):
            return None
        total += int(c())
    return total


def _telemetry_bytes(tc: TrainConfig, plan, mesh, pod_ks=None):
    """Per-step wire accounting for the telemetry sink: the exact
    ``amortized_bytes_per_step`` dict (1/H under local steps), split
    ``{"intra", "cross", "total"}`` on a (pod, data) mesh. Best-effort
    — returns None for non-bucketed syncs or config combinations with
    no defined accounting, because observe-only telemetry must never
    turn an accounting edge case into a training failure."""
    if plan is None:
        return None
    from repro.core.distributed import amortized_bytes_per_step

    try:
        if "pod" in mesh.axis_names:
            acct = amortized_bytes_per_step(
                tc.sync.with_pod(axis="pod"), plan, by_level=True,
                n_data=int(mesh.shape["data"]), pod_ks=pod_ks,
            )
        else:
            acct = {"total": amortized_bytes_per_step(tc.sync, plan)}
    except (ValueError, TypeError):
        return None
    return acct


def train(model, mesh, tc: TrainConfig, batches, n_steps: int,
          checkpointer=None, ckpt_every: int = 0, log_every: int = 10,
          rng=None, delta_sink=None, ckpt_wire: bool = False,
          ckpt_memory_ratio: float = 0.05, refresh_cb=None,
          pod_k_schedule=None, diagnostics=None, telemetry=None):
    """End-to-end training loop. ``batches``: iterator of device-ready
    global batches (see repro.data.pipeline.ShardedBatcher).

    With ``tc.emit_deltas``, ``delta_sink(step_index, wire_msgs)`` is
    called with the packed per-bucket delta buffers each step (decode
    them against ``make_train_step(...).delta_spec`` — see
    ``repro.launch.delta_stream``).

    With ``ckpt_wire`` (requires ``tc.sync.bucketed``), checkpoints go
    through the packed wire codec (``Checkpointer.save_wire``): params
    diff-encoded against the boot state, the error-feedback memory
    top-k'-compressed at ``ckpt_memory_ratio`` — instead of dense f32
    dumps.

    With ``tc.pod_refresh`` enabled, the per-bucket pod ks re-calibrate
    every ``pod_refresh.every`` steps from the live memory+gradient
    buffers, riding the k-padded dynamic wire into the SAME jitted step
    (no recompile; ks clamp to the step's static ``pod_k_max``).
    ``refresh_cb(step_index, ks_tuple)`` observes each applied refresh.
    ``pod_k_schedule`` — a sequence of ``(step_index, ks_tuple)`` —
    REPLAYS a recorded schedule instead of re-calibrating (the bitwise
    reproducibility path: a fresh run fed the same schedule reproduces
    the refreshed run exactly). Pass a dict as ``diagnostics`` to
    receive ``step_cache_size`` (the jit cache population after the
    run — 1 means zero recompiles past the first trace), the applied
    ``pod_refresh_schedule`` and the ``initial_pod_ks``.

    ``telemetry`` — a ``repro.utils.telemetry.Telemetry`` sink fed
    every step (loss + rolling medians, spike/non-finite detection,
    per-step bytes, pod-k refreshes, jit-cache sizes); when omitted an
    internal sink with the default config runs, so a NaN/inf loss
    raises ``NonFiniteLossError`` instead of training to the step
    budget on garbage (pass a sink configured with
    ``stop_on_nonfinite=False`` to restore observe-only behaviour).
    The raised error carries the partial ``history`` accumulated before
    the stop, so a crash at step N does not discard N-1 steps of
    signal. A caller-provided sink stays OPEN after train() returns
    (reuse it across runs, close it yourself / via its context
    manager); only the internal default sink is closed here.
    Telemetry is observe-only: enabling it never changes the applied
    params/memory — bitwise (DESIGN.md invariant 13), and never blocks
    async dispatch — each step's device loss is drained only after the
    NEXT step is dispatched, so detection/printing lag one step while
    the host keeps running ahead. The legacy ``diagnostics`` dict is
    filled from the sink, keys unchanged.
    """
    plan = _bucket_plan(tc, model.param_shapes())
    if ckpt_wire and plan is None:
        raise ValueError("ckpt_wire requires sync.bucketed (a BucketPlan)")
    refresh = tc.pod_refresh if (
        tc.pod_refresh is not None and tc.pod_refresh.enabled) else None
    if refresh is not None or pod_k_schedule is not None:
        kw = {"dynamic": True}
        if refresh is not None and refresh.k_max_ratio is not None:
            kw["k_max_ratio"] = refresh.k_max_ratio
        tc = dataclasses.replace(tc, sync=tc.sync.with_pod(**kw))
    dyn = tc.sync.pod_dynamic
    if dyn and (plan is None or tc.sync.strategy != "hierarchical"
                or "pod" not in mesh.axis_names):
        raise ValueError(
            "pod_refresh / pod_k_schedule / sync.pod_dynamic require "
            "sync.bucketed, strategy='hierarchical' and a (pod, data) mesh"
        )
    params, memory, opt, count = init_train_state(model, mesh, tc, rng=rng)
    batches = iter(batches)
    calib = None
    if dyn:
        calib = PodRatioCalibrator(model, plan, int(mesh.shape["data"]))
    tc, batches = _maybe_autotune_pod_ratios(
        model, mesh, tc, plan, params, batches, calib=calib
    )
    base_params = None
    if ckpt_wire and checkpointer is not None:
        from repro.launch.serve import replica_copy

        base_params = replica_copy(params)  # survives the donated step
    pshard, mshard, oshard, cshard = state_shardings(model, mesh, tc)
    params = jax.device_put(params, pshard)
    memory = jax.device_put(memory, mshard)
    if oshard != ():
        opt = jax.device_put(opt, oshard)
    step = make_train_step(model, mesh, tc)
    H = int(getattr(step, "local_steps", 1))
    acc = None
    if H > 1:
        # bucket-space local accumulator: same (W, rows, cols) layout
        # and per-worker sharding as the error-feedback memory
        data_axes = (("pod",) if "pod" in mesh.axis_names else ()) + ("data",)
        W = _worker_count(mesh, data_axes)
        acc = jax.device_put(
            tuple(jnp.zeros((W,) + spec.shape, jnp.float32)
                  for spec in plan.buckets),
            mshard,
        )
    pod_ks = live_ks = k_caps = None
    sched = dict(pod_k_schedule) if pod_k_schedule is not None else None
    if dyn:
        from repro.core.distributed import bucketed_message_bytes

        n_data = int(mesh.shape["data"])
        k_caps = step.pod_k_max
        live_ks = tuple(
            tc.sync.pod_k_for_bucket(b, s.cols) if s.kind == "sparse" else 1
            for b, s in enumerate(plan.buckets)
        )
        pod_ks = jnp.asarray(live_ks, jnp.int32)
    history = []
    initial_pod_ks = live_ks
    tel_owned = telemetry is None
    tel = telemetry if telemetry is not None else Telemetry()
    tel.initial_pod_ks = initial_pod_ks
    # bytes_live is the driver's own record of the accounting currently
    # in effect (the sink's copy gets rewound to per-step snapshots by
    # the drains below, so it cannot serve as the source of truth)
    bytes_live = _telemetry_bytes(tc, plan, mesh, pod_ks=live_ks)
    tel.set_bytes_per_step(bytes_live)
    from repro.data.pipeline import take

    # one-step-late loss readback: float(loss) blocks on the device
    # value, so the driver holds each step's loss as a device array and
    # drains it only after the NEXT step has been dispatched — the host
    # keeps running ahead of the device (the async-dispatch overlap the
    # double-buffered bucket pipeline depends on) at the cost of
    # detection/printing lagging one step. The bytes accounting in
    # effect at dispatch rides along so a pod refresh between dispatch
    # and drain still attributes the step's bytes correctly.
    pending = None

    def _drain(rec):
        idx, dev_loss, cache_rec, log_rec, bytes_rec = rec
        loss = float(dev_loss)
        tel.set_bytes_per_step(bytes_rec)
        try:
            tel.step(idx, loss, cache_size=cache_rec, log=log_rec)
        except NonFiniteLossError as e:
            # a crash at step N must not discard N-1 steps of signal
            # (the garbage step itself stays out of the history)
            e.history = list(history)
            if tel_owned:
                tel.close()
            raise
        if log_rec:
            history.append((idx, loss))

    # take() consumes EXACTLY n_steps from the (typically shared,
    # typically infinite) stream — a bare `enumerate + break` would pull
    # and discard one extra batch per run
    for i, batch in enumerate(take(batches, n_steps)):
        # Qsparse-local-SGD cadence: steps i with (i+1) % H != 0 only
        # accumulate locally; step i with (i+1) % H == 0 closes sync
        # round j = i // H (H == 1: every step syncs, j == i)
        j = i // H
        is_sync = (i + 1) % H == 0
        if dyn and sched is not None and i in sched:
            # clamp to the step's static padded ceilings HOST-SIDE, so
            # the recorded/applied schedule and the effective-byte
            # accounting always describe the ks the wire realizes (the
            # jitted step clips too, but silently)
            live_ks = tuple(
                max(1, min(int(k), int(c)))
                for k, c in zip(sched[i], k_caps)
            )
            pod_ks = jnp.asarray(live_ks, jnp.int32)
            tel.pod_refresh(i, live_ks)
            bytes_live = _telemetry_bytes(tc, plan, mesh, pod_ks=live_ks)
        elif (dyn and sched is None and refresh is not None and is_sync
              and j > 0 and j % refresh.every == 0):
            # live re-calibration (an explicit pod_k_schedule REPLACES
            # it entirely — a replay must stay deterministic even past
            # the recorded entries): read-only on params/memory (fully
            # materialized host-side before the donating step call),
            # at the SAME eta the step applies — the scheduled eta_t
            # (or adam's fixed 1.0); with eta decay the base eta would
            # overweight the gradient in u = m + eta*g and mis-size k.
            # Computed host-side from the loop index (== count here):
            # float(count) would sync the dispatch queue every refresh
            eta_now = (
                _eta_at(tc, i)
                if tc.optimizer in ("memsgd", "memsgd_momentum", "dense")
                else 1.0
            )
            # at H > 1 the sync consumes u = m + acc (+ eta*g): fold the
            # live local accumulator into the calibration view of memory
            mem_live = (memory if acc is None else
                        tuple(m + a for m, a in zip(memory, acc)))
            u_bufs = calib.u_bufs(params, batch, eta_now, memory=mem_live)
            ratios = _calibrate_pod_ratios(
                tc.sync, plan, u_bufs, n_data,
                mass_target=refresh.mass_target, k_caps=k_caps,
                byte_budget=refresh.byte_budget,
            )
            live_ks = tuple(
                int(round(r * s.cols)) if s.kind == "sparse" else 1
                for r, s in zip(ratios, plan.buckets)
            )
            pod_ks = jnp.asarray(live_ks, jnp.int32)
            lv = bucketed_message_bytes(
                tc.sync.with_pod(axis="pod"), plan,
                by_level=True, n_data=n_data, pod_ks=live_ks,
            )
            print(
                f"pod refresh @ step {i}: ks="
                + ",".join(str(k) for k in live_ks)
                + f"  effective cross-pod {lv['cross']}B /step/worker"
            )
            tel.pod_refresh(i, live_ks, cross_bytes=lv["cross"])
            bytes_live = _telemetry_bytes(tc, plan, mesh, pod_ks=live_ks)
            if refresh_cb is not None:
                refresh_cb(i, live_ks)
        if H > 1:
            if is_sync:
                out = (step(params, memory, acc, opt, count, batch, pod_ks)
                       if dyn else
                       step(params, memory, acc, opt, count, batch))
            else:
                out = step.accum(params, memory, acc, opt, count, batch)
        else:
            out = (step(params, memory, opt, count, batch, pod_ks)
                   if dyn else step(params, memory, opt, count, batch))
        cache = _cache_sizes(step, H)
        if diagnostics is not None:
            diagnostics.setdefault("step_cache_sizes", []).append(cache)
        if H > 1:
            if tc.emit_deltas and is_sync:
                params, memory, acc, opt, count, metrics, delta = out
                if delta_sink is not None:
                    delta_sink(i, delta)
            else:
                params, memory, acc, opt, count, metrics = out
        elif tc.emit_deltas:
            params, memory, opt, count, metrics, delta = out
            if delta_sink is not None:
                delta_sink(i, delta)
        else:
            params, memory, opt, count, metrics = out
        # the sink sees EVERY step's loss (spike/non-finite detection
        # can't run on a log_every subsample); it owns the per-step
        # print, so a NaN/inf loss raises NonFiniteLossError from the
        # drain instead of printing garbage to the step budget. Step i
        # is already dispatched when step i-1's loss is drained, so the
        # blocking float() never stalls the dispatch queue.
        do_log = bool(log_every and (i % log_every == 0 or i == n_steps - 1))
        if pending is not None:
            _drain(pending)
        pending = (i, metrics["loss"], cache, do_log, bytes_live)
        if tel.should_stop:
            print(f"telemetry early stop @ step {i}: {tel.stop_reason}")
            break
        if checkpointer is not None and ckpt_every and (i + 1) % ckpt_every == 0:
            if ckpt_wire:
                checkpointer.save_wire(
                    i + 1, params, memory, plan,
                    base_params=base_params,
                    memory_ratio=ckpt_memory_ratio,
                )
            else:
                checkpointer.save(i + 1, {"params": params})
    if pending is not None:
        _drain(pending)  # the last dispatched step's loss
    if tel_owned:
        # caller-provided sinks stay open for reuse (they own their
        # lifetime via the context-manager protocol); only the
        # internally-created default sink is closed here
        tel.close()
    if diagnostics is not None:
        # legacy ad-hoc dict, now sourced from the telemetry sink (same
        # keys and values as before the sink absorbed the bookkeeping)
        d = tel.diagnostics(H)
        for key in ("step_cache_size", "pod_refresh_schedule",
                    "initial_pod_ks", "steady_state_recompiles"):
            diagnostics[key] = d[key]
    return params, memory, opt, count, history


def _sync_from_args(ap, args) -> SyncConfig:
    """CLI arg assembly for the sync config, routed through the grouped
    SyncConfig API. With ``--preset`` the named ``SyncConfig.preset``
    bundle is the base and only flags the user set EXPLICITLY (value
    differs from the argparse default) override it; without a preset
    every flag lands in the grouped constructors directly."""
    from repro.core.distributed import (
        PodConfig,
        TransportConfig,
        WireConfig,
    )

    bucketed = (args.bucketed or args.emit_deltas or args.ckpt_wire
                or args.pod_refresh_every > 0 or args.local_steps > 1
                or args.wire_quant is not None)
    overlap = None if args.overlap == "auto" else args.overlap == "on"
    if args.preset is not None:
        # flat override keys are the blessed warning-free preset inputs
        overrides = {}
        for arg, key in (("ratio", "ratio"), ("strategy", "strategy"),
                         ("local_steps", "local_steps"), ("wire", "wire"),
                         ("wire_quant", "quant"),
                         ("pod_ratio", "pod_ratio"),
                         ("pod_mass_target", "pod_mass_target"),
                         ("pod_k_max_ratio", "pod_k_max_ratio"),
                         ("byte_budget", "byte_budget"),
                         ("repack", "repack")):
            if getattr(args, arg) != ap.get_default(arg):
                overrides[key] = getattr(args, arg)
        if args.overlap != ap.get_default("overlap"):
            overrides["overlap"] = overlap
        if bucketed:
            overrides["bucketed"] = True
        return SyncConfig.preset(args.preset, **overrides)
    return SyncConfig(
        ratio=args.ratio,
        strategy=args.strategy,
        local_steps=args.local_steps,
        bucketed=bucketed,
        wire=WireConfig(wire=args.wire, quant=args.wire_quant),
        pod=PodConfig(ratio=args.pod_ratio,
                      mass_target=args.pod_mass_target,
                      k_max_ratio=args.pod_k_max_ratio),
        transport=TransportConfig(repack=args.repack,
                                  byte_budget=args.byte_budget,
                                  overlap=overlap),
    )


def main():
    """CLI: train an assigned architecture end to end.

    ``--size smoke`` (the default, for CPU runs) trains the reduced
    variant; ``--size full`` the published config, with ``--layers N``
    cutting depth only (widths, heads, head_dim, LoRA ranks and vocab
    stay published) so it fits one accelerator:

        python -m repro.launch.train --arch qwen3-4b
        python -m repro.launch.train --arch rwkv6-3b --size full \
            --layers 2 --preset topk --bucketed --wire packed --seq 512

    The persistent compile cache follows ``JAX_COMPILATION_CACHE_DIR``
    when set, else ``<repo>/.jax_cache``.
    """
    import argparse

    from repro.checkpoint import Checkpointer
    from repro.configs import ARCH_IDS, MESHES, sized_config
    from repro.data import token_batches
    from repro.data.pipeline import ShardedBatcher
    from repro.models import build_model

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--size", default="smoke", choices=("smoke", "full"),
                    help="smoke: the reduced same-family variant (CPU); "
                         "full: the published config")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut depth to N layers (widths, heads, head_dim, "
                         "LoRA ranks and vocab are never cut)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--optimizer", default="memsgd")
    ap.add_argument("--eta", type=float, default=0.5)
    ap.add_argument("--ratio", type=float, default=0.01)
    ap.add_argument("--strategy", default="sparse_allgather")
    ap.add_argument("--mesh", default=None, choices=sorted(MESHES),
                    help="named MeshConfig (repro.configs.MESHES); the "
                         "smoke_2pod layout exercises the two-level pod "
                         "sync on 8 forced host devices")
    ap.add_argument("--pods", type=int, default=1,
                    help="ad-hoc (pod, data) mesh: split the available "
                         "devices into this many pods (hierarchical "
                         "strategy re-compresses at the pod boundary)")
    ap.add_argument("--pod-ratio", type=float, default=None,
                    help="global pod re-compression ratio (hierarchical); "
                         "autotuned per bucket by default")
    ap.add_argument("--pod-mass-target", type=float, default=0.9,
                    help="mass-capture target for the per-bucket pod-"
                         "ratio autotune")
    ap.add_argument("--no-pod-autotune", action="store_true",
                    help="disable the per-bucket pod-ratio calibration")
    ap.add_argument("--pod-refresh-every", type=int, default=0,
                    help="re-calibrate the per-bucket pod ks every N "
                         "steps from the live memory+gradient buffers "
                         "and feed them into the RUNNING jitted step "
                         "(k-padded dynamic wire, zero recompiles; "
                         "requires --strategy hierarchical on a pod "
                         "mesh, implies --bucketed; 0 = off)")
    ap.add_argument("--pod-k-max-ratio", type=float, default=None,
                    help="cap the static padded pod k at this fraction "
                         "of bucket cols (default: the n_data*k_row "
                         "support bound) — smaller caps shrink the "
                         "padded gather but bound upward refreshes")
    ap.add_argument("--byte-budget", type=int, default=None,
                    help="global cross-pod byte budget per step per "
                         "worker: the per-bucket pod ks are sized by "
                         "water-filling this allowance across buckets "
                         "by marginal mass-per-byte "
                         "(repro.core.budget.BudgetController) instead "
                         "of the per-bucket mass-capture target; "
                         "refreshes re-spend it on the live buffers")
    ap.add_argument("--repack", action="store_true",
                    help="header-aware repack transport: grow each "
                         "bucket's pipeline an explicit repack stage at "
                         "the pod boundary so cross-pod bytes track the "
                         "live pod k instead of the padded k_max "
                         "(bitwise-identical results; see DESIGN.md "
                         "invariant 11)")
    ap.add_argument("--bucketed", action="store_true",
                    help="flat-buffer bucketed sync (repro.core.buckets)")
    ap.add_argument("--preset", default=None,
                    choices=("dense", "topk", "qsparse_local",
                             "pod_budgeted"),
                    help="start from a named SyncConfig.preset; other "
                         "sync flags given EXPLICITLY on the command "
                         "line override the preset's fields")
    ap.add_argument("--local-steps", type=int, default=1,
                    help="Qsparse-local-SGD: take H uncommunicated local "
                         "steps (accumulating eta_t*g_t in bucket space "
                         "next to the error memory), then sync ONCE "
                         "through top-k (+ optional --wire-quant) — "
                         "cross-worker bytes/step shrink ~1/H (implies "
                         "--bucketed; 1 = classic per-step sync)")
    ap.add_argument("--wire-quant", type=int, default=None,
                    help="QSGD stochastic-rounding quantization level s "
                         "for the packed sparse wire: values ship as one "
                         "f32 row norm + (1+ceil(log2(s+1)))-bit codes; "
                         "memory absorbs the quantization error (implies "
                         "--bucketed; requires --wire packed for byte "
                         "savings)")
    ap.add_argument("--quant-seed", type=int, default=0,
                    help="base PRNG seed for the QSGD rounding noise "
                         "(step count folded in per step)")
    ap.add_argument("--wire", default="unpacked",
                    choices=("unpacked", "packed"),
                    help="sync wire format (repro.core.encoding)")
    ap.add_argument("--overlap", default="auto",
                    choices=("auto", "on", "off"),
                    help="software-pipelined bucket schedule "
                         "(repro.core.pipeline): 'on' double-buffers so "
                         "bucket b's all-gather+decode overlaps bucket "
                         "b+1's select+encode, 'off' pins the strict "
                         "sequential schedule, 'auto' keeps the legacy "
                         "emission. Bitwise-identical params/memory in "
                         "all modes")
    ap.add_argument("--platform", default=None,
                    choices=("cpu", "gpu", "cuda", "tpu"),
                    help="pin the JAX platform and set its XLA perf "
                         "flags (GPU: async collectives + latency-hiding "
                         "scheduler — what makes --overlap on hide the "
                         "gathers; repro.utils.platform.setup_platform)")
    ap.add_argument("--emit-deltas", action="store_true",
                    help="stream packed parameter deltas for serving "
                         "replicas (implies --bucketed)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-wire", action="store_true",
                    help="checkpoint params+memory through the packed "
                         "wire codec instead of dense f32 dumps "
                         "(implies --bucketed)")
    ap.add_argument("--ckpt-memory-ratio", type=float, default=0.05,
                    help="per-row top-k ratio for the lossy memory "
                         "section of wire checkpoints")
    args = ap.parse_args()

    from repro.utils.platform import enable_compile_cache

    enable_compile_cache()
    if args.platform is not None:
        # before any backend use (device_count below initializes the
        # client, which reads XLA_FLAGS once)
        from repro.utils.platform import setup_platform

        setup_platform(args.platform)

    if args.mesh:
        from repro.launch.mesh import mesh_from_config

        mesh = mesh_from_config(MESHES[args.mesh])
    elif args.pods > 1:
        n = jax.device_count()
        if n % args.pods:
            ap.error(f"--pods {args.pods} does not divide {n} devices")
        mesh = compat.make_mesh(
            (args.pods, n // args.pods, 1), ("pod", "data", "model")
        )
    else:
        mesh = compat.make_mesh((jax.device_count(), 1), ("data", "model"))
    batch_axes = (
        ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    )
    cfg = sized_config(args.arch, args.size, args.layers)
    model = build_model(cfg)
    refresh = None
    if args.pod_refresh_every > 0:
        from repro.configs import PodRefreshConfig

        refresh = PodRefreshConfig(every=args.pod_refresh_every,
                                   k_max_ratio=args.pod_k_max_ratio)
    sync = _sync_from_args(ap, args)
    tc = TrainConfig(optimizer=args.optimizer, eta=args.eta,
                     emit_deltas=args.emit_deltas,
                     pod_autotune=not args.no_pod_autotune,
                     pod_refresh=refresh,
                     quant_seed=args.quant_seed,
                     sync=sync)
    batches = ShardedBatcher(
        mesh, token_batches(cfg.vocab_size, args.batch, args.seq, seed=0),
        batch_axes=batch_axes,
    )
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    streamed = [0]
    sink = None
    if args.emit_deltas:
        sink = lambda i, msgs: streamed.__setitem__(
            0, streamed[0] + sum(m.nbytes for m in msgs))
    train(model, mesh, tc, batches, n_steps=args.steps, checkpointer=ck,
          ckpt_every=max(1, args.steps // 2), delta_sink=sink,
          ckpt_wire=args.ckpt_wire,
          ckpt_memory_ratio=args.ckpt_memory_ratio)
    if args.ckpt_wire and ck is not None:
        import json as _json

        with open(ck._wire_path(ck.latest_wire_step()) + ".json") as f:
            w = _json.load(f)["wire"]
        print(f"wire checkpoint: {w['nbytes']/1e6:.2f} MB "
              f"(dense f32 dump: {w['dense_nbytes']/1e6:.2f} MB, "
              f"x{w['ratio_vs_dense']:.1f} smaller)")
    if args.emit_deltas:
        dense = sum(
            p.size * 4 for p in jax.tree.leaves(model.param_shapes())
        ) * args.steps
        print(f"delta stream: {streamed[0]/1e6:.2f} MB over {args.steps} "
              f"steps (dense refresh would be {dense/1e6:.2f} MB)")


if __name__ == "__main__":
    main()
