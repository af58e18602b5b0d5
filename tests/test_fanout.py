"""Fan-out hub, replay catch-up, bf16 tier, snapshot codec and wire
checkpoints (repro.launch.fanout, repro.core.encoding snapshot records,
repro.checkpoint.Checkpointer.save_wire).

Fast tests drive the hub with a SYNTHETIC sparse update stream: per-step
bucket updates with support <= the delta spec's k' bound, so the packed
encode captures them exactly — the same contract the trainer guarantees
(see repro.launch.delta_stream). The slow test replays the real trainer
end to end on 4 fake devices, in the file's one child."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _multidevice import probe_fixture

from repro.checkpoint import Checkpointer
from repro.core import buckets as bk
from repro.core import encoding as enc
from repro.core.distributed import SyncConfig, _row_scatter, _row_topk
from repro.launch import delta_stream as ds
from repro.launch.fanout import FanoutHub


# -- synthetic trainer-side stream -------------------------------------------


def _plan_and_spec(workers: int = 2):
    tree = {
        "w": jax.ShapeDtypeStruct((100, 300), jnp.float32),
        "b": jax.ShapeDtypeStruct((40,), jnp.float32),
    }
    plan = bk.make_plan(tree, cols=256, dense_below=512)
    cfg = SyncConfig(ratio=0.05, bucketed=True, bucket_cols=256)
    return plan, ds.make_delta_spec(plan, cfg, workers=workers)


def _update_bufs(plan, dspec, seed):
    """Per-bucket update buffers with support <= each wire's k — the
    invariant the trainer's synced update satisfies by construction."""
    bufs = []
    for i, (spec, w) in enumerate(zip(plan.buckets, dspec.wires)):
        g = jax.random.normal(jax.random.PRNGKey(seed * 13 + i), spec.shape)
        if spec.kind == "dense":
            bufs.append(g * 0.01)
        else:
            vals, idx = _row_topk(g, w.k)
            bufs.append(_row_scatter(spec.shape, vals, idx, jnp.float32))
    return bufs


def _init_params():
    return {
        "w": jax.random.normal(jax.random.PRNGKey(99), (100, 300)),
        "b": jax.random.normal(jax.random.PRNGKey(98), (40,)),
    }


def _bitwise(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x).view(np.uint8),
                       np.asarray(y).view(np.uint8))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def _run_stream(hub, plan, dspec, trainer, steps, *, start=0, on_step=None):
    """Publish ``steps`` synthetic updates; apply them to ``trainer`` the
    way the train step does (p - u). Returns the new trainer params."""
    for t in range(start, start + steps):
        bufs = _update_bufs(plan, dspec, t)
        hub.publish(t, ds.encode_delta_bufs(dspec, bufs))
        trainer = jax.tree.map(
            lambda p, u: p - u.astype(p.dtype), trainer,
            bk.unpack(plan, bufs),
        )
        if on_step is not None:
            on_step(t)
    return trainer


# -- replay catch-up ----------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    # shim sweep runs the FIRST samples: lead with the snapshot-forcing
    # cases (join long after the log start) and the full-replay edge 0
    join_step=st.sampled_from([12, 0, 9, 4, 11]),
)
def test_replay_catchup_property(join_step):
    """A replica joining at ANY step and syncing after every subsequent
    publish ends bitwise-equal to the trainer. Joins beyond the log
    bound go through a snapshot resync first; joins inside it replay
    wire messages only."""
    T, log_bound = 12, 5
    plan, dspec = _plan_and_spec()
    trainer = _init_params()
    hub = FanoutHub(dspec, trainer, log_bound=log_bound)
    trainer = _run_stream(hub, plan, dspec, trainer, join_step)
    replica = hub.join()
    hub.sync(replica)
    expect_resync = join_step > log_bound
    assert replica.resyncs == (1 if expect_resync else 0)
    assert _bitwise(trainer, replica.params)
    trainer = _run_stream(
        hub, plan, dspec, trainer, T - join_step, start=join_step,
        on_step=lambda t: hub.sync(replica),
    )
    assert replica.cursor == T
    assert _bitwise(trainer, replica.params)
    assert _bitwise(trainer, hub.shadow)
    if join_step < T:  # everything after the join was replayed exactly
        assert replica.steps_replayed >= T - join_step


def test_lagged_replica_snapshot_resync_and_replay_tail():
    """A replica that stops syncing falls off the log; the next sync
    restores from the cached periodic snapshot (wire-compressed diff vs
    base) and replays only the tail — still bitwise-equal."""
    plan, dspec = _plan_and_spec()
    trainer = _init_params()
    hub = FanoutHub(dspec, trainer, log_bound=6, snapshot_every=4)
    replica = hub.join()
    trainer = _run_stream(hub, plan, dspec, trainer, 15)
    hub.sync(replica)
    assert replica.resyncs == 1
    assert 0 < replica.steps_replayed <= 6  # only the post-snapshot tail
    assert _bitwise(trainer, replica.params)
    # the resync moved fewer bytes than replaying the whole stream
    full_replay = 15 * dspec.nbytes
    assert replica.bytes_rx < full_replay


def test_publish_out_of_order_rejected():
    plan, dspec = _plan_and_spec()
    hub = FanoutHub(dspec, _init_params(), log_bound=4)
    bufs = _update_bufs(plan, dspec, 0)
    hub.publish(0, ds.encode_delta_bufs(dspec, bufs))
    with pytest.raises(ValueError):
        hub.publish(2, ds.encode_delta_bufs(dspec, bufs))
    with pytest.raises(ValueError):
        FanoutHub(dspec, _init_params(), log_bound=4, snapshot_every=9)


# -- bf16 tier ---------------------------------------------------------------


def test_bf16_tier_drift_bounded_over_10_steps():
    """The lossy tier's parameter drift after 10 steps stays under the
    stated bound: the sum of per-step transcode rounding errors
    ``||u_t - bf16(u_t)||_inf`` (f32 accumulation error is orders of
    magnitude below the bf16 rounding and covered by the 1% slack)."""
    T = 10
    plan, dspec = _plan_and_spec()
    trainer = _init_params()
    hub = FanoutHub(dspec, trainer, log_bound=T)
    exact = hub.join()
    lossy = hub.join("bfloat16")
    trainer = _run_stream(
        hub, plan, dspec, trainer, T,
        on_step=lambda t: (hub.sync(exact), hub.sync(lossy)),
    )
    assert _bitwise(trainer, exact.params)
    bound = hub.drift_bound("bfloat16")  # log covers all T steps here
    drift = max(
        float(jnp.max(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32))))
        for a, b in zip(jax.tree.leaves(trainer),
                        jax.tree.leaves(lossy.params))
    )
    assert 0 < drift <= bound * 1.01 + 1e-6, (drift, bound)
    # the lossy tier is the cheaper one, and both beat a dense broadcast
    assert lossy.bytes_rx < exact.bytes_rx
    assert exact.bytes_rx < T * dspec.dense_nbytes


def test_transcode_delta_matches_direct_bf16_encode():
    """Hub-side f32->bf16 transcode produces byte-identical messages to
    encoding the update with a bf16 delta spec directly."""
    plan, dspec = _plan_and_spec()
    bufs = _update_bufs(plan, dspec, 5)
    f32_msgs = ds.encode_delta_bufs(dspec, bufs)
    via_transcode = ds.transcode_delta(dspec, f32_msgs, "bfloat16")
    direct = ds.encode_delta_bufs(dspec.with_value_dtype("bfloat16"), bufs)
    for a, b in zip(via_transcode, direct):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- snapshot records --------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(
    # first samples matter for the shim: sparse diff, full-support
    # (dense fallback), empty diff, single column, tie to base
    support=st.sampled_from([3, 256, 0, 1, 100]),
)
def test_snapshot_diff_roundtrip_bitwise(support):
    base = jax.random.normal(jax.random.PRNGKey(1), (8, 256))
    cur = base
    if support:
        cols = jnp.arange(support)
        cur = base.at[jnp.arange(8)[:, None], cols[None, :]].add(1.0)
    rec = enc.snapshot_encode(cur, base=base)
    assert rec.exact
    out = enc.snapshot_decode(rec, base=base)
    assert np.array_equal(
        np.asarray(out).view(np.uint8), np.asarray(cur).view(np.uint8)
    )
    # exact size accounting: spec bytes == realized buffer bytes
    assert rec.nbytes == np.asarray(rec.buf).size * 4
    if 0 < support <= 100:
        assert rec.nbytes < rec.dense_nbytes
    if support == 256:
        assert rec.spec.kind == "dense"  # fallback, never worse than dense


def test_snapshot_diff_sees_signed_zero():
    """The support mask compares BIT PATTERNS: an entry that changed
    from +0.0 to -0.0 (float == can't see it) must still be captured,
    or the 'exact' record would restore the wrong sign bit."""
    base = jnp.zeros((2, 8))
    cur = base.at[0, 3].set(-0.0)
    rec = enc.snapshot_encode(cur, base=base)
    assert rec.exact
    out = enc.snapshot_decode(rec, base=base)
    assert np.array_equal(
        np.asarray(out).view(np.uint8), np.asarray(cur).view(np.uint8)
    )
    # and without a base, -0.0 counts as a set entry
    rec2 = enc.snapshot_encode(cur)
    out2 = enc.snapshot_decode(rec2)
    assert np.array_equal(
        np.asarray(out2).view(np.uint8), np.asarray(cur).view(np.uint8)
    )


def test_snapshot_lossy_topk_support_exact():
    m = jax.random.normal(jax.random.PRNGKey(2), (16, 256))
    rec = enc.snapshot_encode(m, k=16)
    assert not rec.exact and 0.0 < rec.dropped_frac < 1.0
    out = np.asarray(enc.snapshot_decode(rec))
    assert (np.count_nonzero(out, axis=1) <= 16).all()
    kept = out != 0
    assert np.array_equal(np.asarray(m)[kept], out[kept])
    assert rec.nbytes < rec.dense_nbytes / 4
    # a zero buffer compresses to the minimal 1-slot message, exactly
    z = enc.snapshot_encode(jnp.zeros((16, 256)), k=16)
    assert z.exact and z.spec.k == 1


# -- wire checkpoints --------------------------------------------------------


def test_checkpointer_wire_roundtrip_and_size():
    plan, dspec = _plan_and_spec()
    base = _init_params()
    trainer = _init_params()
    hub = FanoutHub(dspec, trainer, log_bound=8)
    trainer = _run_stream(hub, plan, dspec, trainer, 6)
    W = 4
    memory = tuple(
        jax.random.normal(jax.random.PRNGKey(7 + i), (W,) + s.shape) * 0.01
        for i, s in enumerate(plan.buckets)
    )
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d, max_to_keep=2)
        path = ck.save_wire(6, trainer, memory, plan, base_params=base,
                            memory_ratio=0.1)
        params2, mem2, meta = ck.restore_wire(plan=plan, base_params=base)
        assert _bitwise(trainer, params2)
        w = meta["wire"]
        # measurably smaller than the dense f32 dump, accounting exact
        assert w["nbytes"] * 3 < w["dense_nbytes"]
        realized = sum(
            np.load(path)[k].size * 4 for k in np.load(path).files
        )
        assert w["nbytes"] == realized
        # memory: bitwise on the kept support, shapes/dtypes preserved
        for m, m2 in zip(memory, mem2):
            assert m.shape == m2.shape
            kept = np.asarray(m2) != 0
            assert np.array_equal(np.asarray(m)[kept], np.asarray(m2)[kept])
        # diff-encoded restore demands the base tree
        with pytest.raises(ValueError):
            ck.restore_wire(plan=plan)
        # no base -> dense-fallback params records, still exact
        ck.save_wire(7, trainer, memory, plan, memory_ratio=0.1)
        params3, _, meta3 = ck.restore_wire(7, plan=plan)
        assert _bitwise(trainer, params3)
        assert meta3["wire"]["nbytes"] > meta["wire"]["nbytes"]
        # gc keeps the newest max_to_keep wire checkpoints
        ck.save_wire(8, trainer, memory, plan, memory_ratio=0.1)
        assert ck.wire_steps() == [7, 8]


# -- donate_argnums at the serve boundary ------------------------------------


def test_replica_copy_survives_trainer_donation():
    """Stepping the (donating) train step must never invalidate a held
    replica: replica_copy makes fresh buffers, so the replica stays
    readable and bitwise-equal to the pre-step params."""
    from repro.configs import get_smoke_config
    from repro.data import token_batches
    from repro.data.pipeline import ShardedBatcher
    from repro.launch.serve import replica_copy
    from repro.launch.train import (TrainConfig, init_train_state,
                                    make_train_step, state_shardings)
    from repro.models import build_model
    from repro.utils.compat import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke_config("rwkv6-3b")
    model = build_model(cfg)
    tc = TrainConfig(optimizer="memsgd", eta=0.5,
                     sync=SyncConfig(ratio=0.02, bucketed=True))
    params, memory, opt, count = init_train_state(
        model, mesh, tc, rng=jax.random.PRNGKey(0))
    replica = replica_copy(params)
    before = [np.asarray(x).copy() for x in jax.tree.leaves(replica)]
    pshard, mshard, _, _ = state_shardings(model, mesh, tc)
    params = jax.device_put(params, pshard)
    memory = jax.device_put(memory, mshard)
    step = make_train_step(model, mesh, tc)
    batch = next(iter(ShardedBatcher(
        mesh, token_batches(cfg.vocab_size, 4, 32, seed=0), prefetch=0)))
    params, memory, opt, count, _ = step(params, memory, opt, count, batch)
    # the held replica is still alive and untouched after the donation
    after = jax.tree.leaves(replica)
    for b, a in zip(before, after):
        assert not a.is_deleted()
        assert np.array_equal(b, np.asarray(a))
    # and the trainer really moved away from it
    assert not _bitwise(params, replica)


# -- end-to-end with the real trainer (the file's one child, 4 devices) ------


probes = probe_fixture({
    "fanout": """
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.train import (TrainConfig, make_train_step,
                                        init_train_state, state_shardings)
        from repro.launch.fanout import FanoutHub
        from repro.core.distributed import SyncConfig
        from repro.data import token_batches
        from repro.data.pipeline import ShardedBatcher

        mesh = make_debug_mesh(4, 1)
        cfg = get_smoke_config("rwkv6-3b")
        model = build_model(cfg)
        tc = TrainConfig(optimizer="memsgd", eta=0.5, emit_deltas=True,
                         sync=SyncConfig(ratio=0.02, bucketed=True,
                                         wire="packed"))
        params, memory, opt, count = init_train_state(
            model, mesh, tc, rng=jax.random.PRNGKey(0))
        step = make_train_step(model, mesh, tc)
        dspec = step.delta_spec
        hub = FanoutHub(dspec, params, log_bound=3, snapshot_every=2)
        steady = hub.join(); lossy = hub.join("bfloat16")
        pshard, mshard, _, _ = state_shardings(model, mesh, tc)
        params = jax.device_put(params, pshard)
        memory = jax.device_put(memory, mshard)
        it = ShardedBatcher(mesh, token_batches(cfg.vocab_size, 8, 32,
                            seed=1), prefetch=0)
        from repro.launch import delta_stream as dsm

        mid = None
        T = 6
        bound = 0.0  # accumulated per step: the log only spans 3 steps
        for i, batch in enumerate(it):
            if i >= T: break
            params, memory, opt, count, m, delta = step(
                params, memory, opt, count, batch)
            hub.publish(i, delta)
            exact_u = dsm.decode_delta(dspec, delta)
            lossy_u = dsm.decode_delta(
                dspec.with_value_dtype("bfloat16"),
                dsm.transcode_delta(dspec, delta, "bfloat16"))
            bound += max(
                float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(exact_u),
                                jax.tree.leaves(lossy_u)))
            hub.sync(steady); hub.sync(lossy)
            if i == 3:
                mid = hub.join(); hub.sync(mid)  # cursor 0 < log start
        late = hub.join()  # joins at T, log covers [T-3, T) -> snapshot
        hub.sync(late); hub.sync(mid)

        def bitwise(a, b):
            return all(
                np.array_equal(np.asarray(x).view(np.uint8),
                               np.asarray(y).view(np.uint8))
                for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

        drift = max(
            float(jnp.max(jnp.abs(a - b)))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(lossy.params)))
        snap_step, snap_recs, snap_bytes = hub.snapshot()
        rec = {
            "steady": bitwise(params, steady.params),
            "mid": bitwise(params, mid.params),
            "late": bitwise(params, late.params),
            "late_resyncs": late.resyncs,
            "mid_resyncs": mid.resyncs,
            "drift_ok": bool(0 < drift),
            "drift_under_bound": bool(drift <= bound * 1.01 + 1e-6),
            "snap_bytes": snap_bytes,
            "snap_dense": sum(r.dense_nbytes for r in snap_recs),
            "stats": hub.stats(),
        }
        """,
}, devices=4, timeout=240)


@pytest.mark.slow
def test_fanout_replicas_track_real_trainer(probes):
    """Acceptance: replicas subscribed at different steps — one steady,
    one joining mid-stream inside the log, one joining past the replay
    bound (forcing a wire-compressed snapshot resync) — all end
    bitwise-equal to the real Mem-SGD trainer on the f32 tier, while a
    bf16-tier replica stays within the hub's drift bound."""
    rec = probes("fanout")
    assert rec["steady"] and rec["mid"] and rec["late"], rec
    assert rec["late_resyncs"] >= 1 and rec["mid_resyncs"] >= 1
    assert rec["drift_ok"] and rec["drift_under_bound"], rec
    # the wire-compressed snapshot beats the dense f32 params dump
    assert rec["snap_bytes"] < rec["snap_dense"], rec
    assert rec["stats"]["fanout_ratio"] > 1.0
