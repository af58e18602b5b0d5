"""Bucketed flat-buffer engine tests (repro.core.buckets)."""
import jax
import jax.numpy as jnp
import numpy as np

from _multidevice import probe_fixture

from repro.core import buckets as bk
from repro.core import compression as C
from repro.core.memsgd import constant_eta, memsgd_bucketed


def _tree(key=0):
    ks = jax.random.split(jax.random.PRNGKey(key), 6)
    return {
        "w1": jax.random.normal(ks[0], (128, 256)),           # sparse f32
        "w2": jax.random.normal(ks[1], (300, 70)),            # sparse f32
        "h": jax.random.normal(ks[2], (200, 100)).astype(jnp.bfloat16),
        "b": jax.random.normal(ks[3], (64,)),                 # dense f32
        "s": jax.random.normal(ks[4], (8, 16)),               # dense f32
        "hb": jax.random.normal(ks[5], (33,)).astype(jnp.bfloat16),
    }


def test_plan_groups_by_dtype_and_route():
    plan = bk.make_plan(_tree(), cols=1024, dense_below=16384)
    kinds = sorted((s.dtype, s.kind) for s in plan.buckets)
    assert kinds == [
        ("bfloat16", "dense"),
        ("bfloat16", "sparse"),
        ("float32", "dense"),
        ("float32", "sparse"),
    ]
    assert plan.n_dispatch <= 4  # the whole point of the engine
    for spec in plan.buckets:
        assert spec.rows * spec.cols >= spec.size


def test_plan_works_on_abstract_shapes():
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _tree()
    )
    plan_a = bk.make_plan(shapes)
    plan_c = bk.make_plan(_tree())
    assert plan_a.buckets == plan_c.buckets
    assert plan_a.placements == plan_c.placements


def test_pack_unpack_roundtrip_exact():
    tree = _tree()
    plan = bk.make_plan(tree)
    out = bk.unpack(plan, bk.pack(plan, tree), cast=True)
    for k in tree:
        assert out[k].dtype == tree[k].dtype
        np.testing.assert_array_equal(
            np.asarray(out[k], np.float32), np.asarray(tree[k], np.float32)
        )


def test_bucket_memory_step_conservation_and_contraction():
    """new_m + applied == m + eta*g per sparse bucket (error-feedback
    conservation), and the per-bucket selection equals blockwise top-k
    over the concatenated leaves (Definition 2.1 contraction)."""
    tree = _tree()
    plan = bk.make_plan(tree, cols=512, dense_below=16384)
    mem = bk.init_bucket_memory(plan)
    eta = 0.7
    k_for = lambda c: max(1, c // 64)
    applied, new_mem, n = bk.bucket_memory_step(
        plan, mem, tree, eta, k_for
    )
    assert n == plan.n_dispatch
    g_bufs = bk.pack(plan, tree, dtype=jnp.float32)
    a_bufs = bk.pack(plan, applied, dtype=jnp.float32)
    for spec, m0, g, nm, a in zip(plan.buckets, mem, g_bufs, new_mem, a_bufs):
        u = m0 + eta * g
        np.testing.assert_allclose(
            np.asarray(nm + a), np.asarray(u), atol=1e-5
        )
        if spec.kind == "dense":
            np.testing.assert_array_equal(np.asarray(nm), 0.0)
            continue
        # equivalence with the framework-level blockwise compressor
        comp = C.blockwise_top_k(k_for(spec.cols), spec.cols)
        want = comp.dense(u.reshape(-1)[: spec.size], None)
        got = np.asarray(a).reshape(-1)[: spec.size]
        np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)


def test_memsgd_bucketed_transform_converges():
    """Algorithm 1 through the bucketed engine drives a quadratic to its
    optimum (error feedback must re-inject suppressed coordinates)."""
    target = {
        "a": jnp.ones((64, 300)),
        "c": jnp.full((32,), 2.0),
    }
    w = jax.tree.map(jnp.zeros_like, target)
    # eta must respect the error-feedback stability limit ~ O(k/d): the
    # selection delay is d/k = 10 steps here.
    tx = memsgd_bucketed(0.1, constant_eta(0.05), cols=256, dense_below=64)
    state = tx.init(w)
    assert len(state.memory) == 2  # sparse + dense bucket
    for _ in range(250):
        grads = jax.tree.map(lambda x, t: x - t, w, target)
        updates, state = tx.update(grads, state)
        w = jax.tree.map(lambda x, u: x + u, w, updates)
    err = max(
        float(jnp.max(jnp.abs(w[k] - target[k]))) for k in target
    )
    assert err < 1e-2, err


def test_bucketed_sync_single_worker_matches_memory_step():
    """On a 1-worker mesh the synced update equals the worker's own
    selection (mean over one worker), and the memories agree."""
    from repro.core.distributed import SyncConfig, bucketed_sync_gradients
    from repro.utils.compat import shard_map

    tree = _tree()
    plan = bk.make_plan(tree, cols=512)
    mem = bk.init_bucket_memory(plan)
    cfg = SyncConfig(ratio=0.02, bucketed=True, bucket_cols=512,
                     selection="threshold_onehot")
    mesh = jax.make_mesh((1,), ("data",))

    def body(mem, tree):
        upd, new_mem, _ = bucketed_sync_gradients(
            cfg, plan, mem, tree, jnp.float32(0.3)
        )
        return upd, new_mem

    upd, new_mem = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: jax.sharding.PartitionSpec(), mem),
                  jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)),
        out_specs=(jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree),
                   jax.tree.map(lambda _: jax.sharding.PartitionSpec(), mem)),
    ))(mem, tree)

    k_for = lambda c: cfg.k_for(c)
    applied, want_mem, _ = bk.bucket_memory_step(
        plan, mem, tree, 0.3, k_for
    )
    for k in tree:
        np.testing.assert_allclose(
            np.asarray(upd[k]), np.asarray(applied[k]), atol=1e-5
        )
    for got, want in zip(new_mem, want_mem):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-5
        )


probes = probe_fixture({
    "bucketed_train": """
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.train import (TrainConfig, make_train_step,
                                        init_train_state, state_shardings)
        from repro.core.distributed import SyncConfig
        from repro.data import token_batches
        from repro.data.pipeline import ShardedBatcher

        mesh = make_debug_mesh(4, 1)
        cfg = get_smoke_config("qwen3-4b")
        model = build_model(cfg)
        tc = TrainConfig(optimizer="memsgd", eta=0.5,
                         sync=SyncConfig(ratio=0.02, bucketed=True,
                                         selection="threshold_onehot"))
        params, memory, opt, count = init_train_state(
            model, mesh, tc, rng=jax.random.PRNGKey(0))
        pshard, mshard, oshard, _ = state_shardings(model, mesh, tc)
        params = jax.device_put(params, pshard)
        memory = jax.device_put(memory, mshard)
        step = make_train_step(model, mesh, tc)
        it = ShardedBatcher(mesh, token_batches(cfg.vocab_size, 8, 64,
                            seed=1), prefetch=0)
        losses = []
        for i, batch in enumerate(it):
            if i >= 12: break
            params, memory, opt, count, m = step(params, memory, opt,
                                                 count, batch)
            losses.append(float(m["loss"]))
        rec = {"first": losses[0], "last": losses[-1],
               "n_buckets": len(memory)}
        """,
}, timeout=120)


def test_distributed_bucketed_memsgd_loss_decreases(probes):
    """Full train step with sync.bucketed on a 4-worker mesh (model=1)."""
    rec = probes("bucketed_train")
    assert rec["last"] < rec["first"]
    assert rec["n_buckets"] <= 4
