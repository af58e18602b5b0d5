"""End-to-end system tests on a 1x1 mesh (single real CPU device):
train -> checkpoint -> restore -> serve."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs import get_smoke_config
from repro.core.distributed import SyncConfig
from repro.data import token_batches
from repro.data.pipeline import ShardedBatcher, take
from repro.launch.train import (
    TrainConfig,
    init_train_state,
    make_train_step,
    state_shardings,
    train,
)
from repro.models import build_model
from repro.utils.compat import make_mesh


def _mesh11():
    return make_mesh((1, 1), ("data", "model"))


def test_end_to_end_train_checkpoint_serve():
    mesh = _mesh11()
    cfg = get_smoke_config("granite-3-8b")
    model = build_model(cfg)
    tc = TrainConfig(optimizer="memsgd", eta=0.5, sync=SyncConfig(ratio=0.02))
    with tempfile.TemporaryDirectory() as tmp:
        ck = Checkpointer(tmp, max_to_keep=2)
        batches = ShardedBatcher(
            mesh, token_batches(cfg.vocab_size, 4, 64, seed=0), prefetch=0
        )
        params, memory, opt, count, history = train(
            model, mesh, tc, batches, n_steps=25, checkpointer=ck,
            ckpt_every=10, log_every=0,
        )
        # loss decreased vs fresh init
        batch = next(iter(ShardedBatcher(
            mesh, token_batches(cfg.vocab_size, 4, 64, seed=0), prefetch=0)))
        final_loss = float(model.loss(params, batch)[0])
        init_params = model.init(jax.random.PRNGKey(0))
        init_loss = float(model.loss(init_params, batch)[0])
        assert final_loss < init_loss
        # checkpoints written during training and restorable
        assert ck.latest_step() == 20
        # save the final state and round-trip it exactly
        ck.save(25, {"params": params})
        restored, meta = ck.restore(like={"params": params})
        assert meta["step"] == 25
        np.testing.assert_allclose(
            np.asarray(jax.tree.leaves(restored["params"])[0]),
            np.asarray(jax.tree.leaves(params)[0]),
        )
        # serving with the trained params
        from repro.launch.serve import decode_loop

        prompts = jnp.zeros((2, 4), jnp.int32)
        toks = decode_loop(model, mesh, params, prompts, n_tokens=5,
                           max_len=32)
        assert toks.shape == (2, 5)
        assert int(jnp.max(toks)) < cfg.vocab_size


def test_structured_stream_is_learnable():
    """The synthetic token stream has next-token structure; a short run
    with the compressed-Adam mode must show clear improvement."""
    mesh = _mesh11()
    cfg = get_smoke_config("musicgen-medium").replace(n_prefix_embeddings=0)
    model = build_model(cfg)
    tc = TrainConfig(optimizer="adam_compressed", eta=3e-3,
                     sync=SyncConfig(ratio=0.05))
    batches = ShardedBatcher(
        mesh, token_batches(cfg.vocab_size, 4, 64, seed=3), prefetch=0
    )
    params, memory, opt, count = init_train_state(
        model, mesh, tc, rng=jax.random.PRNGKey(1))
    pshard, mshard, oshard, _ = state_shardings(model, mesh, tc)
    params = jax.device_put(params, pshard)
    memory = jax.device_put(memory, mshard)
    if oshard != ():
        opt = jax.device_put(opt, oshard)
    step = make_train_step(model, mesh, tc)
    losses = []
    for batch in take(iter(batches), 30):
        params, memory, opt, count, m = step(params, memory, opt, count, batch)
        # repro-lint: disable=RL001  (convergence smoke: per-step sync
        # keeps the assertion simple; throughput is irrelevant here)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.3


def test_prefill_logits_match_forward_tail():
    cfg = get_smoke_config("qwen1.5-4b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks}
    full, _ = model.forward(params, batch)
    last = model.prefill_logits(params, batch)
    np.testing.assert_allclose(
        np.asarray(full[:, -1], np.float32), np.asarray(last, np.float32),
        atol=1e-2, rtol=1e-2,
    )


def test_f32_step_conserves_against_separate_grad():
    """With the model in f32 the train step traces under its shard_map
    and its step 1 from zero memory obeys Mem-SGD's conservation against
    a separately jitted jax.grad: applied update + new memory ==
    eta * g (f32 rounding only)."""
    from repro.core import buckets as bk

    mesh = _mesh11()
    cfg = get_smoke_config("rwkv6-3b").replace(compute_dtype="float32")
    model = build_model(cfg)
    tc = TrainConfig(optimizer="memsgd", eta=0.3,
                     sync=SyncConfig.preset("topk", ratio=0.01))
    plan = bk.make_plan(model.param_shapes(), cols=tc.sync.bucket_cols,
                        dense_below=tc.sync.dense_below)
    params, memory, opt, count = init_train_state(
        model, mesh, tc, rng=jax.random.PRNGKey(0))
    pshard, mshard, _, _ = state_shardings(model, mesh, tc)
    params = jax.device_put(params, pshard)
    memory = jax.device_put(memory, mshard)
    batch = next(iter(ShardedBatcher(
        mesh, token_batches(cfg.vocab_size, 2, 32, seed=0), prefetch=0)))
    g = jax.jit(lambda p: bk.pack(
        plan, jax.grad(lambda q: model.loss(q, batch)[0])(p),
        dtype=jnp.float32))(params)
    p0 = jax.device_get(params)
    p1, m1, _, _, _ = make_train_step(model, mesh, tc)(
        params, memory, opt, count, batch)
    applied = bk.pack(plan, jax.tree.map(np.subtract, p0,
                                         jax.device_get(p1)),
                      dtype=jnp.float32)
    r = np.concatenate([np.ravel(a + m[0]) for a, m in zip(applied, m1)])
    eg = tc.eta * np.concatenate([np.ravel(x) for x in g])
    assert np.linalg.norm(r - eg) <= 1e-5 * np.linalg.norm(eg)
    # the selection moved a real share of eta * g into the update
    assert np.linalg.norm(np.concatenate(
        [np.ravel(a) for a in applied])) > 0.1 * np.linalg.norm(eg)
