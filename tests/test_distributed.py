"""Distributed PARALLEL-MEM-SGD tests.

Multi-device cases run in the file's one child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (tests/_multidevice.py)
so the main pytest process keeps the single real CPU device (per the
dry-run contract).
"""
import jax.numpy as jnp
import pytest

from _multidevice import child_watchdog, probe_fixture


def test_message_bytes_accounting():
    from repro.core.distributed import SyncConfig, message_bytes

    params = {"w": jnp.zeros((128, 256)), "b": jnp.zeros((8,))}
    cfg = SyncConfig(ratio=0.01, dense_below=16)
    # w: 128 rows (col axis last, len 256) -> k_row = max(1, 2.56) = 3
    # b: small but above dense_below(16)? 8 < 16 -> dense: 8*4 bytes
    got = message_bytes(cfg, params)
    assert got == 128 * 3 * 8 + 8 * 4


def test_sync_col_axes_rules():
    from repro.launch.sharding import sync_col_axes, param_specs
    from jax.sharding import PartitionSpec as P

    params = {
        "embed": jnp.zeros((64, 32)),
        "blocks": {
            "attn": {"wq": jnp.zeros((2, 32, 64)), "wo": jnp.zeros((2, 64, 32))},
            "mlp": {"w_down": jnp.zeros((2, 128, 32))},
        },
    }
    cols = sync_col_axes(params)
    # embed is vocab-parallel; selection runs along d_model per vocab row
    # (the D-sharded alternative measured worse: EXPERIMENTS.md §Perf A2a)
    assert cols["embed"] == 1
    assert cols["blocks"]["attn"]["wq"] == 1  # (L, D, heads): cols = D
    assert cols["blocks"]["attn"]["wo"] == 2  # (L, heads, D): cols = D
    specs = param_specs(params)
    assert specs["embed"] == P("model", None)
    assert specs["blocks"]["attn"]["wq"] == P(None, None, "model")
    assert specs["blocks"]["mlp"]["w_down"] == P(None, "model", None)


# The child's limit is the largest of any file: "hier_vs_flat" compiles a
# hierarchical step whose pod stage unrolls one argmax pass per kept
# column (pod_ratio 1.0 keeps the full row), minutes of XLA compile on a
# CPU.
CHILD_TIMEOUT = 600
probes = probe_fixture({
    "memsgd_loss": """
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.train import (TrainConfig, make_train_step,
                                        init_train_state, state_shardings)
        from repro.core.distributed import SyncConfig
        from repro.data import token_batches
        from repro.data.pipeline import ShardedBatcher

        mesh = make_debug_mesh(4, 2)
        cfg = get_smoke_config("qwen3-4b")
        model = build_model(cfg)
        tc = TrainConfig(optimizer="memsgd", eta=0.5,
                         sync=SyncConfig(ratio=0.01))
        params, memory, opt, count = init_train_state(
            model, mesh, tc, rng=jax.random.PRNGKey(0))
        pshard, mshard, oshard, _ = state_shardings(model, mesh, tc)
        params = jax.device_put(params, pshard)
        memory = jax.device_put(memory, mshard)
        step = make_train_step(model, mesh, tc)
        it = ShardedBatcher(mesh, token_batches(cfg.vocab_size, 8, 64, seed=1),
                            prefetch=0)
        losses = []
        for i, batch in enumerate(it):
            if i >= 15: break
            params, memory, opt, count, m = step(params, memory, opt, count,
                                                 batch)
            losses.append(float(m["loss"]))
        rec = {"first": losses[0], "last": losses[-1]}
        """,
    "no_dense_allreduce": """
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.train import (TrainConfig, make_train_step,
                                        init_train_state, state_shardings)
        from repro.core.distributed import SyncConfig
        from repro.roofline.analysis import parse_collectives
        import re
        from repro.utils.shapes import parse_hlo_shape_bytes

        mesh = make_debug_mesh(4, 1)  # pure data-parallel: no model axis use
        cfg = get_smoke_config("qwen3-4b")
        model = build_model(cfg)
        tc = TrainConfig(optimizer="memsgd", eta=0.1,
                         sync=SyncConfig(ratio=0.001))
        st = init_train_state(model, mesh, tc, abstract=True)
        pshard, mshard, oshard, cshard = state_shardings(model, mesh, tc)
        def abst(tree, sh):
            return jax.tree.map(lambda l, s: jax.ShapeDtypeStruct(
                l.shape, l.dtype, sharding=s), tree, sh)
        params, memory, opt, count = st
        from jax.sharding import NamedSharding, PartitionSpec as P
        batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                     sharding=NamedSharding(mesh, P("data")))
                 for k, v in {
                    "tokens": jnp.zeros((8, 64), jnp.int32),
                    "labels": jnp.zeros((8, 64), jnp.int32)}.items()}
        step = make_train_step(model, mesh, tc)
        lowered = step.lower(abst(params, pshard), abst(memory, mshard), (),
                             jax.ShapeDtypeStruct((), jnp.int32,
                                                  sharding=cshard), batch)
        hlo = lowered.compile().as_text()
        # largest all-reduce operand
        biggest_ar = 0
        for line in hlo.splitlines():
            m = re.search(r"= ([a-z0-9\\[\\],{}]+) all-reduce", line)
            if m:
                biggest_ar = max(biggest_ar, parse_hlo_shape_bytes(m.group(1)))
        biggest_param = max(
            int(np.prod(l.shape)) * 4 for l in jax.tree.leaves(params))
        rec = {"biggest_ar": biggest_ar, "biggest_param": biggest_param}
        """,
    "hier_vs_flat": """
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.train import (TrainConfig, make_train_step,
                                        init_train_state, state_shardings)
        from repro.core.distributed import SyncConfig
        from repro.data import token_batches
        from repro.data.pipeline import ShardedBatcher
        from repro.utils.compat import make_mesh

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_smoke_config("yi-9b")
        model = build_model(cfg)
        def one_step(strategy, pod_ratio):
            tc = TrainConfig(optimizer="memsgd", eta=0.3,
                             sync=SyncConfig(ratio=0.02, strategy=strategy,
                                             pod_ratio=pod_ratio))
            params, memory, opt, count = init_train_state(
                model, mesh, tc, rng=jax.random.PRNGKey(0))
            pshard, mshard, oshard, _ = state_shardings(model, mesh, tc)
            params = jax.device_put(params, pshard)
            memory = jax.device_put(memory, mshard)
            step = make_train_step(model, mesh, tc)
            it = ShardedBatcher(mesh, token_batches(cfg.vocab_size, 8, 32,
                                seed=5), batch_axes=("pod", "data"),
                                prefetch=0)
            batch = next(iter(it))
            params, *_ = step(params, memory, opt, count, batch)
            return params
        p_flat = one_step("sparse_allgather", None)
        p_hier = one_step("hierarchical", 1.0)
        diff = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(jax.tree.leaves(p_flat),
                                   jax.tree.leaves(p_hier)))
        rec = {"maxdiff": diff}
        """,
}, timeout=CHILD_TIMEOUT)


@pytest.mark.slow
@child_watchdog(CHILD_TIMEOUT)
def test_distributed_memsgd_loss_decreases(probes):
    rec = probes("memsgd_loss")
    assert rec["last"] < rec["first"]


@pytest.mark.slow
@child_watchdog(CHILD_TIMEOUT)
def test_distributed_sparse_sync_no_dense_allreduce(probes):
    """The compiled train step must NOT contain a dense gradient
    all-reduce: the biggest all-reduce operand must be far smaller than
    the largest parameter (the paper's communication claim, verified on
    the compiled HLO)."""
    rec = probes("no_dense_allreduce")
    # dense sync would all-reduce the largest param (>= MBs); the sparse
    # scheme's all-reduces are only scalar metrics / norm reductions.
    assert rec["biggest_ar"] < rec["biggest_param"] / 50


@pytest.mark.slow
@child_watchdog(CHILD_TIMEOUT)
def test_hierarchical_matches_flat_when_pod_ratio_full(probes):
    """With pod re-compression disabled (pod_ratio=1.0 => k_pod = full
    row), hierarchical == flat sparse_allgather updates after one step."""
    rec = probes("hier_vs_flat")
    assert rec["maxdiff"] < 1e-5
