"""The fused Mem-SGD kernel on the flat sparse stage of the bucketed sync.

On a TPU the flat sparse stage of ``bucketed_sync_gradients`` runs the
fused Pallas kernel (``u = m + eta*g``, the per-row top-k and the
residual in one pass) where it runs the XLA select, own densify and
``u - own`` elsewhere. Here, on the CPU, the backend lookup is patched to
say "tpu" and the kernel runs in interpret mode, and both paths must
give the same update and the same new memory, bitwise.

eta is 0.5 throughout: XLA:CPU contracts ``m + eta*g`` into a fused
multiply-add while the interpreted kernel rounds the product first, so
an eta whose product with g is exact is what lets the two paths be
compared bit for bit.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from _multidevice import probe_fixture
from repro.core import buckets as bk
from repro.core.distributed import SyncConfig, bucketed_sync_gradients
from repro.core.selfcheck import bitwise_equal
from repro.utils import platform
from repro.utils.compat import make_mesh, shard_map

ETA = 0.5
COLS = 128


def _tree(key):
    ks = jax.random.split(jax.random.PRNGKey(key), 3)
    return {
        # 57,000 compressed elements: 446 rows of 128, so two row blocks,
        # the second one partial
        "w1": jax.random.normal(ks[0], (300, 130)),
        "w2": jax.random.normal(ks[1], (90, 200)),
        "b": jax.random.normal(ks[2], (64,)),
    }


@pytest.fixture
def fused(monkeypatch):
    """The TPU's choice of path, with the kernel interpreted."""
    monkeypatch.setattr(platform, "backend", lambda: "tpu")
    monkeypatch.setenv(platform.ENV_INTERPRET, "1")


def _two_syncs(cfg, mesh, **kw):
    """Two syncs from zero memory on a one-worker mesh; (update, new
    memory) of the second."""
    plan = bk.make_plan(_tree(0), cols=COLS)
    rep = lambda t: jax.tree.map(lambda _: P(), t)

    def body(mem, tree):
        return bucketed_sync_gradients(cfg, plan, mem, tree,
                                       jnp.float32(ETA), **kw)[:2]

    mem = bk.init_bucket_memory(plan)
    step = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(rep(mem), rep(_tree(0))),
        out_specs=(rep(_tree(0)), rep(mem))))
    _, mem = step(mem, _tree(0))
    return step, (mem, _tree(1))


def _has_kernel(step, args) -> bool:
    return "pallas_call" in str(jax.make_jaxpr(step)(*args))


def test_fused_sync_bitwise_one_worker(monkeypatch):
    cfg = SyncConfig.preset("topk", ratio=0.04, bucket_cols=COLS)
    mesh = make_mesh((1,), ("data",))
    step, args = _two_syncs(cfg, mesh)
    assert not _has_kernel(step, args)
    want = step(*args)
    with monkeypatch.context() as mp:
        mp.setattr(platform, "backend", lambda: "tpu")
        mp.setenv(platform.ENV_INTERPRET, "1")
        step, args = _two_syncs(cfg, mesh)
        assert _has_kernel(step, args)
        got = step(*args)
    assert bitwise_equal(got[0], want[0])  # update
    assert bitwise_equal(got[1], want[1])  # new memory


@pytest.mark.parametrize("preset, ratio", [
    ("topk", 40 / COLS),  # k = 40, above the loop select's cutover of 32
    ("qsparse_local", 0.04),
    ("pod_budgeted", 0.04),
])
def test_fused_sync_stays_off(fused, preset, ratio):
    """Where the kernel does not engage, the XLA path runs as before:
    above the loop select's cutover, with a quantised wire, and in the
    hierarchical (pod) stage."""
    cfg = SyncConfig.preset(preset, ratio=ratio, bucket_cols=COLS)
    kw, axes = {}, ("data",)
    if cfg.quant is not None:
        kw["quant_key"] = jax.random.PRNGKey(3)
    if cfg.strategy == "hierarchical":
        cfg, axes = cfg.with_pod(axis="pod"), ("pod", "data")
        n = len(bk.make_plan(_tree(0), cols=COLS).buckets)
        kw["pod_ks"] = jnp.ones((n,), jnp.int32)
    step, args = _two_syncs(cfg, make_mesh((1,) * len(axes), axes), **kw)
    assert not _has_kernel(step, args)


probes = probe_fixture({
    "four_devices": """
        from jax.sharding import PartitionSpec as P
        from repro.core import buckets as bk
        from repro.core.distributed import (SyncConfig,
                                            bucketed_sync_gradients)
        from repro.core.selfcheck import bitwise_equal
        from repro.utils import platform
        from repro.utils.compat import make_mesh, shard_map

        def run(cfg, mesh, W):
            # W data workers; any other mesh axis is left to GSPMD, as
            # the train step leaves its model axis
            ks = jax.random.split(jax.random.PRNGKey(0), 3)
            tree = {"w1": jax.random.normal(ks[0], (W, 300, 130)),
                    "w2": jax.random.normal(ks[1], (W, 90, 200)),
                    "b": jax.random.normal(ks[2], (W, 64))}
            plan = bk.make_plan(jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree),
                cols=128)

            def body(mem, tree):
                mem = jax.tree.map(lambda m: m[0], mem)
                tree = jax.tree.map(lambda t: t[0], tree)
                upd, new_mem, _ = bucketed_sync_gradients(
                    cfg, plan, mem, tree, jnp.float32(0.5))
                return upd, jax.tree.map(lambda m: m[None], new_mem)
            mem = tuple(jnp.zeros((W,) + s.shape, jnp.float32)
                        for s in plan.buckets)
            spec_w = jax.tree.map(lambda _: P("data"), mem)
            step = jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(spec_w, jax.tree.map(lambda _: P("data"), tree)),
                out_specs=(jax.tree.map(lambda _: P(), {k: 0 for k in tree}),
                           spec_w),
                axis_names={"data"}))
            _, mem = step(mem, tree)
            second = jax.tree.map(lambda t: -0.25 * t, tree)
            kernel = "pallas_call" in str(jax.make_jaxpr(step)(mem, second))
            return step(mem, second), kernel

        rec = {}
        for label, shape, names, wire in (
                ("packed", (4,), ("data",), "packed"),
                ("unpacked", (4,), ("data",), "unpacked"),
                ("model_axis", (2, 2), ("data", "model"), "packed")):
            cfg = SyncConfig.preset("topk", ratio=0.04,
                                    bucket_cols=128).with_wire(wire=wire)
            mesh = make_mesh(shape, names)
            (u1, m1), k1 = run(cfg, mesh, shape[0])
            backend = platform.backend
            platform.backend = lambda: "tpu"
            os.environ[platform.ENV_INTERPRET] = "1"
            try:
                (u2, m2), k2 = run(cfg, mesh, shape[0])
            finally:
                del os.environ[platform.ENV_INTERPRET]
                platform.backend = backend
            rec[label] = {"xla_path_kernel": k1, "fused_path_kernel": k2,
                          "update": bool(bitwise_equal(u1, u2)),
                          "memory": bool(bitwise_equal(m1, m2))}
    """,
}, devices=4, timeout=240)


@pytest.mark.parametrize("case", ["packed", "unpacked", "model_axis"])
def test_fused_sync_bitwise_four_devices(probes, case):
    """Four workers over the packed and the unpacked wire, and two
    workers on a (2, 2) mesh whose model axis GSPMD keeps."""
    rec = probes("four_devices")[case]
    assert rec == {"xla_path_kernel": False, "fused_path_kernel": True,
                   "update": True, "memory": True}, rec
