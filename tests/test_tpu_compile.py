"""Compile every Pallas kernel for a described TPU v5e chip.

Nothing runs: the TPU compiler, installed next to jax, compiles each
kernel for a ``v5e:2x2`` topology that is described, not attached, at
the bucket shape of rwkv6-3b (published widths, 2 layers, 1024-column
buckets, k = 10). A kernel Mosaic cannot lower, or one that needs more
VMEM than the chip allows, fails here at no chip time.

The topology is described inside a module-scoped fixture (never at
import time): only one process may load the TPU library, and the
worker that is given this file keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.topk_select import row_topk_pallas

BUCKET_COLS = 1024
RATIO = 0.01


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        # the TPU library logs to files unless told not to; restored on
        # teardown
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A described-chip compile is written to the persistent cache but
    cannot be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def bucket(one_chip, no_compile_cache):
    """(rows, cols) of rwkv6-3b's f32 sparse bucket, and its k."""
    from repro.configs import sized_config
    from repro.core import buckets as bk
    from repro.models import build_model

    model = build_model(sized_config("rwkv6-3b", "full", 2))
    plan = bk.make_plan(model.param_shapes(), cols=BUCKET_COLS)
    (spec,) = [s for s in plan.buckets if s.kind == "sparse"]
    k = max(1, round(RATIO * spec.cols))
    x = jax.ShapeDtypeStruct(spec.shape, jnp.float32, sharding=one_chip)
    eta = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    return x, eta, k


def _compiled_text(fn, *args, **static) -> str:
    return fn.lower(*args, **static).compile().as_text()


@pytest.mark.parametrize("method", ["loop", "threshold"])
def test_row_topk_compiles(bucket, method):
    """ops.row_topk: the whole-row loop kernel, or the column-tiled
    threshold kernel."""
    x, _, k = bucket
    assert "tpu_custom_call" in _compiled_text(
        ops.row_topk, x, k=k, method=method, interpret=False)


def test_row_topk_threshold_whole_row_compiles(bucket):
    x, _, k = bucket
    rows = x.shape[0] // 8 * 8  # whole 8-row blocks, as ops pads to
    x8 = jax.ShapeDtypeStruct((rows, x.shape[1]), x.dtype,
                              sharding=x.sharding)
    fn = jax.jit(row_topk_pallas,
                 static_argnames=("k", "selection", "interpret"))
    assert "tpu_custom_call" in _compiled_text(
        fn, x8, k=k, selection="threshold", interpret=False)


@pytest.mark.parametrize("method", ["loop", "threshold"])
def test_fused_memsgd_compiles(bucket, method):
    x, eta, k = bucket
    assert "tpu_custom_call" in _compiled_text(
        ops.fused_memsgd_update, x, x, eta, k=k, method=method,
        interpret=False)
