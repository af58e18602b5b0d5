"""Qsparse-local-SGD tests: composed contraction theory, the
amortized byte accounting, the H local-steps accumulator, and the
8-device selfcheck of the H=1 bitwise identity + quantized
mass conservation (``repro.core.selfcheck.local_quant_selfcheck``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _multidevice import probe_fixture

from repro.core import buckets as bk
from repro.core import theory
from repro.core.distributed import (
    SyncConfig,
    WireConfig,
    amortized_bytes_per_step,
    bucketed_message_bytes,
)


# -- theory: the composed Q_s ∘ top_k contraction ---------------------------


def test_composed_contraction_reduces_to_topk():
    assert theory.composed_contraction(1000, 10) == 10 / 1000


def test_composed_contraction_empirical_bound():
    """Measured E||Q_s(top_k(x)) - x||^2 over random draws stays within
    the (1 - delta) ||x||^2 bound of ``composed_contraction``."""
    from repro.kernels.ref import row_topk_ref
    from repro.optim.qsgd import quantize_rows
    from repro.core.encoding import dequantize_rows

    d, k = 256, 16
    # beta_k = min(k/s^2, sqrt(k)/s) >= 1 at s=1: the bound is vacuous
    # (delta = 0) — the composition only contracts once s beats sqrt(k)
    assert theory.composed_contraction(d, k, 1) == 0.0
    for s in (5, 15):
        delta = theory.composed_contraction(d, k, s)
        assert 0.0 < delta <= k / d
        errs, norms2 = [], []
        for i in range(30):
            x = jax.random.normal(jax.random.PRNGKey(i), (1, d))
            vals, idx = row_topk_ref(x, k)
            n, c = quantize_rows(vals, s, jax.random.PRNGKey(1000 + i))
            q = dequantize_rows(n, c, s)
            recon = jnp.zeros((1, d)).at[0, idx[0]].add(q[0])
            errs.append(float(jnp.sum((recon - x) ** 2)))
            norms2.append(float(jnp.sum(x**2)))
        measured = sum(errs) / sum(norms2)
        assert measured <= (1.0 - delta) + 1e-6, (s, measured, delta)


def test_local_steps_residual_factor():
    assert theory.local_steps_residual_factor(1) == 1.0
    assert theory.local_steps_residual_factor(4) == 16.0
    with pytest.raises(ValueError):
        theory.local_steps_residual_factor(0)


# -- amortized byte accounting ----------------------------------------------


def _plan():
    return bk.make_plan(
        {"w": jax.ShapeDtypeStruct((16, 384), jnp.float32),
         "b": jax.ShapeDtypeStruct((40,), jnp.float32)},
        cols=128, dense_below=64,
    )


def test_amortized_bytes_scale_one_over_h():
    plan = _plan()
    base = SyncConfig(ratio=0.05, bucketed=True, bucket_cols=128,
                      wire=WireConfig(wire="packed", quant=15))
    full = bucketed_message_bytes(base, plan)
    for h in (1, 2, 4, 8):
        cfg = SyncConfig.preset("qsparse_local", ratio=0.05,
                                bucket_cols=128, local_steps=h)
        assert amortized_bytes_per_step(cfg, plan) == full / h


def test_amortized_bytes_by_level_dict():
    plan = _plan()
    cfg = SyncConfig(strategy="hierarchical", ratio=0.05, bucketed=True,
                     bucket_cols=128, local_steps=4,
                     pod=SyncConfig.preset("pod_budgeted").pod,
                     wire=WireConfig(wire="packed"))
    cfg = cfg.with_pod(axis="pod", dynamic=False)
    lv_full = bucketed_message_bytes(cfg, plan, by_level=True, n_data=4)
    lv = amortized_bytes_per_step(cfg, plan, by_level=True, n_data=4)
    assert set(lv) == set(lv_full)
    for key in lv:
        assert lv[key] == lv_full[key] / 4


def test_quant_shrinks_accounted_bytes():
    plan = _plan()
    exact = SyncConfig(ratio=0.05, bucketed=True, bucket_cols=128,
                       wire=WireConfig(wire="packed"))
    quant = exact.with_wire(quant=15)
    assert bucketed_message_bytes(quant, plan) < \
        bucketed_message_bytes(exact, plan)


# -- the bucket-space local accumulator -------------------------------------


def test_accumulate_local_matches_pack_sum():
    """acc after H accumulations == sum_h eta_h * pack(g_h), exactly
    (pack is elementwise-linear placement, no arithmetic)."""
    plan = _plan()
    tree = lambda i: {
        "w": jax.random.normal(jax.random.PRNGKey(i), (16, 384)),
        "b": jax.random.normal(jax.random.PRNGKey(100 + i), (40,)),
    }
    acc = bk.init_local_accum(plan)
    etas = [0.3, 0.1, 0.25]
    for h, eta in enumerate(etas):
        acc = bk.accumulate_local(plan, acc, tree(h),
                                  jnp.float32(eta))
    want = [jnp.zeros(s.shape, jnp.float32) for s in plan.buckets]
    for h, eta in enumerate(etas):
        bufs = bk.pack(plan, tree(h), dtype=jnp.float32)
        want = [w + jnp.float32(eta) * b for w, b in zip(want, bufs)]
    for a, w in zip(acc, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   rtol=0, atol=1e-6)


def test_make_train_step_rejects_local_steps_without_buckets():
    from repro.configs import get_smoke_config
    from repro.launch.train import TrainConfig, make_train_step
    from repro.models import build_model
    from repro.utils.compat import make_mesh

    model = build_model(get_smoke_config("granite-3-8b"))
    mesh = make_mesh((1, 1), ("data", "model"))
    tc = TrainConfig(sync=SyncConfig(ratio=0.02, local_steps=2))
    with pytest.raises(ValueError, match="local_steps"):
        make_train_step(model, mesh, tc)


# -- 8-device selfcheck (the file's one child) -----------------------------


probes = probe_fixture({
    "local_quant": """
        from repro.core.selfcheck import local_quant_selfcheck
        from repro.utils.compat import make_mesh

        rec = local_quant_selfcheck(make_mesh((2, 4), ("pod", "data")))
        """,
}, timeout=100)


def test_local_quant_selfcheck(probes):
    """On a real 2-pod x 4-worker mesh: (1) the H=1 accumulator path
    (init_local_accum + accumulate_local + sync(grad_bufs, eta=1)) is
    BITWISE identical to the direct per-step sync on all three
    strategies, (2) quantized mass conservation mean_w(u) == update +
    mean_w(new_mem) holds exactly, (3) packed and unpacked quantized
    wires agree bitwise, (4) realized bytes == the quant-aware
    accounting, (5) amortized bytes scale exactly 1/H."""
    rec = probes("local_quant")
    assert rec["h1_accum_bitwise"], rec
    assert rec["quant_conservation_max_err"] < 1e-5, rec
    assert rec["quant_bit_identical"], rec
    assert rec["quant_accounting_exact"], rec
    assert rec["amortized_ratio_exact"], rec
