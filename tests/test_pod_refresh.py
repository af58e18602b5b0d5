"""Runtime pod-k (k-padded wire) + live-refresh tests.

The masking/accounting machinery (no devices, or a tiny
in-process (1, 1) pod mesh), the live-k wire header, the autotune k
caps, and the delta-spec k_max support bound (the upward-refresh
regression). The dynamic==static / conservation / accounting probe on
a REAL 8-device 2-pod mesh runs in the file's one child
(``repro.core.selfcheck.dynamic_k_selfcheck``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _multidevice import probe_fixture

from repro.core import buckets as bk
from repro.core import encoding as enc
from repro.core.distributed import (
    SyncConfig,
    autotune_pod_ratios,
    bucketed_message_bytes,
    bucketed_sync_gradients,
)
from repro.core.selfcheck import bitwise_equal
from repro.kernels.topk_select import mask_live_k
from repro.launch import delta_stream as ds


# -- mask_live_k: padded static selection == smaller static selection --------


def test_mask_live_k_prefix_equals_smaller_topk():
    """The first k_live slots of a contract-ordered top-k_max ARE the
    top-k_live selection; the masked tail is (0.0, 0)."""
    from repro.kernels.ref import row_topk_ref

    x = jax.random.normal(jax.random.PRNGKey(0), (6, 96))
    k_max, k_live = 16, 5
    v_max, i_max = row_topk_ref(x, k_max)
    v_small, i_small = row_topk_ref(x, k_live)
    vm, im = mask_live_k(v_max, i_max, jnp.int32(k_live))
    np.testing.assert_array_equal(np.asarray(vm[:, :k_live]),
                                  np.asarray(v_small))
    np.testing.assert_array_equal(np.asarray(im[:, :k_live]),
                                  np.asarray(i_small))
    assert np.all(np.asarray(vm[:, k_live:]) == 0.0)
    assert np.all(np.asarray(im[:, k_live:]) == 0)


def test_mask_live_k_jits_over_traced_k():
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    _, idx = jax.lax.top_k(jnp.abs(x), 8)
    vals = jnp.take_along_axis(x, idx, axis=-1)

    @jax.jit
    def f(k):
        return mask_live_k(vals, idx.astype(jnp.int32), k)

    v3, _ = f(jnp.int32(3))
    v8, _ = f(jnp.int32(8))  # same trace, different live k
    assert np.all(np.asarray(v3[:, 3:]) == 0.0)
    np.testing.assert_array_equal(np.asarray(v8), np.asarray(vals))


# -- live-k wire header ------------------------------------------------------


def test_encode_live_n_header_word():
    """The live count rides in header word LIVE_N_WORD without touching
    the static layout; decode of the padded message is unchanged and the
    masked tail scatters as no-ops."""
    spec = enc.WireSpec(3, 100, 8, "float32")
    vals = jax.random.normal(jax.random.PRNGKey(2), (3, 8))
    idx = jnp.tile(jnp.arange(8, dtype=jnp.int32), (3, 1))
    vals_m, idx_m = mask_live_k(vals, idx, jnp.int32(5))
    buf = jax.jit(
        lambda v, i, n: enc.encode(spec, v, i, live_n=n)
    )(vals_m, idx_m, jnp.int32(5))
    assert buf.shape == (spec.words,)
    assert int(buf[enc.LIVE_N_WORD]) == 5
    assert enc.live_n_of(buf) == 5
    # layout words untouched: the header still round-trips the spec
    assert enc.WireSpec.from_header(np.asarray(buf)) == spec
    v2, i2 = enc.decode(spec, buf)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(idx_m))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(vals_m))
    # a message without live_n reads back None (word 7 == 0, historical)
    plain = enc.encode(spec, vals, idx)
    assert enc.live_n_of(plain) is None


# -- pod_k_max / autotune caps ----------------------------------------------


def _plan2():
    tree = {"w": jax.ShapeDtypeStruct((64 * 256,), jnp.float32),
            "b": jax.ShapeDtypeStruct((40,), jnp.float32)}
    return bk.make_plan(tree, cols=256, dense_below=64)


def test_pod_k_max_for_bucket_bounds():
    plan = _plan2()
    cfg = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_ratios=(1.0, 0.02), bucketed=True, pod_dynamic=True)
    b = 1  # the sparse bucket ("b" packs first: dict key order)
    cols = plan.buckets[b].cols
    # support bound: n_data * k_row (k_row = 5 at ratio 0.02, cols 256)
    assert cfg.pod_k_max_for_bucket(b, cols, n_data=4) == min(
        cols, 4 * cfg.k_for(cols))
    # never below the statically configured pod k
    big = dataclasses.replace(cfg, pod_ratios=(1.0, 0.5))
    assert cfg.pod_k_for_bucket(b, cols) <= cfg.pod_k_max_for_bucket(
        b, cols, n_data=4)
    assert big.pod_k_max_for_bucket(b, cols, n_data=4) == \
        big.pod_k_for_bucket(b, cols)
    # pod_k_max_ratio tightens the cap (but not below the static k)
    capped = dataclasses.replace(cfg, pod_k_max_ratio=8 / cols)
    assert capped.pod_k_max_for_bucket(b, cols, n_data=4) == 8


def test_autotune_k_caps_clamp():
    plan = _plan2()
    cfg = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_mass_target=0.999)
    # flat buffers at a 0.999 target want (nearly) the full support
    u = [jnp.ones(s.shape, jnp.float32) for s in plan.buckets]
    free = autotune_pod_ratios(cfg, plan, u, n_data=4)
    capped = autotune_pod_ratios(cfg, plan, u, n_data=4, k_caps=[1, 3])
    b = 1
    assert int(round(free[b] * plan.buckets[b].cols)) > 3
    assert int(round(capped[b] * plan.buckets[b].cols)) == 3


def test_dynamic_accounting_padded_vs_effective():
    plan = _plan2()
    dyn = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_ratios=(1.0, 0.02), bucketed=True,
                     pod_dynamic=True, wire="packed")
    with pytest.raises(ValueError, match="n_data"):
        bucketed_message_bytes(dyn, plan)  # padded size needs n_data
    padded = bucketed_message_bytes(dyn, plan, by_level=True, n_data=4)
    live = bucketed_message_bytes(dyn, plan, by_level=True, n_data=4,
                                  pod_ks=(1, 2))
    assert live["cross"] < padded["cross"]
    assert live["intra"] == padded["intra"]  # level 1 is not padded
    # effective accounting equals a static config at the same k
    static = dataclasses.replace(
        dyn, pod_dynamic=False,
        pod_ratios=(1.0, 2 / plan.buckets[1].cols))
    assert live["cross"] == bucketed_message_bytes(
        static, plan, by_level=True)["cross"]


# -- dynamic == static on a tiny in-process pod mesh -------------------------


def test_dynamic_pod_k_matches_static_single_device():
    """(pod=1, data=1) mesh fits in-process: the k-padded dynamic path
    fed a constant live k is bitwise identical to the static path —
    compared on the APPLIED update (params - update) and the memory, the
    state that actually persists (the raw update's all-zero columns may
    differ in zero SIGN at k_live=1: XLA's no-reduce special case; see
    ``mask_live_k``) — for several live ks through one compiled step
    that takes the live k as an argument."""
    from repro.core.selfcheck import compiled_sync
    from repro.utils.compat import make_mesh

    if jax.device_count() < 1:
        pytest.skip("no devices")
    mesh = make_mesh((1, 1), ("pod", "data"))
    tree = {"w": jax.random.normal(jax.random.PRNGKey(3), (8, 192))}
    plan = bk.make_plan(tree, cols=64, dense_below=32)
    mem = tuple(jnp.zeros((1,) + s.shape, jnp.float32)
                for s in plan.buckets)
    gs = jax.tree.map(lambda x: x[None], tree)

    for wire in ("unpacked", "packed"):
        dyn = SyncConfig(ratio=0.1, strategy="hierarchical",
                         data_axes=("data",), pod_axis="pod",
                         bucketed=True, bucket_cols=64, wire=wire,
                         pod_ratios=(0.05,), pod_dynamic=True)
        dyn_step = compiled_sync(mesh, dyn, plan, 0.4)  # one program, any k
        for k_live in (1, 3, 6):
            static = dataclasses.replace(
                dyn, pod_dynamic=False, pod_ratios=(k_live / 64,))
            upd_s, mem_s = compiled_sync(mesh, static, plan, 0.4)(mem, gs)
            upd_d, mem_d = dyn_step(mem, gs,
                                    jnp.asarray([k_live], jnp.int32))
            applied_s = jax.tree.map(lambda t, u: t - u, tree, upd_s)
            applied_d = jax.tree.map(lambda t, u: t - u, tree, upd_d)
            assert bitwise_equal((applied_s, mem_s), (applied_d, mem_d)), \
                (wire, k_live)


def test_pod_dynamic_requires_pod_ks():
    plan = _plan2()
    cfg = SyncConfig(ratio=0.02, strategy="hierarchical",
                     data_axes=("data",), pod_axis="pod", bucketed=True,
                     pod_dynamic=True)
    mem = tuple(jnp.zeros(s.shape, jnp.float32) for s in plan.buckets)
    tree = {"w": jnp.zeros((64 * 256,), jnp.float32),
            "b": jnp.zeros((40,), jnp.float32)}
    with pytest.raises(ValueError, match="pod_ks"):
        bucketed_sync_gradients(cfg, plan, mem, tree, jnp.float32(0.1))
    # the converse misconfiguration is loud too: pod_dynamic on a flat/
    # pod-less sync would silently drop the k schedule
    for bad in (dataclasses.replace(cfg, strategy="sparse_allgather"),
                dataclasses.replace(cfg, pod_axis=None)):
        with pytest.raises(ValueError, match="silently ignore"):
            bucketed_sync_gradients(
                bad, plan, mem, tree, jnp.float32(0.1),
                pod_ks=jnp.asarray([1, 2], jnp.int32))


# -- delta spec follows k_max (the upward-refresh regression) ----------------


def test_delta_spec_survives_upward_k_refresh():
    """make_delta_spec sized from the step-0 pod k would overflow after
    a refresh RAISES k; with pod_dynamic it is sized at the bucket's
    k_max, so an update whose support reflects any live k <= k_max
    round-trips exactly."""
    plan = _plan2()
    n_pods, n_data = 2, 4
    cols = plan.buckets[1].cols
    k0, k_hi = 2, 12  # step-0 autotuned k, refreshed-upward k
    dyn = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_ratios=(1.0, k0 / cols), bucketed=True,
                     pod_dynamic=True)
    k_max = dyn.pod_k_max_for_bucket(1, cols, n_data)
    assert k0 < k_hi <= k_max
    dspec = ds.make_delta_spec(plan, dyn, workers=n_pods * n_data,
                               n_pods=n_pods)
    assert dspec.wires[1].k == min(cols, n_pods * k_max)
    # the OLD sizing (current pod k) could not carry the k_hi support
    static = dataclasses.replace(dyn, pod_dynamic=False)
    old = ds.make_delta_spec(plan, static, workers=n_pods * n_data,
                             n_pods=n_pods)
    assert old.wires[1].k == n_pods * k0 < n_pods * k_hi

    # simulate the post-refresh update: n_pods * k_hi nonzeros per row
    rng = np.random.default_rng(0)
    buf = np.zeros(plan.buckets[1].shape, np.float32)
    for r in range(buf.shape[0]):
        pos = rng.choice(cols, size=n_pods * k_hi, replace=False)
        buf[r, pos] = rng.standard_normal(n_pods * k_hi)
    bufs = [jnp.zeros(plan.buckets[0].shape, jnp.float32),
            jnp.asarray(buf)]
    msgs = ds.encode_delta_bufs(dspec, bufs)
    dec = ds.decode_delta(dspec, msgs)
    rec = bk.pack(plan, dec, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(rec[1]), buf)
    # the old spec drops mass for the same update (the regression)
    old_rec = bk.pack(
        plan, ds.decode_delta(old, ds.encode_delta_bufs(old, bufs)),
        dtype=jnp.float32)
    assert not np.array_equal(np.asarray(old_rec[1]), buf)


# -- real 2-pod mesh probe (the file's one child) ---------------------------


probes = probe_fixture({
    "dynamic_k": """
        from repro.core.selfcheck import dynamic_k_selfcheck
        from repro.utils.compat import make_mesh

        rec = dynamic_k_selfcheck(make_mesh((2, 4), ("pod", "data")))
        """,
}, timeout=90)


def test_dynamic_k_selfcheck_on_2pod_mesh(probes):
    """dynamic==static bitwise, conservation under a switched live k,
    and padded accounting, on a REAL 8-device 2-pod mesh (shared probe:
    ``repro.core.selfcheck.dynamic_k_selfcheck`` — the same harness the
    refresh bench runs)."""
    rec = probes("dynamic_k")
    assert rec["dynamic_matches_static"], rec
    assert rec["conservation_max_err"] < 1e-5, rec
    assert rec["accounting_exact"], rec
