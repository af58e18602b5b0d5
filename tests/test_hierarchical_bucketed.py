"""Two-level pod-aware bucketed sync tests (repro.core.distributed).

Per-bucket pod-k resolution, per-level byte accounting, and the
mass-capture autotuner — pure accounting, no devices. Then the property
the scheme lives or dies by, checked on a REAL 8-device 2-pod mesh in
the file's one child (tests/_multidevice.py): exact mass conservation
across BOTH residual levels and packed == unpacked bit-identity.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import buckets as bk
from repro.core.distributed import (
    SyncConfig,
    autotune_pod_ratios,
    bucketed_message_bytes,
)

from _multidevice import probe_fixture
from tests._hypothesis_compat import given, settings, st


def _tree(key=0, heavy=1.0):
    ks = jax.random.split(jax.random.PRNGKey(key), 2)
    w = jax.random.normal(ks[0], (64, 2048))
    w = jnp.sign(w) * jnp.abs(w) ** heavy  # heavy > 1: fatter tails
    return {"w": w, "b": jax.random.normal(ks[1], (48,))}


def _plan(tree):
    return bk.make_plan(tree, cols=1024, dense_below=1024)


def test_pod_k_for_bucket_overrides_global_ratio():
    cfg = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_ratio=0.01, pod_ratios=(1.0, 0.05))
    # bucket 1 uses its own ratio: 0.05 * 1024 ~ 51
    assert cfg.pod_k_for_bucket(1, 1024) == 51
    # beyond the tuple RAISES — the old silent fallback to the global
    # pod_ratio quietly desynced byte accounting from the wire layout
    with pytest.raises(ValueError, match="index-aligned"):
        cfg.pod_k_for_bucket(7, 1024)
    # without per-bucket ratios everything falls back to the global ratio
    cfg2 = dataclasses.replace(cfg, pod_ratios=None)
    assert cfg2.pod_k_for_bucket(1, 1024) == cfg2.pod_k_for(1024) == 10


def test_pod_ratios_must_align_with_plan():
    """A pod_ratios tuple that is not index-aligned with the bucket plan
    is rejected at every accounting/sync/delta entry point."""
    from repro.core.distributed import validate_pod_ratios
    from repro.launch.delta_stream import make_delta_spec

    plan = _plan(_tree())  # 2 buckets
    short = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                       pod_ratios=(1.0,), bucketed=True)
    with pytest.raises(ValueError, match="2-bucket plan"):
        validate_pod_ratios(short, plan)
    with pytest.raises(ValueError, match="2-bucket plan"):
        bucketed_message_bytes(short, plan)
    with pytest.raises(ValueError, match="2-bucket plan"):
        make_delta_spec(plan, short, workers=8, n_pods=2)
    # aligned ratios pass
    ok = dataclasses.replace(short, pod_ratios=(1.0, 0.05))
    validate_pod_ratios(ok, plan)
    assert bucketed_message_bytes(ok, plan) > 0


def test_by_level_accounting_sums_and_beats_flat():
    plan = _plan(_tree())
    dense_nb = sum(
        s.rows * s.cols * 4 for s in plan.buckets if s.kind == "dense"
    )
    for wire in ("packed", "unpacked"):
        two_cfg = SyncConfig(ratio=0.02, strategy="hierarchical",
                             pod_axis="pod", pod_ratios=(1.0, 0.02),
                             wire=wire, bucketed=True)
        lv = bucketed_message_bytes(two_cfg, plan, by_level=True)
        # dense buckets move ~size bytes at BOTH levels; sparse levels
        # split exactly
        assert lv["intra"] + lv["cross"] == lv["total"] + dense_nb
        # the scalar form keeps its historical meaning
        assert bucketed_message_bytes(two_cfg, plan) == lv["total"]
        flat_cfg = SyncConfig(ratio=0.02, strategy="sparse_allgather",
                              pod_axis="pod", wire=wire, bucketed=True)
        flat = bucketed_message_bytes(flat_cfg, plan, by_level=True,
                                      n_data=4)
        # flat re-ships the concatenated data-axis buffer across pods;
        # the two-level summary (k_pod == k_row here) is strictly smaller
        assert lv["cross"] < flat["cross"]
        # per-worker emitted message is identical at level 1
        assert lv["intra"] == flat["intra"]


def test_by_level_flat_needs_n_data():
    plan = _plan(_tree())
    cfg = SyncConfig(ratio=0.02, strategy="sparse_allgather",
                     pod_axis="pod", bucketed=True)
    with pytest.raises(ValueError, match="n_data"):
        bucketed_message_bytes(cfg, plan, by_level=True)
    # dense strategy never consults n_data: the all-reduce moves
    # ~buffer-size bytes at each level
    dense = SyncConfig(strategy="dense", pod_axis="pod", bucketed=True)
    lv = bucketed_message_bytes(dense, plan, by_level=True)
    total = sum(s.rows * s.cols * 4 for s in plan.buckets)
    assert lv["intra"] == lv["cross"] == lv["total"] == total


@settings(max_examples=5, deadline=None)
@given(heavy=st.sampled_from([3.0, 1.0]),
       target=st.floats(min_value=0.5, max_value=0.99))
def test_autotune_within_bounds_and_tail_sensitive(heavy, target):
    """Autotuned pod k always lands in [k_min, support bound], and a
    heavier-tailed bucket never needs MORE slots than a flatter one at
    the same target."""
    cfg = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_mass_target=float(target))
    n_data = 4
    plan = _plan(_tree())
    for h, label in ((heavy, "sampled"), (1.0, "flat")):
        bufs = bk.pack(plan, _tree(heavy=h), dtype=jnp.float32)
        ratios = autotune_pod_ratios(cfg, plan, bufs, n_data=n_data)
        assert len(ratios) == len(plan.buckets)
        for spec, r in zip(plan.buckets, ratios):
            if spec.kind == "dense":
                assert r == 1.0
                continue
            k = int(round(r * spec.cols))
            support = min(spec.cols, n_data * cfg.k_for(spec.cols))
            assert cfg.k_min <= k <= support, (label, k, support)
        if h == heavy:
            sampled = ratios
    flat_bufs = bk.pack(plan, _tree(heavy=1.0), dtype=jnp.float32)
    flat_ratios = autotune_pod_ratios(cfg, plan, flat_bufs, n_data=n_data)
    assert sampled[1] <= flat_ratios[1] + 1e-9


def test_autotune_shard_simulation_sees_overlap():
    """With per-shard buffers the autotuner simulates the pod stage.
    Perfectly correlated shards -> the pod mean's support collapses to
    k_row, so the tuned k never exceeds it (a 4x smaller wire than the
    support bound at n_data=4)."""
    cfg = SyncConfig(ratio=0.02, strategy="hierarchical", pod_axis="pod",
                     pod_mass_target=0.99)
    plan = _plan(_tree())
    buf = bk.pack(plan, _tree(), dtype=jnp.float32)
    identical = [jnp.stack([b] * 4) for b in buf]  # 4 identical shards
    ratios = autotune_pod_ratios(cfg, plan, identical, n_data=4)
    k_row = cfg.k_for(plan.buckets[1].cols)
    assert int(round(ratios[1] * plan.buckets[1].cols)) <= k_row
    # decorrelated shards need more slots than perfectly aligned ones
    mixed = [jnp.stack([bk.pack(plan, _tree(key=i), dtype=jnp.float32)[b]
                        for i in range(4)])
             for b in range(len(plan.buckets))]
    mixed_ratios = autotune_pod_ratios(cfg, plan, mixed, n_data=4)
    assert mixed_ratios[1] >= ratios[1]


def test_mass_capture_monotone_and_complete():
    buf = bk.pack(_plan(_tree()), _tree(), dtype=jnp.float32)[1]
    frac = np.asarray(bk.bucket_mass_capture(buf, buf.shape[1]))
    assert frac.shape == (buf.shape[1],)
    assert np.all(np.diff(frac) >= -1e-6)
    np.testing.assert_allclose(frac[-1], 1.0, atol=1e-5)
    # all-zero rows count as fully captured, not as 0/0
    z = jnp.zeros_like(buf)
    np.testing.assert_allclose(
        np.asarray(bk.bucket_mass_capture(z, 4)), 1.0
    )


_TWO_LEVEL_CASES = [(0.05, 0.1), (0.02, 0.05), (0.05, 1.0)]

probes = probe_fixture({
    f"two_level_{ratio}_{pod_ratio}": f"""
        from repro.core.selfcheck import two_level_selfcheck
        from repro.utils.compat import make_mesh

        rec = two_level_selfcheck(
            make_mesh((2, 4), ("pod", "data")),
            ratio={ratio}, pod_ratio={pod_ratio})
        """
    for ratio, pod_ratio in _TWO_LEVEL_CASES
}, timeout=100)


@pytest.mark.parametrize("case", _TWO_LEVEL_CASES, ids=str)
def test_two_level_conservation_and_wire_bit_identity(probes, case):
    """On a real 2-pod x 4-worker mesh (shared probe:
    ``repro.core.selfcheck.two_level_selfcheck``): (1) the two-level
    mass-conservation invariant mean_w(u) == update + mean_w(new_memory)
    holds exactly (both residual levels fold back into bucket memory),
    (2) packed and unpacked wires produce BITWISE identical updates and
    memories, (3) the bytes the sync realizes equal the static
    ``bucketed_message_bytes`` accounting. All three (ratio, pod_ratio)
    cases run in the file's one child."""
    ratio, pod_ratio = case
    rec = probes(f"two_level_{ratio}_{pod_ratio}")
    assert rec["bit_identical"]
    assert rec["conservation_max_err"] < 1e-5, rec
    assert rec["accounting_exact"], rec
    assert rec["accounted_bytes"]["packed"] < rec["accounted_bytes"]["unpacked"]
