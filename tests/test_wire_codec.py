"""Packed sparse wire codec + delta stream tests (repro.core.encoding,
repro.launch.delta_stream).

Multi-worker cases run in the file's one child with 8 fake CPU devices
(tests/_multidevice.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from _multidevice import probe_fixture

from repro.core import buckets as bk
from repro.core import encoding as enc
from repro.core.distributed import SyncConfig, bucketed_message_bytes


def _pairs(rows, cols, k, value_dtype, seed=0):
    """Random (vals, idx) in the shapes the codec expects (idx need not
    be distinct — the codec is agnostic)."""
    kv, ki = jax.random.split(jax.random.PRNGKey(seed))
    vals = jax.random.normal(kv, (rows, k)).astype(jnp.dtype(value_dtype))
    idx = jax.random.randint(ki, (rows, k), 0, cols).astype(jnp.int32)
    return vals, idx


def _assert_roundtrip(spec, vals, idx):
    buf = jax.jit(lambda v, i: enc.encode(spec, v, i))(vals, idx)
    assert buf.dtype == jnp.uint32
    assert buf.shape == (spec.words,)
    v2, i2 = jax.jit(lambda b: enc.decode(spec, b))(buf)
    np.testing.assert_array_equal(np.asarray(i2), np.asarray(idx))
    # values round-trip BITWISE in the wire dtype
    want = np.asarray(vals.astype(jnp.dtype(spec.value_dtype)))
    got = np.asarray(v2)
    assert got.dtype == want.dtype
    assert np.array_equal(
        got.view(np.uint8), want.view(np.uint8)
    ), "wire values not bitwise-identical"
    return buf


@settings(max_examples=40, deadline=None)
@given(
    rows=st.integers(min_value=1, max_value=7),
    # the no-hypothesis fallback sweep takes SPREAD samples — indices
    # {0, 2, 4, 5, 7} of this 8-element list — so the must-cover shapes
    # (pow2, cols=1 with its 0-bit index packing, tiny, cols=2,
    # non-pow2) sit at those positions; the others only run under real
    # hypothesis
    cols=st.sampled_from([1024, 17, 1, 100, 3, 2, 1000, 700]),
    k_mode=st.sampled_from(["one", "interior", "full"]),
    value_dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_roundtrip_property(rows, cols, k_mode, value_dtype):
    """decode(encode(v, i)) == (v, i) for non-power-of-two cols and the
    k=1 / k=cols edges, f32 and bf16 values."""
    k = {"one": 1, "interior": max(1, cols // 3), "full": cols}[k_mode]
    spec = enc.WireSpec(rows, cols, k, value_dtype)
    vals, idx = _pairs(rows, cols, k, value_dtype, seed=rows * cols + k)
    _assert_roundtrip(spec, vals, idx)


def test_roundtrip_tie_heavy_topk_selection():
    """Tie-heavy input through a real per-row top-k: the selected pairs
    survive the wire bitwise (including repeated magnitudes and signs)."""
    from repro.kernels.ref import row_topk_ref

    R, C, k = 6, 257, 16
    u = jnp.round(jax.random.normal(jax.random.PRNGKey(3), (R, C)) * 2) / 2
    vals, idx = row_topk_ref(u, k)
    spec = enc.WireSpec(R, C, k, "float32")
    _assert_roundtrip(spec, vals, idx)


def test_roundtrip_special_values():
    """Denormals, zeros, infs and extreme indices survive the wire."""
    C = 1000
    vals = jnp.array(
        [[0.0, -0.0, 1e-40, -1e-40, jnp.inf, -jnp.inf, 3.14, -2.5]],
        jnp.float32,
    )
    idx = jnp.array([[0, C - 1, 1, C - 2, 511, 512, 3, 999]], jnp.int32)
    spec = enc.WireSpec(1, C, 8, "float32")
    _assert_roundtrip(spec, vals, idx)


def test_header_is_self_describing():
    spec = enc.WireSpec(5, 300, 7, "bfloat16")
    vals, idx = _pairs(5, 300, 7, "bfloat16")
    buf = enc.encode(spec, vals, idx)
    assert enc.WireSpec.from_header(np.asarray(buf)) == spec


def test_dense_kind_roundtrip():
    spec = enc.WireSpec(2, 77, 77, "float32", kind="dense")
    vals = jax.random.normal(jax.random.PRNGKey(1), (2, 77))
    buf = enc.encode(spec, vals)
    v2, i2 = enc.decode(spec, buf)
    assert i2 is None
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(vals))


# -- header-aware repack transport --------------------------------------------


def _padded_message(rows, cols, k_max, live_n, value_dtype, seed=0):
    """A contract-ordered ``k_max``-padded message: per-row top-``k_max``
    of a random buffer with the tail past ``live_n`` masked to the
    (-0.0, 0) identity — exactly what the dynamic pod stage ships."""
    from repro.kernels.topk_select import mask_live_k

    u = jax.random.normal(jax.random.PRNGKey(seed), (rows, cols))
    _, idx = jax.lax.top_k(jnp.abs(u), k_max)
    vals = jnp.take_along_axis(u, idx, axis=-1).astype(jnp.dtype(value_dtype))
    vals, idx = mask_live_k(vals, idx.astype(jnp.int32), live_n)
    spec = enc.WireSpec(rows, cols, k_max, value_dtype)
    return spec, enc.encode(spec, vals, idx, live_n=live_n)


def test_decode_raises_on_corrupt_live_n_header():
    """A header claiming more live slots than the message lays out is
    corruption (a decoder honoring it would read past the value
    section) — both ``decode`` and ``live_n_of`` must refuse it."""
    spec = enc.WireSpec(3, 100, 5, "float32")
    vals, idx = _pairs(3, 100, 5, "float32")
    buf = enc.encode(spec, vals, idx, live_n=2)
    bad = buf.at[enc.LIVE_N_WORD].set(spec.n_sel + 1)
    with pytest.raises(ValueError, match="live_n"):
        enc.decode(spec, bad)
    with pytest.raises(ValueError, match="live_n"):
        enc.live_n_of(bad)
    # the uncorrupted message still decodes and reports its live count
    enc.decode(spec, buf)
    assert enc.live_n_of(buf) == 2


@settings(max_examples=40, deadline=None)
@given(
    rows=st.integers(min_value=1, max_value=6),
    # <= 5 elements: the no-hypothesis fallback sweep cycles ALL of them,
    # so every must-cover shape (non-pow2, pow2, cols=1, tiny) runs
    cols=st.sampled_from([100, 64, 1, 700, 5]),
    live_mode=st.sampled_from(["zero", "one", "interior", "full"]),
    value_dtype=st.sampled_from(["float32", "bfloat16"]),
)
def test_repack_roundtrip_property(rows, cols, live_mode, value_dtype):
    """repack/repad round the padded buffer BITWISE for every live_n
    edge (0, 1, interior, k_max), non-pow2 cols and both value tiers;
    the repacked message decodes to exactly the live prefix of the
    padded decode."""
    k_max = max(1, (cols + 1) // 2)
    live = {
        "zero": 0,
        "one": min(1, k_max),
        "interior": max(1, k_max // 2),
        "full": k_max,
    }[live_mode]
    spec, buf = _padded_message(
        rows, cols, k_max, live, value_dtype, seed=rows * cols + live
    )
    # live_n=0 must be passed explicitly: the header stamps 0, which the
    # wire convention reads as "all slots live" (auto-detect no-ops)
    small_spec, small_buf = enc.repack(spec, buf, live_n=live)
    if 0 < live < k_max:
        # header auto-detection agrees with the explicit argument
        auto_spec, auto_buf = enc.repack(spec, buf)
        assert auto_spec == small_spec
        assert np.array_equal(np.asarray(auto_buf), np.asarray(small_buf))
    # the wire shrinks to the live payload (k=max(1, live)), never grows
    assert small_spec.k == (max(1, live) if live < k_max else k_max)
    assert small_spec.nbytes <= spec.nbytes
    # repad restores the padded buffer bitwise (invariant 10's currency)
    repadded = enc.repad(spec, small_spec, small_buf)
    assert np.array_equal(np.asarray(repadded), np.asarray(buf))
    # decode(repack(buf)) == the live prefix of decode(buf), bitwise
    v_small, i_small = enc.decode(small_spec, small_buf)
    v_pad, i_pad = enc.decode(spec, buf)
    n = small_spec.k
    assert np.array_equal(
        np.asarray(v_small).view(np.uint8),
        np.asarray(v_pad[:, :n]).view(np.uint8),
    )
    np.testing.assert_array_equal(
        np.asarray(i_small), np.asarray(i_pad[:, :n])
    )
    # live_n survives the round trip: repack stamps the small header
    assert enc.live_n_of(repadded) == enc.live_n_of(buf)


# -- accounting == what the codec actually emits ------------------------------


def test_wirespec_accounting_matches_encoded_bytes():
    for rows, cols, k, vd in [
        (64, 1024, 64, "float32"),
        (64, 1024, 64, "bfloat16"),
        (3, 700, 5, "bfloat16"),
        (1, 1, 1, "float32"),
    ]:
        spec = enc.WireSpec(rows, cols, k, vd)
        vals, idx = _pairs(rows, cols, k, vd)
        buf = enc.encode(spec, vals, idx)
        encoded_bits = buf.size * buf.dtype.itemsize * 8
        assert spec.nbits == encoded_bits
        assert spec.nbytes * 8 == encoded_bits


def test_bucketed_message_bytes_matches_encoded_buffers():
    """The static accounting equals the realized bytes of the buffers the
    packed sync would all-gather (per-bucket row-local index_bits)."""
    tree = {
        "w": jax.random.normal(jax.random.PRNGKey(0), (100, 300)),
        "h": jax.random.normal(jax.random.PRNGKey(1), (220, 90)).astype(
            jnp.bfloat16
        ),
        "b": jax.random.normal(jax.random.PRNGKey(2), (40,)),
    }
    plan = bk.make_plan(tree, cols=512)
    for vd in ("float32", "bfloat16"):
        cfg = SyncConfig(ratio=0.02, wire="packed", value_dtype=vd,
                         bucketed=True, bucket_cols=512)
        realized = 0
        for spec in plan.buckets:
            if spec.kind == "dense":
                realized += spec.rows * spec.cols * 4
                continue
            k = cfg.k_for(spec.cols)
            wspec = enc.WireSpec(spec.rows, spec.cols, k, vd)
            vals, idx = _pairs(spec.rows, spec.cols, k, vd)
            realized += enc.encode(wspec, vals, idx).size * 4
        assert bucketed_message_bytes(cfg, plan) == realized
        # packed accounting uses the bucket's ceil(log2 cols), not 32
        unpacked = bucketed_message_bytes(
            SyncConfig(ratio=0.02, value_dtype=vd, bucketed=True,
                       bucket_cols=512), plan)
        assert bucketed_message_bytes(cfg, plan) < unpacked


def test_sparse_bits_accounts_value_dtype():
    assert enc.value_bits("bfloat16") == 16
    assert enc.value_bits(jnp.float32) == 32
    assert enc.sparse_bits(2**16, 10, enc.value_bits("bfloat16")) == 10 * (
        16 + 16
    )
    assert enc.memsgd_message_bits(2**16, 10, "bfloat16") == 10 * (16 + 16)
    assert enc.memsgd_message_bits(2**16, 10) == 10 * (32 + 16)


def test_message_bytes_packed_smaller_than_unpacked():
    from repro.core.distributed import message_bytes

    params = {"w": jnp.zeros((128, 1024))}
    base = SyncConfig(ratio=64 / 1024)
    packed = message_bytes(
        SyncConfig(ratio=64 / 1024, wire="packed",
                   value_dtype="bfloat16"), params)
    assert packed * 1.8 <= message_bytes(base, params)


# -- packed sync == unpacked sync, end to end ---------------------------------


probes = probe_fixture({
    "packed_vs_unpacked": """
        import dataclasses
        from repro.core import buckets as bk
        from repro.core.distributed import (SyncConfig,
                                            bucketed_sync_gradients,
                                            sparse_sync_gradients)
        from repro.core.selfcheck import bitwise_equal
        from repro.utils.compat import make_mesh, shard_map
        from jax.sharding import PartitionSpec as P

        tree = {
            "w1": jax.random.normal(jax.random.PRNGKey(0), (8, 100, 300)),
            "w2": jax.random.normal(jax.random.PRNGKey(1), (8, 450, 40)),
            "b": jax.random.normal(jax.random.PRNGKey(2), (8, 64)),
        }
        plan = bk.make_plan(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), tree),
            cols=512)

        def run(cfg, mesh, axes):
            W = 8
            mem = tuple(jnp.zeros((W,) + s.shape, jnp.float32)
                        for s in plan.buckets)
            def body(mem, tree):
                mem = jax.tree.map(lambda m: m[0], mem)
                tree = jax.tree.map(lambda t: t[0], tree)
                upd, new_mem, _ = bucketed_sync_gradients(
                    cfg, plan, mem, tree, jnp.float32(0.3))
                return upd, jax.tree.map(lambda m: m[None], new_mem)
            spec_w = jax.tree.map(lambda _: P(axes), mem)
            return jax.jit(shard_map(
                body, mesh=mesh,
                in_specs=(spec_w, jax.tree.map(lambda _: P(axes), tree)),
                out_specs=(jax.tree.map(lambda _: P(), {k: 0 for k in tree}),
                           spec_w),
                axis_names=set(mesh.axis_names)))(mem, tree)

        results = {}
        flat_mesh = make_mesh((8,), ("data",))
        pod_mesh = make_mesh((2, 4), ("pod", "data"))
        for vd in ("float32", "bfloat16"):
            base = SyncConfig(ratio=0.02, bucketed=True, bucket_cols=512,
                              value_dtype=vd)
            for label, cfg, mesh, axes in (
                ("flat", base, flat_mesh, "data"),
                ("hier", dataclasses.replace(
                    base, strategy="hierarchical", pod_axis="pod",
                    pod_ratio=0.01), pod_mesh, ("pod", "data")),
            ):
                u1, m1 = run(cfg, mesh, axes)
                u2, m2 = run(dataclasses.replace(cfg, wire="packed"),
                             mesh, axes)
                results[f"{label}_{vd}"] = bool(
                    bitwise_equal(u1, u2) and bitwise_equal(m1, m2))

        # leaf-wise path (no buckets): batched layout, flat strategy
        def run_leaf(cfg):
            mem0 = jax.tree.map(
                lambda t: jnp.zeros(t.shape[1:], jnp.float32), tree)
            def body(tree):
                tree = jax.tree.map(lambda t: t[0], tree)
                return sparse_sync_gradients(
                    cfg, mem0, tree, jnp.float32(0.3))[:2]
            return jax.jit(shard_map(
                body, mesh=flat_mesh,
                in_specs=(jax.tree.map(lambda _: P("data"), tree),),
                out_specs=(jax.tree.map(lambda _: P(), tree),) * 2,
                axis_names={"data"}))(tree)

        leaf_cfg = SyncConfig(ratio=0.02, dense_below=256)
        u1, m1 = run_leaf(leaf_cfg)
        u2, m2 = run_leaf(dataclasses.replace(leaf_cfg, wire="packed"))
        results["leafwise_float32"] = bool(bitwise_equal(u1, u2)
                                           and bitwise_equal(m1, m2))
        rec = results
        """,
    "delta_stream": """
        from repro.configs import get_smoke_config
        from repro.models import build_model
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.train import (TrainConfig, make_train_step,
                                        init_train_state, state_shardings)
        from repro.launch.serve import apply_delta
        from repro.core.distributed import SyncConfig
        from repro.core.selfcheck import bitwise_equal
        from repro.data import token_batches
        from repro.data.pipeline import ShardedBatcher

        mesh = make_debug_mesh(4, 1)
        cfg = get_smoke_config("rwkv6-3b")
        model = build_model(cfg)
        tc = TrainConfig(optimizer="memsgd", eta=0.5, emit_deltas=True,
                         sync=SyncConfig(ratio=0.02, bucketed=True,
                                         wire="packed",
                                         selection="threshold_onehot"))
        params, memory, opt, count = init_train_state(
            model, mesh, tc, rng=jax.random.PRNGKey(0))
        replica = jax.tree.map(lambda x: jnp.array(np.asarray(x)), params)
        pshard, mshard, _, _ = state_shardings(model, mesh, tc)
        params = jax.device_put(params, pshard)
        memory = jax.device_put(memory, mshard)
        step = make_train_step(model, mesh, tc)
        dspec = step.delta_spec
        it = ShardedBatcher(mesh, token_batches(cfg.vocab_size, 8, 32,
                            seed=1), prefetch=0)
        streamed = 0
        for i, batch in enumerate(it):
            if i >= 3: break
            params, memory, opt, count, m, delta = step(
                params, memory, opt, count, batch)
            assert sum(b.size * 4 for b in delta) == dspec.nbytes
            streamed += dspec.nbytes
            replica = apply_delta(replica, dspec, delta)
        rec = {"bitwise": bool(bitwise_equal(params, replica)),
               "streamed": streamed,
               "dense": dspec.dense_nbytes * 3}
        """,
}, timeout=180)


@pytest.mark.slow
def test_packed_sync_identical_to_unpacked(probes):
    """Packed-wire sync is bit-identical to the unpacked path on an
    8-worker mesh, each run compiled: bucketed for both value dtypes,
    plus the leaf-wise path (flat + hierarchical share the same leaf
    sync functions)."""
    rec = probes("packed_vs_unpacked")
    assert all(rec.values()), rec


@pytest.mark.slow
def test_delta_stream_replica_tracks_trainer_bitwise(probes):
    """3 trainer steps with emit_deltas; streaming the packed deltas to a
    fresh replica reproduces the trainer's params bitwise."""
    rec = probes("delta_stream")
    assert rec["bitwise"]
    assert rec["streamed"] * 4 < rec["dense"]
