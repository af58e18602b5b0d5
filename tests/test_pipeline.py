"""Double-buffered bucket pipeline tests (repro.core.pipeline).

Fast tier: planner legality properties (permutation, per-bucket order,
depth-window bound), the exact classic double-buffer order at depth 2,
degeneration to strict sequential at depth 1, value-identity of the
in-jit executor across depths, and the host pipeline actually hiding an
``EmulatedLink``'s latency behind younger buckets' compute. Slow tier:
the guarantee the feature ships on — ``SyncConfig.overlap`` in {None,
False, True} is BITWISE identical on applied params + memory across all
three sync paths on a real 8-device 2-pod mesh, including a mid-run
pod-k refresh (``repro.core.selfcheck.overlap_selfcheck``).
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipeline import (
    COMM,
    COMPUTE,
    REPACK,
    EmulatedLink,
    overlap_depth,
    plan_schedule,
    run_host_pipeline,
    run_schedule,
    validate_schedule,
)

from _multidevice import child_watchdog, probe_fixture
from tests._hypothesis_compat import given, settings, st

E, G, R = COMPUTE, COMM, REPACK
FLAT = (E, G, E)          # select+encode / gather / decode+apply
HIER = (E, G, E, G, E)    # + pod re-select and the cross-pod gather
HIER_R = (E, G, E, R, G, E)  # + boundary repack before the cross-pod hop
DENSE = (G,)              # one all-reduce


def _sequential(kinds):
    return [(b, s) for b in range(len(kinds)) for s in range(len(kinds[b]))]


def test_depth1_is_strict_sequential():
    kinds = [FLAT, HIER, DENSE, FLAT]
    assert plan_schedule(kinds, 1) == _sequential(kinds)


def test_depth2_is_classic_double_buffer():
    """For uniform [E, G, E] buckets the depth-2 plan is the textbook
    software pipeline: bucket b+1's encode issues while bucket b's
    gather is in flight, and decodes drain one transfer behind."""
    kinds = [FLAT] * 4
    order = plan_schedule(kinds, 2)
    assert order == [
        (0, 0), (0, 1), (1, 0),   # E0, G0 issues, E1 hides behind it
        (0, 2), (1, 1), (2, 0),   # D0 drains, G1 issues, E2 hides
        (1, 2), (2, 1), (3, 0),
        (2, 2), (3, 1),
        (3, 2),                   # tail drains
    ]
    validate_schedule(order, kinds, 2)
    # every gather except the last has a younger bucket's compute
    # scheduled between its issue and its bucket's next stage
    pos = {bs: i for i, bs in enumerate(order)}
    for b in range(3):
        between = order[pos[(b, 1)] + 1: pos[(b, 2)]]
        assert any(kinds[b2][s2] == COMPUTE and b2 > b
                   for b2, s2 in between), (b, order)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=1, max_value=6),
       depth=st.integers(min_value=1, max_value=4),
       mix=st.integers(min_value=0, max_value=2))
def test_planner_always_legal(n, depth, mix):
    shapes = [FLAT, HIER, DENSE]
    kinds = [shapes[(b + mix) % 3] for b in range(n)]
    order = plan_schedule(kinds, depth)
    validate_schedule(order, kinds, depth)
    # depth >= n can never beat the full-width schedule; depth 1 is
    # exactly sequential
    assert plan_schedule(kinds, 1) == _sequential(kinds)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=1, max_value=6),
       depth=st.integers(min_value=1, max_value=4),
       mix=st.integers(min_value=0, max_value=3))
def test_planner_legal_with_repack_chains(n, depth, mix):
    """The 6-stage repack chain (E, G, E, R, G, E) mixes with every
    other bucket shape at every depth; REPACK schedules like a local
    stage (the planner only yields at COMM issues), so the plan stays
    legal and depth 1 stays exactly sequential."""
    shapes = [HIER_R, FLAT, HIER, DENSE]
    kinds = [shapes[(b + mix) % 4] for b in range(n)]
    order = plan_schedule(kinds, depth)
    validate_schedule(order, kinds, depth)
    assert plan_schedule(kinds, 1) == _sequential(kinds)
    # the repack stage is never left dangling across a bucket's own
    # cross-pod gather: within each bucket, R immediately precedes the
    # second COMM in program order (per-bucket order is monotone)
    pos = {bs: i for i, bs in enumerate(order)}
    for b, ks in enumerate(kinds):
        if ks is HIER_R:
            assert pos[(b, 3)] < pos[(b, 4)]


def test_repack_stage_transparent_to_overlap_structure():
    """Inserting the R stage must not perturb the overlap structure:
    dropping every (b, 3) from the depth-2 repack-chain schedule and
    renumbering the later stages yields EXACTLY the plain hierarchical
    schedule, and each R lands immediately before its bucket's cross-pod
    gather issue (repack runs boundary-side, just in time for the hop)."""
    for depth in (1, 2, 3):
        order = plan_schedule([HIER_R] * 3, depth)
        validate_schedule(order, [HIER_R] * 3, depth)
        squeezed = [(b, s if s < 3 else s - 1)
                    for b, s in order if s != 3]
        assert squeezed == plan_schedule([HIER] * 3, depth), depth
        pos = {bs: i for i, bs in enumerate(order)}
        for b in range(3):
            assert pos[(b, 4)] == pos[(b, 3)] + 1, (depth, order)


def test_validate_schedule_rejects_violations():
    kinds = [FLAT, FLAT, FLAT]
    good = plan_schedule(kinds, 1)
    with pytest.raises(AssertionError, match="permutation"):
        validate_schedule(good[:-1], kinds, 1)
    bad = list(good)
    bad[0], bad[1] = bad[1], bad[0]  # stage 1 before stage 0
    with pytest.raises(AssertionError):
        validate_schedule(bad, kinds, 1)
    # depth-2 plan violates the depth-1 window
    with pytest.raises(AssertionError, match="window"):
        validate_schedule(plan_schedule([FLAT] * 4, 2), [FLAT] * 4, 1)
    with pytest.raises(ValueError, match="depth"):
        plan_schedule(kinds, 0)
    with pytest.raises(ValueError, match="kind"):
        plan_schedule([("compute", "mystery")], 1)


def test_overlap_depth_mapping():
    assert overlap_depth(None) is None
    assert overlap_depth(False) == 1
    assert overlap_depth(True) == 2


def _toy_chains(n=4):
    """n independent 3-stage chains over arrays, with a fake comm stage
    (a roll — any value-preserving op) so all depths must agree."""
    inits = [jnp.arange(8.0) * (b + 1) for b in range(n)]
    stage_lists = [
        [lambda x: jnp.sin(x) + 1.0,
         lambda x: jnp.roll(x, 1),
         lambda x: (x * 2.0, jnp.cumsum(x))]
        for _ in range(n)
    ]
    kinds = [FLAT] * n
    return inits, stage_lists, kinds


def test_run_schedule_value_identity_across_depths():
    """The in-jit executor returns bitwise-equal results at every depth
    (barriers only order, never transform) — under jit, where the
    barrier actually lowers."""
    inits, stage_lists, kinds = _toy_chains()

    def run(depth):
        return jax.jit(
            lambda xs: run_schedule(xs, stage_lists, kinds, depth)
        )(inits)

    ref = run(None)
    for depth in (1, 2, 3):
        out = run(depth)
        for (a1, a2), (b1, b2) in zip(ref, out):
            assert np.array_equal(np.asarray(a1).view(np.uint8),
                                  np.asarray(b1).view(np.uint8))
            assert np.array_equal(np.asarray(a2).view(np.uint8),
                                  np.asarray(b2).view(np.uint8))


def test_host_pipeline_matches_and_overlaps():
    """The host executor over an ``EmulatedLink``: (1) results equal the
    sequential run bit for bit, (2) depth 2 hides the transfer latency
    behind the next bucket's compute — with compute time ~= wire time
    the pipelined wall clock must land well under the serial sum."""
    n, delay = 4, 0.03
    rng = np.random.default_rng(0)
    data = [rng.standard_normal(64).astype(np.float32) for _ in range(n)]

    def make(link):
        def compute1(x):
            time.sleep(delay)
            return np.tanh(x)

        def comm(x):
            return link.transfer(x, x.nbytes)

        def compute2(x):
            return (x * 2.0).sum()

        return [[compute1, comm, compute2] for _ in range(n)]

    def run(depth):
        link = EmulatedLink(latency_s=delay)
        t0 = time.monotonic()
        out = run_host_pipeline(list(data), make(link), [FLAT] * n, depth)
        return out, time.monotonic() - t0

    out1, t1 = run(1)
    out2, t2 = run(2)
    assert [float(a) for a in out1] == [float(a) for a in out2]
    # serial: n*(compute+wire) ~ 8*delay. pipelined: ~ (n+1)*delay.
    # assert with a wide margin so scheduler jitter can't flake this.
    assert t2 < t1 - 1.5 * delay, (t1, t2)


def test_emulated_link_serializes_transfers():
    link = EmulatedLink(latency_s=0.01, bandwidth_Bps=1e6)
    f1 = link.transfer("a", 10_000)  # 10ms latency + 10ms wire
    f2 = link.transfer("b", 10_000)
    assert f1.result() == "a" and f2.result() == "b"
    (i1, d1), (i2, d2) = link.transfers
    assert d2 >= d1 + link.delay_for(10_000) - 1e-6  # no double-booking
    assert link.delay_for(10_000) == pytest.approx(0.02)


def test_run_schedule_none_depth_needs_no_kinds_order():
    """depth=None (legacy emission) must not even consult the planner —
    it is the exact bucket-after-bucket fold."""
    inits, stage_lists, kinds = _toy_chains(2)
    out = run_schedule(inits, stage_lists, kinds, None)
    st0 = inits[0]
    for f in stage_lists[0]:
        st0 = f(st0)
    assert np.array_equal(np.asarray(out[0][1]), np.asarray(st0[1]))


_OVERLAP = """
    from repro.core.selfcheck import overlap_selfcheck
    from repro.utils.compat import make_mesh

    rec = overlap_selfcheck(make_mesh((2, 4), ("pod", "data")),
                            wire={wire!r})
    """

# About three times the child's 117-143 s inside a whole run: the two
# overlap probes compile 9 syncs each (3 paths x 3 overlap modes).
CHILD_TIMEOUT = 420
probes = probe_fixture({
    "overlap_packed": _OVERLAP.format(wire="packed"),
    "overlap_unpacked": _OVERLAP.format(wire="unpacked"),
    "repack": """
        from repro.core.selfcheck import repack_selfcheck
        from repro.utils.compat import make_mesh

        rec = repack_selfcheck(make_mesh((2, 4), ("pod", "data")))
        """,
}, timeout=CHILD_TIMEOUT)


@pytest.mark.slow
@child_watchdog(CHILD_TIMEOUT)
def test_overlap_bitwise_identity_all_paths(probes):
    """flat / hierarchical / pod-dynamic (with a mid-run live-k switch)
    on a real 2-pod x 4-worker mesh: overlap in {None, False, True}
    applies BITWISE identical params and memory
    (``repro.core.selfcheck.overlap_selfcheck``, packed wire, its
    default)."""
    rec = probes("overlap_packed")
    assert rec["flat_bitwise"], rec
    assert rec["hierarchical_bitwise"], rec
    assert rec["pod_dynamic_bitwise"], rec
    assert rec["bitwise_all"], rec


@pytest.mark.slow
@child_watchdog(CHILD_TIMEOUT)
@pytest.mark.parametrize("wire", ["unpacked", "packed"])
def test_overlap_bitwise_identity_per_wire(probes, wire):
    """The identity holds for both wire formats (the packed encode/
    decode split across pipeline stages is the riskier path)."""
    rec = probes(f"overlap_{wire}")
    assert rec["bitwise_all"], rec


@pytest.mark.slow
@child_watchdog(CHILD_TIMEOUT)
def test_repack_bitwise_identity_and_transport(probes):
    """``SyncConfig.repack`` on a real 2-pod mesh: the in-jit R stage is
    bitwise inert across overlap modes and a live-k switch, the host
    ``repack_transport`` round-trips the padded buffer bitwise (inline
    and over an ``EmulatedLink``), and its realized bytes equal the
    live-k accounting exactly (``repro.core.selfcheck.repack_selfcheck``)."""
    rec = probes("repack")
    assert rec["repack_bitwise"], rec
    assert rec["transport_roundtrip_bitwise"], rec
    assert rec["transport_accounting_exact"], rec
    padded, live = rec["padded_vs_live_bytes"]
    assert live < padded, rec
