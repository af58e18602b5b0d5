"""The comparison that decides ``correct``, on numbers recorded on the chip
and on small hand-made cases, and the harness's host-side readings. No
model compiles."""
import json
from pathlib import Path

import jax
import pytest

from bench import correct, harness

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
READINGS = json.loads((FIXTURES / "chip_readings.json").read_text())
SETS = [(cell, kind) for cell, sets in READINGS["cells"].items() for kind in sets]


@pytest.mark.parametrize("cell,kind", SETS)
def test_recorded_readings_meet_or_fail_the_limits(cell, kind):
    """Every sound run recorded on the chip passes the cell's limits; every
    control and half-batch run fails one of them."""
    limits = harness.load_cell(cell)["limits"]
    names = READINGS["numbers"]
    assert set(limits) <= set(names)
    for reading in READINGS["cells"][cell][kind]:
        ok, checks = correct.judge(dict(zip(names, reading)), limits)
        assert ok is (kind == "sound"), (kind, checks)


def _norms(n_leaves, scale=1.0):
    return [scale * (1.0 + i) for i in range(n_leaves)]


def test_a_state_left_unchanged_reads_one():
    ref = {"losses": [2.0, 1.9, 1.8], "grad_norms": _norms(4), "delta_norms": _norms(4)}
    same = correct.compare(ref, ref, list("abcd"))
    assert same["grad_gap"] == same["delta_gap"] == same["loss1_gap"] == 0.0
    unchanged = {"losses": [2.0, 2.0, 2.0], "grad_norms": [0.0] * 4, "delta_norms": [0.0] * 4}
    nums = correct.compare(unchanged, ref, list("abcd"))
    assert nums["grad_gap"] == nums["delta_gap"] == 1.0
    for cell in READINGS["cells"]:
        ok, checks = correct.judge(nums, harness.load_cell(cell)["limits"])
        assert not ok and checks["delta_gap"]["value"] == 1.0


def test_a_negligible_leaf_is_left_out_by_its_reference_gradient():
    ref = {"losses": [1.0], "grad_norms": [1.0, 2.0, 3.0, 1e-6],
           "delta_norms": [1.0, 2.0, 3.0, 1e-9]}
    prog = dict(ref, grad_norms=[1.0, 2.0, 3.0, 5.0], delta_norms=[1.0, 2.0, 3.0, 7.0])
    nums = correct.compare(prog, ref, ["a", "b", "c", "key_bias"])
    assert nums["where"]["left_out"] == ["key_bias"]
    assert nums["grad_gap"] == nums["delta_gap"] == 0.0
    ok, _ = correct.judge({"loss1_gap": float("nan")}, {"loss1_gap": 1.0})
    assert not ok


@pytest.mark.parametrize("workload", [
    w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_the_bucket_layout_is_the_programs(workload):
    """The harness lays out the buckets from the reference's shapes alone;
    they are the program's, so a bucket-wide op in the trace is found."""
    from repro.launch.train import _bucket_plan
    from repro.models import build_model

    cell = harness.load_cell(workload)
    m, traffic = cell["config"]["model"], cell["traffic"]
    lay = correct.layout(jax.eval_shape(lambda: cell["reference"].init(m, jax.random.PRNGKey(0))),
                         traffic["sync"].get("bucket_cols", 1024))
    model = build_model(harness.program_config(cell["config"]))
    plan = _bucket_plan(harness.train_config(traffic), model.param_shapes())
    if plan is None:
        assert traffic["optimizer"] == "dense"
        return
    assert [(k, (r, c)) for k, r, c, _ in lay.buckets] == \
        [(s.kind, s.shape) for s in plan.buckets]


def test_peak_memory_counts_the_scratch_held_for_programs():
    stats = [{"peak_bytes_in_use": 100, "peak_bytes_reserved": 50},
             {"peak_bytes_in_use": 120}]
    # chip 0: 100 + 50 reserved; chip 1 reports no reservation: 120 + the step's 40
    assert harness.peak_bytes(stats, 40) == 160
    assert harness.peak_bytes(stats, 70) == 190
    assert harness.peak_bytes([{}], 0) == 0


def test_the_longest_step_is_read_apart():
    intervals = [0.1, 0.1, 3.7, 0.1]
    host = [(0.0, 0.0, 0, 0, 0, 0), (0.01, 0.02, 0, 0, 1, 0),
            (0.02, 0.04, 0, 0, 2, 0), (0.03, 0.3, 5, 0, 9, 1)]
    dispatch = [(0.0, 0.0)] * 7 + [(10.0, 10.002), (10.1, 10.3)]
    text = harness.longest_step(intervals, host, dispatch, first_call=6)
    assert text.startswith("longest step #2 3700.0 ms (median 100.0; 1 over 1.5x)")
    assert "dispatch 200.0 ms, drain and host loop 3500.0 ms" in text
    assert "thread CPU 10.0 ms, process CPU 260.0 ms" in text
    assert "page faults 5 minor 0 major; context switches 7 voluntary 1 involuntary" in text
    assert harness.longest_step([0.1, 0.2], host[:2], dispatch, 6).endswith("(the last)")
    assert len(harness.host_reading()) == 6
