"""The benchmark's files: every BENCHMARK.json entry resolves by name, the
peak table refuses unknown devices, the FLOP counts follow from the
parameter counts, and a new cell is new files only. No model compiles."""
import importlib.util
import json
import math
import shutil
from pathlib import Path

import jax
import pytest

from bench import harness, trace
from bench.peaks import PEAKS, peaks_for

ROOT = Path(__file__).resolve().parents[2]
BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("TPU v99 imaginary")
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert PEAKS["TPU v5 lite"]["ici_bytes_per_s"] == 1600e9 / 8


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_entry_resolves_by_name(workload):
    cell = harness.load_cell(workload)
    w = cell["workload"]
    assert cell["config"]["name"] == w["config"]
    assert cell["traffic"]["data_parallel"] == w["chips"]
    for fn in ("init", "loss", "flops_per_token", "matmul_params"):
        assert callable(getattr(cell["reference"], fn))
    assert cell["limits"] and all(v > 0 for v in cell["limits"].values())
    assert set(cell["end_to_end"]) >= {"setup_s", "tokens_per_s"}
    assert "mfu" in cell["readers"]
    for reader, unit in cell["readers"].values():
        assert callable(reader.read) and unit


def test_config_files_hold_what_the_entries_say():
    for c in BENCHMARK["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["source"] == c["source"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        left_out = f["assumed"].get("left_out", {})
        for key in c["reduced"]:
            assert f[key] != f["published"][key]
        for key, v in f["published"].items():
            if key in left_out:  # not run by the program, and stated so
                assert key not in f and left_out[key] == v, key
            elif key not in c["reduced"]:
                assert f[key] == v, key


def _params(cfg_name):
    """A configuration's file and reference, found by name."""
    m = json.loads((ROOT / "bench" / "configs" / f"{cfg_name}.json").read_text())["model"]
    ref = harness._module(ROOT / "bench" / "reference" / f"{cfg_name}.py", f"ref_{cfg_name}")
    shapes = jax.eval_shape(lambda: ref.init(m, jax.random.PRNGKey(0)))
    return m, ref, shapes


@pytest.mark.parametrize("cfg_name,tflop,n_params", [
    ("granite-3-8b", 15.0, 600_854_528),
])
def test_flop_count_follows_the_parameter_count(cfg_name, tflop, n_params):
    m, ref, shapes = _params(cfg_name)
    total = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
    assert total == n_params
    vectors = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes)
                  if len(x.shape) < 2 or (len(x.shape) == 2 and min(x.shape) <= 2))
    stacked = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes["blocks"])
                  if x.ndim == 3 and x.shape[1] < 64)  # per-head vectors
    embed = math.prod(shapes["embed"].shape)
    head = 0 if "lm_head" in shapes else embed  # a tied head is one matmul
    matmul = total - embed - vectors - stacked + head
    assert ref.matmul_params(m) == matmul
    step = ref.flops_per_token(m, 512) * 4096
    assert step == pytest.approx(tflop * 1e12, rel=0.01)
    assert step >= 6 * matmul * 4096


def test_a_new_cell_is_new_files_only(tmp_path):
    """Add a configuration, a traffic mix, a per-layer metric and a
    reference as files plus entries, in a copy; no harness file changes."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__", "traces", "out"))
    before = {p: p.read_bytes() for p in bench.rglob("*.py")}
    fixture = json.loads((bench / "tests" / "fixtures" / "tiny-granite.json").read_text())
    (bench / "configs" / "tiny-new.json").write_text(json.dumps(fixture))
    shutil.copy(bench / "reference" / "granite-3-8b.py", bench / "reference" / "tiny-new.py")
    mix = dict(json.loads((bench / "traffic" / "topk.json").read_text()), seq_len=64)
    (bench / "traffic" / "longer.json").write_text(json.dumps(mix))
    (bench / "limits" / "tiny-new.longer.json").write_text(json.dumps({"loss1_gap": 1e-3}))
    (bench / "metrics" / "window_steps.py").write_text(
        "def read(ctx):\n    return float(ctx.steps) or None\n")
    b = json.loads(json.dumps(BENCHMARK))
    b["configs"].append({"name": "tiny-new", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny-new.json", "why": "test"})
    b["workloads"].append({"name": "tiny-new.longer", "config": "tiny-new",
                           "traffic": "longer", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "window_steps", "unit": "steps", "better": "higher",
                           "source": "device_trace", "layer": "host loop",
                           "moves": "tokens_per_s", "workloads": ["tiny-new.longer"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    spec = importlib.util.spec_from_file_location("copied_harness", bench / "harness.py")
    copied = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copied)
    cell = copied.load_cell("tiny-new.longer")
    assert cell["traffic"]["seq_len"] == 64
    assert cell["config"]["model"]["d_model"] == 128
    assert cell["reference"].matmul_params(cell["config"]["model"]) > 0
    reader, unit = cell["readers"]["window_steps"]
    ctx = trace.Context(window_s=1.0, steps=3, chips=1, peaks={}, facts={},
                        ops={"0": []}, busy={"0": 0.5}, gaps=[])
    assert reader.read(ctx) == 3.0 and unit == "steps"
    assert {p: p.read_bytes() for p in before} == before
