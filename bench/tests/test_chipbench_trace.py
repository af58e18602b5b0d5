"""The reduction from a trace to per-layer metrics, on a recorded slice of a
chip trace and on small hand-made cases. No model compiles."""
import importlib
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURES = Path(__file__).resolve().parent / "fixtures"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _slice(name):
    fx = json.loads((FIXTURES / name).read_text())
    return fx, fx["table"]


def _read(metric, ctx):
    return importlib.import_module(f"bench.metrics.{metric}").read(ctx)


def test_recorded_slice_layers_busy_and_gaps():
    fx, table = _slice("trace_slice_1chip.json")
    ctx = trace.reduce(fx, table, steps=1, chips=1, peaks=PEAKS, facts={})
    ops = ctx.ops["0"]
    assert len(ops) == 83 and all(o.known for o in ops)
    by_layer = {}
    for o in ops:
        by_layer[trace.layer_of(o)] = by_layer.get(trace.layer_of(o), 0) + o.dur_ns
    # the end of one step's update and the start of the next step's forward
    assert by_layer == {"jitted step": 398969.0, "fwd+bwd": 393340.0, "unattributed": 165.0}
    assert ctx.window_s == pytest.approx(800e-6)
    gaps = sum(s for s, _ in ctx.gaps)
    assert ctx.busy_s == pytest.approx(ctx.window_s - gaps, abs=1e-12)
    # the longest gap is the boundary between the steps, while the host
    # reads the previous step's loss
    assert ctx.gaps[0] == (pytest.approx(6.641e-6), "np.asarray(jax.Array)")
    assert _read("device_idle", ctx) == pytest.approx(100 * gaps / 800e-6)
    assert not any(o.layer == "exchange" for o in ops)  # one chip: no collective
    assert _read("sync_roofline", ctx) is None  # no compressed elements given


def test_breakdown_lists_the_longest_ops_and_gaps():
    fx, table = _slice("trace_slice_1chip.json")
    ctx = trace.reduce(fx, table, steps=1, chips=1, peaks=PEAKS, facts={})
    b = ctx.breakdown(trace.layer_of)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["device_ops"][0] == ["jitted step: sub.184", pytest.approx(396094e-9)]
    assert b["device_ops"][1][0] == "fwd+bwd: convert_element_type.318"
    assert b["idle_gaps"][0][0] == "np.asarray(jax.Array)"


def test_a_parent_op_is_counted_through_its_children():
    events = {"window": [0, 200], "host": [["bench.feed", 150, 40, "python3"]],
              "devices": {"0": [["while.1", 0, 100], ["fusion.1", 10, 30],
                                ["fusion.2", 50, 40], ["add.1", 120, 20]]}}
    table = {n: {"source": "src/repro/models/m.py", "grad": True, "collective": False}
             for n in ("while.1", "fusion.1", "fusion.2")}
    table["add.1"] = {"source": "src/repro/core/distributed.py", "grad": False,
                      "collective": False}
    ctx = trace.reduce(events, table, steps=2, chips=1, peaks=PEAKS, facts={})
    assert [o.name for o in ctx.ops["0"]] == ["fusion.1", "fusion.2", "add.1"]
    assert ctx.busy_s == pytest.approx(120e-9)  # [0, 100] and [120, 140]
    assert _read("fwd_bwd_ms", ctx) == pytest.approx(1e3 * 70e-9 / 2)
    assert _read("sync_ms", ctx) == pytest.approx(1e3 * 20e-9 / 2)
    assert ctx.gaps == [(pytest.approx(60e-9), "harness batch iterator"),
                        (pytest.approx(20e-9), "train() host loop")]


HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "/x/src/repro/launch/train.py"
2 "/x/src/repro/core/distributed.py"
3 "/x/src/repro/models/rwkv.py"

FunctionNames
1 "step"
2 "bucketed_sync_gradients"
3 "time_mix"

FileLocations
1 {file_name_id=1 function_name_id=1 line=508 end_line=508 column=1 end_column=2}
2 {file_name_id=2 function_name_id=2 line=1323 end_line=1323 column=1 end_column=2}
3 {file_name_id=3 function_name_id=3 line=230 end_line=230 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}

%fused_add (p0: f32[8], p1: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = f32[8]{0} parameter(1)
  ROOT %add.1 = f32[8]{0} add(f32[8]{0} %p0, f32[8]{0} %p1), metadata={op_name="jit(step)/add" stack_frame_id=2}
}

%fused_dot (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  ROOT %dot.1 = bf16[8,8]{1,0} dot(bf16[8,8]{1,0} %p0, bf16[8,8]{1,0} %p0), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp())/dot_general" stack_frame_id=3}
}

%gather_comp (p0: u32[4]) -> u32[16] {
  %p0 = u32[4]{0} parameter(0)
  ROOT %all-gather.2 = u32[16]{0} all-gather(u32[4]{0} %p0), dimensions={0}
}

ENTRY %main (a: f32[8], b: bf16[8,8], w: u32[4]) -> (f32[8], bf16[8,8], u32[16]) {
  %a = f32[8]{0} parameter(0)
  %b = bf16[8,8]{1,0} parameter(1)
  %w = u32[4]{0} parameter(2)
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %a, f32[8]{0} %a), kind=kLoop, calls=%fused_add, metadata={op_name="jit(step)/add" stack_frame_id=1}
  %fusion.8 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %b), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(step)/mul" stack_frame_id=1}
  %all-gather-start.3 = (u32[4]{0}, u32[16]{0}) all-gather-start(u32[4]{0} %w), dimensions={0}
  %all-gather-done.3 = u32[16]{0} all-gather-done((u32[4]{0}, u32[16]{0}) %all-gather-start.3)
  %fusion.9 = u32[16]{0} fusion(u32[4]{0} %w), kind=kCustom, calls=%gather_comp
  ROOT %tuple = (f32[8]{0}, bf16[8,8]{1,0}, u32[16]{0}) tuple(%fusion.7, %fusion.8, %fusion.9)
}
"""


def test_hlo_table_resolves_sources_gradient_marks_and_collectives():
    t = trace.hlo_table(HLO)
    # a fusion that carries only the step's frame takes its callees' source
    assert t["fusion.7"]["source"].endswith("/repro/core/distributed.py")
    assert not t["fusion.7"]["grad"] and not t["fusion.7"]["collective"]
    assert t["fusion.8"]["source"].endswith("/repro/models/rwkv.py")
    assert t["fusion.8"]["grad"]
    assert t["add.1"]["source"].endswith("/repro/core/distributed.py")
    for name in ("all-gather-start.3", "all-gather-done.3", "fusion.9"):
        assert t[name]["collective"], name
    assert t["fusion.9"]["opcode"] == "fusion"
    assert t["all-gather-start.3"]["opcode"] == "all-gather-start"


def _exchange_ms(ctx):
    return ctx.per_step_ms(lambda o: trace.layer_of(o) == "exchange")


def test_collective_time_per_step_over_chips():
    t = trace.hlo_table(HLO)
    events = {"window": [0, 1000], "host": [],
              "devices": {"0": [["fusion.7", 0, 100], ["all-gather-start.3", 100, 10],
                                ["all-gather-done.3", 110, 300], ["fusion.8", 410, 90]],
                          "1": [["fusion.7", 0, 100], ["fusion.9", 100, 500]]}}
    ctx = trace.reduce(events, t, steps=2, chips=2, peaks=PEAKS,
                       facts={"compressed_elements": 1000, "flops_per_step": 1e6})
    # chip 0: 310 ns, chip 1: 500 ns of collectives; mean 405 ns over 2 steps
    assert _exchange_ms(ctx) == pytest.approx(1e3 * 405e-9 / 2)
    assert _read("sync_ms", ctx) == pytest.approx(1e3 * 100e-9 / 2)
    assert _read("fwd_bwd_ms", ctx) == pytest.approx(1e3 * 45e-9 / 2)
    least_ms = 1e3 * 12 * 1000 / 819e9
    assert _read("sync_roofline", ctx) == pytest.approx(100 * least_ms / (1e3 * 50e-9))
    assert _read("mfu", ctx) == pytest.approx(100 * 2e6 / (1e-6 * 2 * 197e12))
    assert ctx.busy == {"0": pytest.approx(500e-9), "1": pytest.approx(600e-9)}
    assert _read("device_idle", ctx) == pytest.approx(100 * (1 - 550e-9 / 1e-6))
    names = [n for n, _ in ctx.breakdown(trace.layer_of)["device_ops"]]
    assert names[:2] == ["exchange: fusion.9", "exchange: all-gather-done.3"]


BUCKET_HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "/x/src/repro/launch/train.py"

FunctionNames
1 "step"

FileLocations
1 {file_name_id=1 function_name_id=1 line=508 end_line=508 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused_update (p0: f32[1,6,4], p1: f32[6,4]) -> f32[6,4] {
  %p0 = f32[1,6,4]{2,1,0} parameter(0)
  %p1 = f32[6,4]{1,0} parameter(1)
  %bitcast.1 = f32[6,4]{1,0} bitcast(%p0)
  ROOT %add.3 = f32[6,4]{1,0} add(%bitcast.1, %p1), metadata={op_name="jit(step)/add" stack_frame_id=1}
}

ENTRY %main (m: f32[1,6,4], g: f32[6,4], p: f32[3,5]) -> (f32[6,4], f32[3,5], f32[24]) {
  %m = f32[1,6,4]{2,1,0:T(8,128)} parameter(0)
  %g = f32[6,4]{1,0} parameter(1)
  %p = f32[3,5]{1,0} parameter(2)
  %bitcast_add_fusion.1 = f32[6,4]{1,0:T(8,128)} fusion(%m, %g), kind=kLoop, calls=%fused_update, metadata={op_name="jit(step)/add" stack_frame_id=1}
  %sub.2 = f32[3,5]{1,0} subtract(%p, %p), metadata={op_name="jit(step)/sub" stack_frame_id=1}
  %copy.4 = f32[24]{0} copy(%bitcast_add_fusion.1)
  %copy.5 = f32[3,5]{1,0} copy(%sub.2)
  ROOT %tuple = (f32[6,4]{1,0}, f32[3,5]{1,0}, f32[24]{0}) tuple(%bitcast_add_fusion.1, %sub.2, %copy.4)
}
"""


def test_bucket_wide_passes_on_the_step_frame_are_the_sync_engine():
    """A pass over a compressed bucket that carries only the step's frame,
    or no frame, is the sync engine's wherever XLA fuses it; a pass over
    a parameter stays the step's (the optimizer's apply)."""
    t = trace.hlo_table(BUCKET_HLO)
    assert t["bitcast_add_fusion.1"]["sizes"] == [24]
    assert t["sub.2"]["sizes"] == [15]
    events = {"window": [0, 100], "host": [],
              "devices": {"0": [["bitcast_add_fusion.1", 0, 40], ["sub.2", 40, 20],
                                ["copy.4", 60, 10], ["copy.5", 70, 10]]}}
    layers = {}
    for buckets in ((), (24,)):
        ctx = trace.reduce(events, t, steps=1, chips=1, peaks=PEAKS,
                           facts={"bucket_sizes": list(buckets)})
        layers[buckets] = {o.name: o.layer for o in ctx.ops["0"]}
    assert layers[()] == {"bitcast_add_fusion.1": "jitted step", "sub.2": "jitted step",
                          "copy.4": "unattributed", "copy.5": "unattributed"}
    assert layers[(24,)] == {"bitcast_add_fusion.1": "sync engine", "sub.2": "jitted step",
                             "copy.4": "sync engine", "copy.5": "unattributed"}
    ctx = trace.reduce(events, t, steps=1, chips=1, peaks=PEAKS,
                       facts={"bucket_sizes": [24], "compressed_elements": 24})
    assert _read("sync_ms", ctx) == pytest.approx(1e3 * 50e-9)
