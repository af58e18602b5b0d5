"""The readings a cell's limits are set from; not part of a benchmark run.

    python bench/readings.py --workload <name> --seeds 12 --control-seeds 3 \
        [--faults half_batch,no_exchange] [--out readings.json]

In one process, on the chips the cell asks for:

* lower readings: the program's numbers (each step's loss gap, the
  gradient and change gaps) from sound runs of the timed path, one run
  per seed with a window of no length;
* upper readings: the control, the float32 reference put in the
  program's place and computed in float8 (e4m3 values, e5m2
  cotangents, per-tensor scales), the precision below the
  configuration's bfloat16; and each planted fault
  that the cell can have, in the reference put in the program's place.

Seeds are large, as the benchmark's callers draw them.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

FIRST_SEED = 3_000_000_017


def _short(nums: dict) -> str:
    return json.dumps({k: v for k, v in nums.items() if k != "where"})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import correct, harness

    import jax

    cell = harness.load_cell(args.workload)
    devices = jax.devices()[:cell["workload"]["chips"]]
    out = {"workload": args.workload, "device": devices[0].device_kind,
           "program": {}, "control": {}, "faults": {}}

    def save():
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            Path(args.out).write_text(json.dumps(out, indent=1))

    seeds = [args.first_seed + 7919 * i
             for i in range(max(args.seeds, args.control_seeds))]
    for s in seeds[:args.seeds]:
        nums = {}
        t = time.perf_counter()
        harness.run_cell(cell, s, 0.0, False, devices, t, numbers_out=nums)
        out["program"][str(s)] = nums
        harness.log(f"program seed {s}: {_short(nums)}")
        save()

    m, traffic, ref = cell["config"]["model"], cell["traffic"], cell["reference"]
    lay = correct.layout(jax.eval_shape(lambda: ref.init(m, jax.random.PRNGKey(0))),
                         traffic["sync"].get("bucket_cols", 1024))
    names = harness._leaf_names(lay)
    faults = [f for f in args.faults.split(",") if f]
    for s in seeds[:args.control_seeds]:
        key = harness.seed_key(s)
        init_key = jax.random.fold_in(key, 0)
        pool = harness.token_pool(traffic, m["vocab"], jax.random.fold_in(key, 1))
        base = correct.reference_run(ref, m, traffic, pool, init_key, devices)
        runs = {"control": dict(rnd=correct.fp8)}
        runs.update({f: dict(fault=f) for f in faults})
        for label, kw in runs.items():
            other = correct.reference_run(ref, m, traffic, pool, init_key, devices, **kw)
            nums = correct.compare(other, base, names)
            nums.update(program=other["losses"], reference=base["losses"])
            dest = out["control"] if label == "control" else out["faults"].setdefault(label, {})
            dest[str(s)] = nums
            harness.log(f"{label} seed {s}: {_short(nums)}")
            save()
    for kind in ("loss1_gap", "loss2_gap", "loss3_gap", "grad_gap", "delta_gap",
                 "grad_gap_median", "delta_gap_median"):
        lows = [v[kind] for v in out["program"].values() if kind in v]
        highs = {label: min(v[kind] for v in d.values())
                 for label, d in [("control", out["control"])] + list(out["faults"].items())
                 if d}
        harness.log(f"{kind}: lower {max(lows) if lows else None!r}; upper {highs}")
    save()
    return 0


if __name__ == "__main__":
    sys.exit(main())
