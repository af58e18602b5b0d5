"""Benchmark entry point.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of the machine it is
started on and prints one JSON line last: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last the numbers compared for ``correct`` beside
their limits (``checks``). Exits non-zero, printing no result, where
JAX finds no accelerator, fewer chips than the cell asks for, or a
device kind with no published peaks.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness
    from bench.peaks import peaks_for

    cell = harness.load_cell(args.workload)
    import jax

    chips = cell["workload"]["chips"]
    devices = jax.devices()
    if devices[0].platform == "cpu":
        harness.log("no accelerator: JAX found only the CPU")
        return 2
    if len(devices) < chips:
        harness.log(f"the cell asks for {chips} chips; JAX found {len(devices)}")
        return 2
    try:
        peaks_for(devices[0].device_kind)
    except KeyError as e:
        harness.log(str(e))
        return 2
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices[:chips], T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
