"""memsgd_kernel_ms reads the fused Mem-SGD kernel's ops by name, on small
hand-made traces. No model compiles."""
import importlib

import pytest

from bench import trace

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
KERNEL_SRC = "/x/src/repro/kernels/fused_memsgd.py"


def _read(metric, ctx):
    return importlib.import_module(f"bench.metrics.{metric}").read(ctx)


def _ctx(ops, table, steps=2):
    events = {"window": [0, 10_000], "host": [],
              "devices": {"0": [[n, s, d] for n, s, d in ops]}}
    return trace.reduce(events, table, steps=steps, chips=1, peaks=PEAKS,
                        facts={})


def test_the_kernel_ops_are_read_by_name_and_counted_in_sync_ms():
    table = {
        "memsgd_fused": {"opcode": "custom-call", "source": KERNEL_SRC,
                         "grad": False, "collective": False, "sizes": []},
        "memsgd_fused.1": {"opcode": "custom-call", "source": KERNEL_SRC,
                           "grad": False, "collective": False, "sizes": []},
        "fusion.3": {"opcode": "fusion", "source": "/x/src/repro/core/encoding.py",
                     "grad": False, "collective": False, "sizes": []},
    }
    ops = [("memsgd_fused", 0, 3000), ("fusion.3", 3000, 500),
           ("memsgd_fused.1", 4000, 5000), ("memsgd_fused_copy.2", 9000, 100)]
    ctx = _ctx(ops, table)
    assert _read("memsgd_kernel_ms", ctx) == pytest.approx(1e3 * 8000e-9 / 2)
    assert _read("sync_ms", ctx) == pytest.approx(1e3 * 8500e-9 / 2)


@pytest.mark.parametrize("steps, want", [(2, 0.0), (0, None)])
def test_a_step_without_the_kernel_reads_zero(steps, want):
    table = {"fusion.15": {"opcode": "fusion", "source": "/x/src/repro/core/distributed.py",
                           "grad": False, "collective": False, "sizes": []}}
    ctx = _ctx([("fusion.15", 0, 7000)], table, steps=steps)
    assert _read("memsgd_kernel_ms", ctx) == want
