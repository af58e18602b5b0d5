"""The check that decides ``correct``, driven through a whole run at test
size on the CPU (the harness's look for a chip skipped): a sound run
passes the cell's limits, and the timed path broken underneath fails
them, once for each fault a training cell can have.

The model is granite-3-8b's layer equations at test widths, computed in
float32 so that a sound run sits at the reference to rounding. Each
test compiles the step and the reference, some minutes in all, so the
file is left out of the default collection by its name; run it as

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests/slow_faults.py
"""
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import correct, harness

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
pytestmark = pytest.mark.slow


def tiny_cell(workers: int) -> dict:
    cell = harness.load_cell("granite-3-8b.topk.1chip")
    cfg = json.loads((FIXTURES / "tiny-granite.json").read_text())
    cfg["program"]["overrides"]["compute_dtype"] = "float32"
    cell["config"] = cfg
    cell["traffic"] = dict(cell["traffic"], batch_per_worker=2, seq_len=16,
                           distinct_batches=3, warmup_steps=3, data_parallel=workers)
    cell["workload"] = dict(cell["workload"], chips=workers)
    return cell


def run(cell, planted=None):
    with jax.default_matmul_precision("highest"):
        r = harness.run_cell(cell, 2**33 + 11, 0.2, False,
                             jax.devices()[:cell["workload"]["chips"]],
                             time.perf_counter(), planted=planted)
    return r


def unchanged(step):
    """A step that returns its state unchanged."""
    def f(params, memory, opt, count, batch, *rest):
        copies = jax.tree.map(jnp.copy, (params, memory, opt))
        out = step(*copies, count, batch, *rest)
        return (params, memory, opt) + tuple(out[3:])
    return f


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    def f(params, memory, opt, count, batch, *rest):
        half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
        return step(params, memory, opt, count, half, *rest)
    return f


@pytest.fixture(scope="module")
def one_chip_cell():
    return tiny_cell(1)


@pytest.mark.parametrize("fault,want", [
    (None, True), (unchanged, False), (half_batch, False)])
def test_one_chip_faults_fail_the_check(one_chip_cell, fault, want):
    r = run(one_chip_cell, fault)
    assert r["correct"] is want, r["checks"]
    assert list(r)[-1] == "checks"


def test_the_control_fails_the_check(one_chip_cell):
    """The reference in float8, put in the program's place, fails a limit."""
    cell = one_chip_cell
    m, traffic, ref = cell["config"]["model"], cell["traffic"], cell["reference"]
    key = harness.seed_key(2**33 + 11)
    init_key = jax.random.fold_in(key, 0)
    pool = harness.token_pool(traffic, m["vocab"], jax.random.fold_in(key, 1))
    dev = jax.devices()[:1]
    base = correct.reference_run(ref, m, traffic, pool, init_key, dev)
    lay = correct.layout(jax.eval_shape(lambda: ref.init(m, init_key)), 1024)
    names = harness._leaf_names(lay)
    ok, _ = correct.judge(correct.compare(base, base, names), cell["limits"])
    assert ok
    ctrl = correct.reference_run(ref, m, traffic, pool, init_key, dev, rnd=correct.fp8)
    ok, checks = correct.judge(correct.compare(ctrl, base, names), cell["limits"])
    assert not ok, checks


EXCHANGE = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/bench/tests"]
    import jax, jax.numpy as jnp
    from repro.core import distributed
    import slow_faults as t
    cell = t.tiny_cell(4)
    out = {"sound": t.run(cell)["correct"]}
    # each worker gathers only its own pairs: the exchange left out
    distributed._gather_buf = lambda buf, axes: jnp.tile(
        buf, (distributed._axis_size(axes),) + (1,) * (buf.ndim - 1))
    out["no_exchange"] = t.run(cell)["correct"]
    print(json.dumps(out))
""")


def test_four_chip_exchange_left_out_fails_the_check():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", EXCHANGE, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out == {"sound": True, "no_exchange": False}, p.stderr[-3000:]
