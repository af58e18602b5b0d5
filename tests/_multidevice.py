"""One child process per test file for the multi-device probes.

The pytest process keeps its single CPU device. A test file that needs a
real mesh names its probe bodies in one dict, and ``probe_fixture`` runs
them all in ONE child started with
``XLA_FLAGS=--xla_force_host_platform_device_count=<devices>`` (set before
jax is imported). Each body is Python source, run in a fresh namespace
that already holds ``os``, ``sys``, ``json``, ``jax``, ``jnp`` and ``np``;
it must bind ``rec`` to a JSON-able dict. The child catches each body's
exception, so one failing probe fails only the tests that read it, with
its traceback; a child over its ``timeout`` fails the probes it had not
finished, by name; and a child that exits nonzero fails every probe.
Each file sets that limit at about three times its child's time while
the whole suite runs, so that a hang fails well inside the suite's
budget. Where a child's limit is over pytest.ini's ``timeout``, the
file's probe tests carry ``child_watchdog(limit)``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

# A result line on the child's stdout; anything else it prints is ignored.
_TAG = "@probe-record "

_CHILD = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count={devices}")
    import sys
    sys.path.insert(0, {src!r})
    import json, linecache, traceback
    import jax, jax.numpy as jnp
    import numpy as np

    for name, body in json.load(sys.stdin).items():
        fname = "<probe " + name + ">"
        linecache.cache[fname] = (len(body), None,
                                  body.splitlines(True), fname)
        ns = dict(os=os, sys=sys, json=json, jax=jax, jnp=jnp, np=np)
        try:
            exec(compile(body, fname, "exec"), ns)
            line = {{"name": name, "rec": ns["rec"]}}
        except Exception:
            line = {{"name": name, "error": traceback.format_exc()}}
        print({tag!r} + json.dumps(line), flush=True)
    """
)


def run_probes(bodies: dict[str, str], *, devices: int,
               timeout: float) -> dict[str, dict | str]:
    """Run every body of ``bodies`` in one child with ``devices`` fake
    CPU devices. Returns ``{name: rec}`` for a body that bound ``rec``
    and ``{name: message}`` (a traceback, or why the child stopped) for
    one that did not, or for every body when the child exits nonzero.
    Any exception other than the timeout (an interrupt, a watchdog's
    signal) kills the child before it propagates."""
    script = _CHILD.format(devices=devices, src=SRC, tag=_TAG)
    payload = json.dumps({n: textwrap.dedent(b) for n, b in bodies.items()})
    with subprocess.Popen(
            [sys.executable, "-c", script], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True) as proc:
        try:
            out, err = proc.communicate(payload, timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.kill()
            out, err = proc.communicate()
            stopped = f"the child ran over its {timeout:g} s limit"
            aborted = False
        except BaseException:
            # an interrupt or a watchdog's signal: leave no child behind
            proc.kill()
            raise
        else:
            stopped = f"the child exited with code {proc.returncode}"
            aborted = proc.returncode != 0
    records: dict[str, dict | str] = {}
    for line in out.splitlines():
        if line.startswith(_TAG):
            got = json.loads(line[len(_TAG):])
            records[got["name"]] = got.get("rec", got.get("error"))
    missing = [n for n in bodies if n not in records]
    for name in bodies:
        if name in missing:
            records[name] = (f"{stopped} before this probe reported; "
                             f"unfinished probes: {', '.join(missing)}"
                             f"\n{err[-3000:]}")
        elif aborted:
            # a child that aborts after its reports (say, in XLA
            # teardown) fails every probe it ran, not only the rest
            own = records[name] if isinstance(records[name], str) else ""
            records[name] = (f"{stopped} after this probe reported\n"
                             f"{own}{err[-3000:]}")
    return records


def record(records: dict[str, dict | str], name: str) -> dict:
    """The record of probe ``name``, or a failure carrying its message."""
    rec = records[name]
    if isinstance(rec, str):
        pytest.fail(f"probe {name!r}: {rec}", pytrace=False)
    return rec


def probe_fixture(bodies: dict[str, str], *, devices: int = 8,
                  timeout: float):
    """A module-scoped ``probes`` fixture: it runs the file's child once,
    and each test calls ``probes(name)`` for its own record."""

    @pytest.fixture(scope="module")
    def probes():
        records = run_probes(bodies, devices=devices, timeout=timeout)
        return lambda name: record(records, name)

    return probes


def child_watchdog(timeout: float):
    """A per-test limit for the probe tests of a file whose child may run
    past pytest.ini's ``timeout``. pytest-timeout (where installed)
    counts the module-scoped fixture, and so the whole child, in the
    setup of the first test that asks for it; this mark puts that
    test's limit a minute above the child's own."""
    return pytest.mark.timeout(timeout + 60)
