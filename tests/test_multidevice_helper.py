"""The one-child-per-file probe runner (tests/_multidevice.py), on 1 device."""
import signal
import subprocess

import pytest

from _multidevice import record, run_probes


def test_raising_probe_reported_by_name_beside_its_sibling():
    records = run_probes(
        {"bad": "raise ValueError('planted fault')",
         "good": "rec = {'devices': jax.device_count()}"},
        devices=1, timeout=120)
    assert records["good"] == {"devices": 1}
    assert record(records, "good") == {"devices": 1}
    assert "Traceback" in records["bad"], records["bad"]
    assert "ValueError: planted fault" in records["bad"], records["bad"]
    with pytest.raises(pytest.fail.Exception, match="(?s)'bad'.*planted fault"):
        record(records, "bad")


def test_child_over_its_limit_fails_naming_unfinished_probes():
    records = run_probes(
        {"first": "import time; time.sleep(600)",
         "second": "rec = {}"},
        devices=1, timeout=5)
    for name in ("first", "second"):
        with pytest.raises(pytest.fail.Exception) as info:
            record(records, name)
        msg = str(info.value)
        assert "over its 5 s limit" in msg, msg
        assert "unfinished probes: first, second" in msg, msg


def test_child_that_dies_fails_the_probes_it_had_not_reported():
    records = run_probes(
        {"ok": "rec = {'x': 1}",
         "dies": "os._exit(3)",
         "after": "rec = {}"},
        devices=1, timeout=120)
    with pytest.raises(pytest.fail.Exception,
                       match="exited with code 3 after this probe reported"):
        record(records, "ok")
    for name in ("dies", "after"):
        with pytest.raises(pytest.fail.Exception,
                           match="exited with code 3 before.*dies, after"):
            record(records, name)


def test_child_aborting_after_its_last_report_fails_every_probe():
    records = run_probes(
        {"first": "rec = {}",
         "last": "import atexit; atexit.register(os._exit, 5); rec = {}"},
        devices=1, timeout=120)
    for name in ("first", "last"):
        with pytest.raises(pytest.fail.Exception,
                           match="exited with code 5 after this probe"):
            record(records, name)


def test_interrupted_wait_kills_the_child(monkeypatch):
    started = []

    def interrupted(proc, *args, **kwargs):
        started.append(proc)
        raise KeyboardInterrupt

    monkeypatch.setattr(subprocess.Popen, "communicate", interrupted)
    with pytest.raises(KeyboardInterrupt):
        run_probes({"hangs": "import time; time.sleep(600)"},
                   devices=1, timeout=120)
    (proc,) = started
    assert proc.wait(timeout=30) == -signal.SIGKILL
