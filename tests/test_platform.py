"""Platform setup + per-backend tables (repro.utils.platform).

Covers the ``REPRO_PALLAS_INTERPRET`` override both ways, the
backend-keyed top-k cutover table and its consumption by
``kernels.ops.row_topk(method="auto")`` / the distributed
``_pick_selection``, and the XLA flag merge (user flags never
overridden, GPU flags never leaked onto CPU runs).
"""
import pytest

from repro.kernels.ops import _resolve_method
from repro.utils import platform as pf


# --- REPRO_PALLAS_INTERPRET env override ---------------------------------

def test_interpret_env_force_on(monkeypatch):
    monkeypatch.setenv(pf.ENV_INTERPRET, "1")
    assert pf.pallas_interpret_default("tpu") is True
    assert pf.pallas_interpret_default("cpu") is True


def test_interpret_env_force_off(monkeypatch):
    monkeypatch.setenv(pf.ENV_INTERPRET, "0")
    assert pf.pallas_interpret_default("cpu") is False
    assert pf.pallas_interpret_default("gpu") is False


def test_interpret_env_invalid_raises(monkeypatch):
    monkeypatch.setenv(pf.ENV_INTERPRET, "yes")
    with pytest.raises(ValueError, match="REPRO_PALLAS_INTERPRET"):
        pf.pallas_interpret_default("cpu")


def test_interpret_backend_defaults(monkeypatch):
    monkeypatch.delenv(pf.ENV_INTERPRET, raising=False)
    # compiled lowerings exist on TPU (Mosaic) and GPU (Triton);
    # CPU falls back to interpret mode
    assert pf.pallas_interpret_default("tpu") is False
    assert pf.pallas_interpret_default("gpu") is False
    assert pf.pallas_interpret_default("cpu") is True
    # empty string == unset (a cleared CI variable)
    monkeypatch.setenv(pf.ENV_INTERPRET, "")
    assert pf.pallas_interpret_default("cpu") is True


def test_kernel_auto_interpret_consults_env(monkeypatch):
    from repro.kernels.topk_select import _auto_interpret

    monkeypatch.setenv(pf.ENV_INTERPRET, "0")
    assert _auto_interpret(None) is False
    monkeypatch.setenv(pf.ENV_INTERPRET, "1")
    assert _auto_interpret(None) is True
    # an explicit interpret= wins over the env var
    assert _auto_interpret(False) is False


# --- top-k loop/threshold cutover table ----------------------------------

def test_cutover_table_per_backend():
    assert pf.topk_loop_cutover("cpu") == pf.TOPK_LOOP_CUTOVER["cpu"]
    assert pf.topk_loop_cutover("tpu") == pf.TOPK_LOOP_CUTOVER["tpu"]
    # a backend missing from the table is an error, not a default
    with pytest.raises(ValueError, match="rocm"):
        pf.topk_loop_cutover("rocm")


def test_auto_method_matches_table():
    """``method="auto"`` flips from the argmax loop to the single-pass
    threshold select exactly at the active backend's cutover."""
    cut = pf.topk_loop_cutover()  # this process runs on CPU
    assert _resolve_method("auto", cut) == "loop"
    assert _resolve_method("auto", cut + 1) == "threshold"
    assert _resolve_method("loop", 64) == "loop"
    assert _resolve_method("threshold", 1) == "threshold"
    with pytest.raises(ValueError, match="method"):
        _resolve_method("bogus", 4)


def test_distributed_selection_uses_cutover():
    """threshold_onehot's tiny-k fallback keys off the same table."""
    from repro.core.distributed import (
        SyncConfig,
        _pick_selection,
        _row_topk_argmax,
        _row_topk_threshold,
    )

    cfg = SyncConfig(selection="threshold_onehot")
    cut = pf.topk_loop_cutover()
    assert _pick_selection(cfg, cut)[0] is _row_topk_argmax
    assert _pick_selection(cfg, cut + 1)[0] is _row_topk_threshold


# --- XLA flag merge / setup_platform -------------------------------------

def test_merge_xla_flags_dedup_and_preserve():
    merged = pf._merge_xla_flags(
        "--xla_gpu_enable_async_collectives=false --foo=1",
        pf.GPU_PERF_FLAGS,
    )
    parts = merged.split()
    # the user's explicit setting survives, un-duplicated
    assert parts.count("--xla_gpu_enable_async_collectives=false") == 1
    assert not any(
        p == "--xla_gpu_enable_async_collectives=true" for p in parts
    )
    # everything else appended once
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in parts
    assert pf._merge_xla_flags("", ["--a=1"]) == "--a=1"


def test_setup_platform_env_and_config(monkeypatch):
    calls = []
    import jax

    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("XLA_FLAGS", "--keep=me")

    import os

    # CPU: host-device count appended, NO gpu flags leak (an XLA build
    # that does not know a flag treats XLA_FLAGS as fatal)
    pf.setup_platform("cpu", host_devices=8)
    flags = os.environ["XLA_FLAGS"].split()
    assert "--keep=me" in flags
    assert "--xla_force_host_platform_device_count=8" in flags
    assert not any("xla_gpu" in f for f in flags)
    assert calls == [("jax_platform_name", "cpu")]

    # GPU: perf flags injected; "cuda" aliases to the gpu platform name
    pf.setup_platform("cuda")
    flags = os.environ["XLA_FLAGS"].split()
    for f in pf.GPU_PERF_FLAGS:
        assert f in flags
    assert calls[-1] == ("jax_platform_name", "gpu")

    # perf_flags=False: platform pinned, flags untouched
    monkeypatch.setenv("XLA_FLAGS", "")
    pf.setup_platform("gpu", perf_flags=False)
    assert os.environ["XLA_FLAGS"] == ""
    assert calls[-1] == ("jax_platform_name", "gpu")


def test_setup_platform_none_is_flags_only(monkeypatch):
    import jax

    monkeypatch.setattr(
        jax.config, "update",
        lambda *_: pytest.fail("platform=None must not pin a platform"))
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    import os

    pf.setup_platform(None, host_devices=4)
    assert os.environ["XLA_FLAGS"] == (
        "--xla_force_host_platform_device_count=4")
    # and with nothing to do it must not create the variable at all
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    pf.setup_platform(None)
    assert "XLA_FLAGS" not in os.environ


# --- persistent compile cache ---------------------------------------------

def test_compile_cache_follows_env_else_repo(monkeypatch, tmp_path):
    import pathlib

    import jax

    # the fixed default: <repo>/.jax_cache, never a run-specific name
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert pf.REPO_COMPILE_CACHE == repo / ".jax_cache"
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    # JAX_COMPILATION_CACHE_DIR set: used as is, nothing set in code
    monkeypatch.setenv(pf.ENV_COMPILE_CACHE, str(tmp_path / "env"))
    assert pf.enable_compile_cache() == str(tmp_path / "env")
    assert calls == []
    # unset: the fixed repo path
    monkeypatch.delenv(pf.ENV_COMPILE_CACHE)
    monkeypatch.setattr(pf, "REPO_COMPILE_CACHE", tmp_path / "repo")
    assert pf.enable_compile_cache() == str(tmp_path / "repo")
    assert calls == [("jax_compilation_cache_dir", str(tmp_path / "repo"))]
    assert (tmp_path / "repo").is_dir()
