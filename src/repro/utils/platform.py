"""Per-backend platform setup + performance tables.

One place that answers "what accelerator are we on and how should the
stack configure itself for it":

* ``setup_platform`` — pin the JAX platform and append the backend's
  XLA perf flags (GPU: async collectives + latency-hiding scheduler,
  the flags that let the pipelined bucket sync's all-gathers actually
  run on a separate stream — see ``repro.core.pipeline``). MUST run
  before JAX initializes its backend client; the train CLI calls it
  first thing (``--platform``).
* ``topk_loop_cutover`` — the k up to which the k-pass argmax loop
  beats the single-pass bisection threshold select, keyed by backend.
  Measured per machine by ``benchmarks/run.py kernel_topk`` (the
  ``cutover`` sweep in BENCH_topk.json) and consumed by
  ``kernels.ops.row_topk(method="auto")`` and the distributed sync's
  ``_pick_selection``.
* ``pallas_interpret_default`` — the ``interpret=None`` resolution for
  the Pallas kernels: compiled lowering on TPU *and* GPU, interpret
  fallback on CPU, overridable either way with
  ``REPRO_PALLAS_INTERPRET=0/1`` (CI on GPU runners can force
  interpret-off; a CPU box can smoke the compiled path's plumbing).

* ``enable_compile_cache`` — JAX's persistent compilation cache:
  ``JAX_COMPILATION_CACHE_DIR`` when it is set (jax reads it itself),
  otherwise the fixed ``<repo>/.jax_cache`` of this checkout.

Nothing here imports the kernels (they import us), so the module stays
import-cycle-free and safe to use before any JAX computation runs.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

# XLA perf flags for CUDA backends (bayespec-style setup): run
# collectives asynchronously on a dedicated high-priority stream and
# let the latency-hiding scheduler overlap them with compute — the
# backend half of the double-buffered bucket pipeline
# (core/pipeline.py supplies the schedule, these flags supply the
# concurrent execution). Only appended when a GPU platform is
# explicitly requested: an XLA build that does not know a flag treats
# XLA_FLAGS as fatal, so a CPU run must never inherit them.
GPU_PERF_FLAGS = (
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
)

# Largest k at which the k-pass argmax loop still beats the fixed-cost
# threshold select (bisection, compaction, sorting network; flat in k),
# per backend. MEASURED: the CPU entry on the interpret-mode reference
# machine (BENCH_topk.json ``cutover`` sweep: the loop is ~1.7x faster
# at k=4 and ~1.2x slower at k=8); the TPU entry on a v5e chip
# (``python chip_smoke.py --kernel-sweep`` at 65,536 x 1024: the loop
# takes ~2.76 ms per unit of k, the threshold a flat ~90 ms, so they
# tie at k=32 and the threshold is 2x faster at k=64). The GPU entry
# keeps the historical ``LOOP_MAX_K = 8`` until a sweep refreshes it.
# A backend missing from the table is an error, not a default.
TOPK_LOOP_CUTOVER = {
    "cpu": 4,
    "gpu": 8,
    "tpu": 32,
}

ENV_INTERPRET = "REPRO_PALLAS_INTERPRET"

ENV_COMPILE_CACHE = "JAX_COMPILATION_CACHE_DIR"
# fixed path inside the checkout (src/repro/utils/platform.py -> repo
# root): the cache key includes nothing run-specific, so a later run of
# the same checkout finds what an earlier one compiled
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def _merge_xla_flags(existing: str, new_flags) -> str:
    """Append ``new_flags`` to an XLA_FLAGS string without duplicating
    flags already present (matched by flag NAME, so a user's explicit
    ``--xla_gpu_enable_async_collectives=false`` is never overridden)."""
    parts = existing.split()
    have = {p.split("=", 1)[0] for p in parts}
    for f in new_flags:
        if f.split("=", 1)[0] not in have:
            parts.append(f)
            have.add(f.split("=", 1)[0])
    return " ".join(parts)


def setup_platform(platform: Optional[str] = None,
                   host_devices: Optional[int] = None,
                   perf_flags: bool = True) -> None:
    """Pin the JAX platform and set its XLA perf flags.

    Call BEFORE the first JAX computation (backend clients read
    ``XLA_FLAGS`` once, at creation). ``platform`` in {"cpu", "gpu",
    "tpu"} (None keeps auto-detection); ``host_devices`` forces that
    many virtual CPU host devices (the 8-device debug-mesh switch the
    tests/benches set by hand today); ``perf_flags=False`` skips the
    GPU flag injection for A/B runs.
    """
    new = []
    if host_devices is not None:
        new.append(
            f"--xla_force_host_platform_device_count={host_devices}")
    if perf_flags and platform in ("gpu", "cuda"):
        new.extend(GPU_PERF_FLAGS)
    if new:
        os.environ["XLA_FLAGS"] = _merge_xla_flags(
            os.environ.get("XLA_FLAGS", ""), new)
    if platform is not None:
        import jax

        jax.config.update(
            "jax_platform_name", "gpu" if platform == "cuda" else platform)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is and nothing
    is set here. Otherwise the cache goes to ``REPO_COMPILE_CACHE``."""
    env = os.environ.get(ENV_COMPILE_CACHE)
    if env:
        return env
    import jax

    REPO_COMPILE_CACHE.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)


def backend() -> str:
    """The active JAX backend name ("cpu" / "gpu" / "tpu")."""
    import jax

    return jax.default_backend()


def topk_loop_cutover(backend_name: Optional[str] = None) -> int:
    """Per-backend loop-vs-threshold top-k cutover (see table above)."""
    b = backend_name if backend_name is not None else backend()
    if b not in TOPK_LOOP_CUTOVER:
        raise ValueError(
            f"no top-k loop/threshold cutover for backend {b!r}; known: "
            f"{sorted(TOPK_LOOP_CUTOVER)}")
    return TOPK_LOOP_CUTOVER[b]


def pallas_interpret_default(backend_name: Optional[str] = None) -> bool:
    """Resolve ``interpret=None`` for the Pallas kernels.

    Priority: the ``REPRO_PALLAS_INTERPRET`` env var ("1" forces
    interpret mode, "0" forces the compiled lowering — anything else
    raises), then the backend default: compiled on TPU and GPU
    (Mosaic / Triton lowerings), interpret on CPU where no compiled
    Pallas path exists.
    """
    env = os.environ.get(ENV_INTERPRET)
    if env is not None and env != "":
        if env not in ("0", "1"):
            raise ValueError(
                f"{ENV_INTERPRET} must be '0' (compiled) or '1' "
                f"(interpret), got {env!r}")
        return env == "1"
    b = backend_name if backend_name is not None else backend()
    return b not in ("tpu", "gpu")
