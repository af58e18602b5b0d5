"""One benchmark run of one cell: set-up, the measured window through the
program's ``train()``, the traced reading of the layers, and the check
that decides ``correct``.

Everything that belongs to one configuration, traffic mix or metric is
found by its name: ``configs/<config>.json`` and
``reference/<config>.py``, ``traffic/<traffic>.json``,
``limits/<workload>.json`` and ``metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import resource
import statistics
import sys
import time
from pathlib import Path

import numpy as np

from bench import correct, trace
from bench.peaks import peaks_for

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = BENCH / "traces"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, benchmark: dict | None = None) -> dict:
    """The workload's entry and every file it names, found by name."""
    if benchmark is None:
        benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in entries:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(entries)}")
    w = entries[workload]
    cfg_entry = {c["name"]: c for c in benchmark["configs"]}[w["config"]]
    per_layer = [(m["name"], m["unit"]) for m in benchmark["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m["name"] for m in benchmark["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return {
        "workload": w,
        "config": json.loads((ROOT / cfg_entry["file"]).read_text()),
        "traffic": json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        "reference": _module(BENCH / "reference" / f"{w['config']}.py",
                             f"bench_reference_{w['config'].replace('-', '_')}"),
        "limits": json.loads((BENCH / "limits" / f"{workload}.json").read_text()),
        "readers": {n: (_module(BENCH / "metrics" / f"{n}.py", f"bench_metric_{n}"), u)
                    for n, u in per_layer},
        "end_to_end": end_to_end,
    }


# ---------------------------------------------------------------------------
# inputs
# ---------------------------------------------------------------------------


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits allowed)."""
    import jax

    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def token_pool(traffic: dict, vocab: int, key) -> np.ndarray:
    """(distinct_batches, workers * batch, seq + 1) token ids, uniform over
    the vocabulary; step i trains on pool[i % distinct_batches]."""
    import jax
    import jax.numpy as jnp

    if traffic["tokens"] != "uniform":
        raise ValueError(f"unknown token distribution {traffic['tokens']!r}")
    shape = (traffic["distinct_batches"],
             traffic["data_parallel"] * traffic["batch_per_worker"],
             traffic["seq_len"] + 1)
    return np.asarray(jax.jit(
        lambda k: jax.random.randint(k, shape, 0, vocab, jnp.int32))(key))


def host_reading() -> tuple:
    """This thread's CPU seconds, the process's CPU seconds, and this
    thread's minor and major page faults and voluntary and involuntary
    context switches so far."""
    t = resource.getrusage(resource.RUSAGE_THREAD)
    p = resource.getrusage(resource.RUSAGE_SELF)
    return (t.ru_utime + t.ru_stime, p.ru_utime + p.ru_stime,
            t.ru_minflt, t.ru_majflt, t.ru_nvcsw, t.ru_nivcsw)


def longest_step(intervals, host, dispatch, first_call) -> str:
    """What the host did in the window's longest step interval: interval
    k runs from request k to request k + 1, and holds the dispatch of
    step call ``first_call + k`` and the drain of the previous step's
    loss."""
    k = max(range(len(intervals)), key=lambda i: intervals[i])
    a, b = dispatch[first_call + k] if first_call + k < len(dispatch) else (0.0, 0.0)
    if k + 1 >= len(host):
        return f"longest step #{k} {1e3 * intervals[k]:.1f} ms (the last)"
    d = [y - x for x, y in zip(host[k], host[k + 1])]
    med = statistics.median(intervals)
    return (f"longest step #{k} {1e3 * intervals[k]:.1f} ms (median {1e3 * med:.1f}; "
            f"{sum(i > 1.5 * med for i in intervals)} over 1.5x): dispatch "
            f"{1e3 * (b - a):.1f} ms, drain and host loop {1e3 * (intervals[k] - b + a):.1f} ms; "
            f"thread CPU {1e3 * d[0]:.1f} ms, process CPU {1e3 * d[1]:.1f} ms; "
            f"page faults {d[2]:.0f} minor {d[3]:.0f} major; "
            f"context switches {d[4]:.0f} voluntary {d[5]:.0f} involuntary")


class Feed:
    """The batch iterator handed to ``train()``.

    ``train()`` asks for batch j once step j-2's loss is drained, so the
    time of each request marks the end of a step on the device. The
    window opens at the request after the last warm-up step has ended and
    closes at the first request after ``seconds``; with a tracer the
    profiler records ``trace_steps`` steps from the window's start."""

    def __init__(self, batches, warmup: int, seconds: float,
                 trace_steps: int = 0, trace_dir: Path | None = None):
        self.batches = batches
        self.warmup = warmup
        self.seconds = seconds
        self.trace_steps = trace_steps
        self.trace_dir = trace_dir
        self.requests = []
        self.host = []  # per request: thread CPU s, process CPU s, faults, switches
        self.t_open = None
        self._span = None
        self.traced_steps = 0
        self.gc_pauses = []
        self._gc_start = 0.0

    def __iter__(self):
        import jax

        i = 0
        open_at = self.warmup + 1
        while True:
            with jax.profiler.TraceAnnotation(trace.FEED_SPAN):
                if i == open_at - 1:
                    # set-up's garbage (tracing, compiling, host copies) is
                    # collected here, not by a full collection in the window
                    gc.collect()
                    gc.callbacks.append(self._on_gc)
                now = time.perf_counter()
                if self.trace_dir is not None:
                    self._trace_control(i, open_at)
                if i == open_at:
                    self.t_open = now
                if self.t_open is not None:
                    self.requests.append(now)
                    self.host.append(host_reading())
                    if now - self.t_open >= self.seconds:
                        return
                batch = self.batches[i % len(self.batches)]
            yield batch
            i += 1

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self.t_open is not None:
            self.gc_pauses.append((info["generation"], time.perf_counter() - self._gc_start))

    def _trace_control(self, i: int, open_at: int) -> None:
        import jax

        if i == open_at - 1:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
        elif i == open_at:
            self._span = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self._span.__enter__()
        elif i == open_at + self.trace_steps and self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self.traced_steps = self.trace_steps
            jax.profiler.stop_trace()

    def close(self) -> None:
        """Stop a trace that the window cut short."""
        import jax

        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None
            self.traced_steps = max(0, len(self.requests) - 1)
            jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# the step as train() builds it, with host copies of the checked state
# ---------------------------------------------------------------------------


def _host_leaves(tree) -> list:
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _host_worker_mean(memory) -> list:
    """Each (workers, ...) memory leaf averaged over its workers, on the host."""
    import jax

    out = []
    for x in jax.tree.leaves(memory):
        shards = {str(s.index): s for s in x.addressable_shards}.values()
        acc = None
        for s in shards:
            part = np.asarray(s.data, np.float32).sum(axis=0)
            acc = part if acc is None else acc + part
        out.append(acc / x.shape[0])
    return out


class StepTap:
    """Stands between ``train()`` and the jitted step it builds: the same
    compiled step serves every call; the first ``checked`` calls keep host
    copies of the state the check reads, before the next call donates it."""

    def __init__(self, checked: int):
        self.checked = checked
        self.calls = 0
        self.real = None
        self.jitted = None
        self.abstract = None
        self.losses = []
        self.dispatch = []  # per call: host clock before and after it
        self._compiled = None
        self.p0 = self.p1 = self.m1 = self.p3 = None

    def wrap(self, real, jitted):
        """``real``: what each call runs; ``jitted``: the compiled step
        that ``train()`` built, which ``real`` calls (the same, unless a
        test plants a fault around it)."""
        self.real = real
        self.jitted = jitted

        def step(*args):
            return self(*args)

        step.local_steps = getattr(real, "local_steps", 1)
        step._cache_size = getattr(real, "_cache_size", None)
        return step

    def __call__(self, *args):
        import jax

        i = self.calls
        self.calls += 1
        if i == 0:
            self.abstract = jax.tree.map(  # an uncommitted array goes where the step puts it
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=x.sharding if x.committed else None),
                args)
            self.p0 = _host_leaves(args[0])
        t = time.perf_counter()
        out = self.real(*args)
        self.dispatch.append((t, time.perf_counter()))
        if i < self.checked:
            self.losses.append(out[4]["loss"])
        if i == 0:
            self.p1 = _host_leaves(out[0])
            self.m1 = _host_worker_mean(out[1])
        if i == self.checked - 1:
            self.p3 = _host_leaves(out[0])
        return out

    def compiled(self):
        """The step as compiled for the state's shapes (from the compile
        cache, which the window's own call filled)."""
        if self._compiled is None:
            self._compiled = self.jitted.lower(*self.abstract).compile()
        return self._compiled


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def program_config(cfg_file: dict):
    """The program's model config, checked against the sizes the file states."""
    from repro.configs import get_config

    prog = cfg_file["program"]
    mc = get_config(prog["arch"])
    over = {k: (dataclasses.replace(getattr(mc, k), **v) if isinstance(v, dict) else v)
            for k, v in prog.get("overrides", {}).items()}
    mc = mc.replace(**over)
    for path, want in prog.get("expect", {}).items():
        have = mc
        for part in path.split("."):
            have = getattr(have, part)
        if have != want:
            raise ValueError(f"program config {path} = {have!r}, the file states {want!r}")
    return mc


def train_config(traffic: dict):
    from repro.core.distributed import SyncConfig
    from repro.launch.train import TrainConfig

    sync = dict(traffic["sync"])
    return TrainConfig(optimizer=traffic["optimizer"], eta=traffic["eta"],
                       sync=SyncConfig.preset(sync.pop("preset"), **sync))


def peak_bytes(stats: list, step_temp: int) -> int:
    """The peak of device memory on the fullest chip: the allocator's
    peak of buffers in use, plus the memory held apart from buffers for
    loaded programs' scratch. That is the runtime's peak reservation
    where it reports one, and never less than the timed step's own
    temporaries (``memory_analysis``), which the buffer count leaves
    out. ``stats``: each chip's ``memory_stats()``."""
    return max(int(s.get("peak_bytes_in_use", 0))
               + max(int(s.get("peak_bytes_reserved", 0)), step_temp) for s in stats)


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, devices,
             t0: float, planted=None, numbers_out: dict | None = None) -> dict:
    """One run. ``devices``: the chips the cell asks for. ``planted``, for
    tests only, wraps the jitted step ``train()`` builds. ``numbers_out``
    receives every number the check computed, limited or not."""
    import jax

    from repro.launch import train as train_mod
    from repro.models import build_model
    from repro.utils import compat
    from repro.utils.platform import enable_compile_cache
    from repro.utils.telemetry import NonFiniteLossError, Telemetry

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg_file, traffic, ref = cell["config"], cell["traffic"], cell["reference"]
    m = cfg_file["model"]
    chips = cell["workload"]["chips"]
    if traffic["data_parallel"] != chips or len(devices) != chips:
        raise ValueError(f"traffic {cell['workload']['traffic']} runs "
                         f"{traffic['data_parallel']} workers; cell asks "
                         f"{chips} chips, {len(devices)} given")
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if devices[0].platform != "cpu" else None
    model = build_model(program_config(cfg_file))
    mesh = compat.make_mesh((chips, 1), ("data", "model"), devices=devices)
    tc = train_config(traffic)

    key = seed_key(seed)
    init_key = jax.random.fold_in(key, 0)
    pool = token_pool(traffic, m["vocab"], jax.random.fold_in(key, 1))
    sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    batches = [jax.device_put({"tokens": b[:, :-1], "labels": b[:, 1:]}, sharding)
               for b in pool]
    trace_dir = None
    if traced:
        trace_dir = TRACE_DIR / cell["workload"]["name"]
        _clear(trace_dir)
    feed = Feed(batches, traffic["warmup_steps"], seconds,
                traffic["trace_steps"] if traced else 0, trace_dir)
    tap = StepTap(traffic["checked_steps"])
    built = train_mod.make_train_step

    def make_step(*a, **k):
        step = built(*a, **k)
        return tap.wrap(planted(step) if planted is not None else step, step)

    compiles = {"window": 0}

    def on_compile(event, *_a, **_k):
        if feed.t_open is not None and "compile" in event:
            compiles["window"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    tel = Telemetry(printer=log)
    nonfinite = None
    train_mod.make_train_step = make_step
    try:
        state = train_mod.train(model, mesh, tc, feed, n_steps=10**9, log_every=0,
                                rng=init_key, telemetry=tel)
        jax.block_until_ready(state[:4])
    except NonFiniteLossError as e:
        nonfinite, state = str(e), None
    finally:
        train_mod.make_train_step = built
        feed.close()
        tel.close()
    t_end = time.perf_counter()
    jax.monitoring.unregister_event_duration_listener(on_compile)
    stats = [d.memory_stats() or {} for d in devices]
    if feed.t_open is None:
        raise RuntimeError(f"the window never opened: {nonfinite or 'train() stopped early'}")

    req = feed.requests
    steps = len(req)  # each request after the first marks one more step done
    intervals = [b - a for a, b in zip(req, req[1:])] + [t_end - req[-1]]
    window_s = t_end - feed.t_open
    setup_s = feed.t_open - t0
    tokens = steps * traffic["data_parallel"] * traffic["batch_per_worker"] * traffic["seq_len"]
    longest = sorted(range(len(intervals)), key=lambda i: -intervals[i])[:3]
    full_gc = [p for g, p in feed.gc_pauses if g == 2]
    log(f"window: {steps} steps in {window_s:.3f} s; set-up {setup_s:.3f} s; "
        f"compiles in window {compiles['window']}; longest steps "
        + ", ".join(f"#{i} {1e3 * intervals[i]:.1f} ms" for i in longest)
        + f"; full collections in window {len(full_gc)} ({sum(full_gc):.3f} s)")
    log(longest_step(intervals, feed.host, tap.dispatch, traffic["warmup_steps"] + 1))
    step_mem = tap.compiled().memory_analysis()
    step_temp = int(getattr(step_mem, "temp_size_in_bytes", 0) or 0)
    mem_peak = peak_bytes(stats, step_temp)
    log(f"memory: peak {mem_peak} B; the step's temporaries {step_temp} B, arguments "
        f"{getattr(step_mem, 'argument_size_in_bytes', None)} B; "
        f"allocator of chip 0 {json.dumps(stats[0])}")
    e2e = {
        "tokens_per_s": (tokens / window_s, "tokens/s"),
        "step_ms_p90": (1e3 * statistics.quantiles(intervals, n=10, method="inclusive")[8]
                        if len(intervals) > 1 else 1e3 * intervals[0], "ms"),
        "peak_hbm_gib": (mem_peak / 2**30, "GiB"),
        "setup_s": (setup_s, "s"),
    }

    result_metrics, breakdown, device_extra = {}, None, {}
    flops_per_step = ref.flops_per_token(m, traffic["seq_len"]) * tokens / steps
    lay = correct.layout(jax.eval_shape(lambda: ref.init(m, init_key)),
                         traffic["sync"].get("bucket_cols", 1024))
    buckets = [r * c for kind, r, c, _ in lay.buckets if kind == "sparse"] \
        if traffic["optimizer"] == "memsgd" else []
    if traced:
        events = trace.extract(str(trace_dir))
        table = trace.hlo_table(tap.compiled().as_text())
        ctx = trace.reduce(events, table, steps=feed.traced_steps, chips=chips,
                           peaks=peaks or {}, facts={"flops_per_step": flops_per_step,
                                                     "compressed_elements": sum(buckets),
                                                     "bucket_sizes": buckets})
        shares = {}
        for ops in ctx.ops.values():
            for o in ops:
                shares[trace.layer_of(o)] = shares.get(trace.layer_of(o), 0.0) + o.dur_ns
        total = sum(shares.values()) or 1.0
        log(f"traced {ctx.steps} steps in {ctx.window_s:.4f} s; device busy {ctx.busy_s:.4f} s; "
            "share of device time by layer: " + ", ".join(
                f"{k} {100 * v / total:.3f} %" for k, v in sorted(shares.items())))
        log(f"unattributed share of device time {100 * shares.get('unattributed', 0.0) / total:.3f} %"
            f" (no frame of the program); step frame only {100 * shares.get('jitted step', 0.0) / total:.3f} %")
        for name, (reader, unit) in cell["readers"].items():
            v = reader.read(ctx)
            if v is not None:
                result_metrics[name] = {"value": v, "unit": unit}
        device_extra = {"busy_s": ctx.busy_s, "window_s": ctx.window_s}
        breakdown = ctx.breakdown(trace.layer_of)
        _clear(trace_dir)  # nothing large outlives the run
    else:
        for name in cell["end_to_end"]:
            v, unit = e2e[name]
            result_metrics[name] = {"value": v, "unit": unit}

    # the check, once the window has closed and the program's state is freed
    numbers = None
    if nonfinite is None:
        prog = correct.program_numbers(tap, lay if traffic["optimizer"] == "memsgd" else None,
                                       traffic["eta"])
        del state, batches, tap.real, tap.jitted, tap._compiled
        tap.p1 = tap.m1 = None
        gc.collect()
        jax.clear_caches()  # a loaded executable keeps its scratch memory
        t_ref = time.perf_counter()
        refr = correct.reference_run(ref, m, traffic, pool, init_key, devices)
        numbers = correct.compare(prog, refr, _leaf_names(lay))
        init_gap = max(float(np.max(np.abs(a - b)))
                       for a, b in zip(tap.p0, refr["p0_host"]))
        where = {k: v for k, v in numbers["where"].items() if not k.endswith("_gaps")}
        log(f"reference: {time.perf_counter() - t_ref:.1f} s; init gap {init_gap:.3g}; "
            f"losses program {prog['losses']} reference {refr['losses']}; "
            f"where {json.dumps(where)}")
        ok, checks = correct.judge(numbers, cell["limits"])
        if numbers_out is not None:
            numbers_out.update(numbers, program=prog["losses"], reference=refr["losses"],
                               init_gap=init_gap)
    else:
        log(f"non-finite loss: {nonfinite}")
        ok, checks = False, {n: {"value": math.inf, "limit": v}
                             for n, v in cell["limits"].items()}
    ok = ok and compiles["window"] == 0
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    result = {
        "correct": bool(ok),
        "attempted": steps,
        "failed": 0 if nonfinite is None else 1,
        "metrics": result_metrics,
        "device": {"platform": devices[0].platform, "kind": kind, "count": chips,
                   "memory_peak_bytes": mem_peak, **device_extra},
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _clear(directory: Path) -> None:
    if directory.exists():
        for f in sorted(directory.rglob("*"), reverse=True):
            f.unlink() if f.is_file() else f.rmdir()


def _leaf_names(lay) -> list:
    import jax

    paths = jax.tree_util.tree_flatten_with_path(
        jax.tree.unflatten(lay.treedef, [0] * len(lay.placements)))[0]
    return [jax.tree_util.keystr(p) for p, _ in paths]
