"""From a profiler trace of the traced window to per-op device time.

Three steps, each a function of plain data so that a recorded slice of a
chip trace can be fed to the later ones in a test:

1. ``extract``: the xplane file -> ``{"devices": {id: [[name, start_ns,
   dur_ns], ...]}, "host": [[name, start_ns, dur_ns, thread], ...],
   "window": [start_ns, end_ns]}``, clipped to the host span that marks
   the traced window.
2. ``hlo_table``: the compiled step's HLO text -> ``{op name: {"opcode",
   "source", "collective"}}``; the source file comes from each
   instruction's ``stack_frame_id`` through the module's StackFrames,
   FileLocations and FileNames tables.
3. ``reduce``: both -> a ``Context`` that the per-layer metric readers
   query (device seconds per step of the ops a predicate picks, busy
   time, idle gaps, the breakdown).
"""
from __future__ import annotations

import dataclasses
import glob
import math
import os
import re
from collections import Counter
from typing import Callable, Optional

WINDOW_SPAN = "bench.traced_window"
FEED_SPAN = "bench.feed"
DEVICE_PLANE = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"

# source files of the program -> layer, first match wins; a collective
# issued anywhere is the exchange's
LAYER_SOURCES = (
    ("/repro/models/", "fwd+bwd"),
    ("/repro/core/", "sync engine"),
    ("/repro/kernels/", "sync engine"),
    ("/repro/launch/", "jitted step"),
)

COLLECTIVE_OPCODES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)


# ---------------------------------------------------------------------------
# 1. xplane -> plain events
# ---------------------------------------------------------------------------


_OP_NAME = re.compile(r"^%?([\w.\-]+) = ")


def extract(trace_dir: str) -> dict:
    """Device ops and host spans of the newest trace under ``trace_dir``,
    clipped to the ``WINDOW_SPAN`` host span. A TPU trace names each op
    by its HLO instruction text; the name is the part before `` = ``."""
    from jax._src.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    host, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append([ev.name, ev.start_ns, ev.duration_ns, line.name])
        elif plane.name.startswith(DEVICE_PLANE):
            ops = []
            for line in plane.lines:
                if line.name != DEVICE_OPS_LINE:
                    continue
                for ev in line.events:
                    m = _OP_NAME.match(ev.name)
                    ops.append([m.group(1) if m else ev.name, ev.start_ns, ev.duration_ns])
            devices[plane.name[len(DEVICE_PLANE):]] = ops
    spans = [h for h in host if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
    w0, w1 = spans[-1][1], spans[-1][1] + spans[-1][2]
    inside = lambda e: e[1] + e[2] > w0 and e[1] < w1
    return {
        "window": [w0, w1],
        "devices": {d: [e for e in ops if inside(e)] for d, ops in devices.items()},
        "host": [h for h in host if inside(h) and h[0] != WINDOW_SPAN],
    }


# ---------------------------------------------------------------------------
# 2. HLO text -> op table
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_FRAME_ID = re.compile(r"stack_frame_id=(\d+)")
_OP_NAME_META = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_ROW = re.compile(r"^(\d+) (.*)$")
_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_OPERAND = re.compile(r"%([\w.\-]+)")
_FIELD = re.compile(r"(\w+)=(\d+)")
SOURCE_MARK = "/repro/"  # frames of the program under test
SHALLOW_SOURCE = "/repro/launch/"  # the step's own frame: says little alone


def _split_type(rhs: str) -> tuple:
    """(type, opcode, rest) of an instruction's right-hand side."""
    i = 0
    if rhs.startswith("("):  # tuple type: skip to its matching paren
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        i = rhs.find(" ")
    rest = rhs[i:].lstrip()
    opcode, _, args = rest.partition("(")
    return rhs[:i], opcode.strip(), args


def _sizes(type_text: str) -> set:
    """Element counts of the arrays an HLO type names."""
    return {math.prod(int(d) for d in dims.split(",") if d)
            for dims in _SHAPE.findall(type_text)}


def _source_tables(lines) -> Callable[[int], str]:
    """frame id -> the innermost source file of the program on its stack
    (the innermost file at all where no frame is the program's)."""
    tables, current = {}, None
    for line in lines:
        if line in ("FileNames", "FunctionNames", "FileLocations", "StackFrames"):
            current = tables.setdefault(line, {})
            continue
        m = _ROW.match(line)
        if m and current is not None:
            current[int(m.group(1))] = m.group(2)
        else:
            current = None
    files = {k: v.strip('"') for k, v in tables.get("FileNames", {}).items()}
    locs = {k: dict((a, int(b)) for a, b in _FIELD.findall(v))
            for k, v in tables.get("FileLocations", {}).items()}
    frames = {k: dict((a, int(b)) for a, b in _FIELD.findall(v))
              for k, v in tables.get("StackFrames", {}).items()}

    def source(frame_id: int) -> str:
        first, seen = "", set()
        while frame_id in frames and frame_id not in seen:
            seen.add(frame_id)
            f = frames[frame_id]
            path = files.get(locs.get(f.get("file_location_id"), {}).get("file_name_id"), "")
            first = first or path
            if SOURCE_MARK in path:
                return path
            frame_id = f.get("parent_frame_id", 0)
        return first

    return source


def hlo_table(hlo_text: str) -> dict:
    """Per instruction: opcode, source file, whether it belongs to the
    gradient computation (``jvp(`` in its op name: the forward and
    backward passes under ``jax.grad``), whether it is (or calls) a
    collective, and the element counts of its result and operands
    (``sizes``). XLA gives a fusion the metadata of one instruction,
    often only the outermost frame of the program (the step's
    ``shard_map`` call); such an op takes the source and the gradient
    mark that most of the instructions it calls carry."""
    lines = hlo_text.splitlines()
    source = _source_tables(lines)
    comps, current = {}, None
    out_sizes, operands = {}, {}
    for line in lines:
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            current = m.group(1)
            comps[current] = []
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            rhs = m.group(2)
            type_text, opcode, args = _split_type(rhs)
            fid = _FRAME_ID.search(rhs)
            name = _OP_NAME_META.search(rhs)
            out_sizes[m.group(1)] = _sizes(type_text)
            operands[m.group(1)] = _OPERAND.findall(args.split("metadata=")[0])
            comps[current].append((m.group(1), opcode,
                                   source(int(fid.group(1))) if fid else "",
                                   "jvp(" in name.group(1) if name else None,
                                   _CALLS.findall(rhs)))

    def called(calls, seen):
        for c in calls:
            if c in seen or c not in comps:
                continue
            seen.add(c)
            for item in comps[c]:
                yield item
                yield from called(item[4], seen)

    table = {}
    for instrs in comps.values():
        for name, opcode, src, grad, calls in instrs:
            inner = list(called(calls, set()))
            collective = opcode.startswith(COLLECTIVE_OPCODES) or any(
                o.startswith(COLLECTIVE_OPCODES) for _, o, *_ in inner)
            if inner and (not src or SHALLOW_SOURCE in src):
                deep = Counter(s for _, _, s, *_ in inner if s and SHALLOW_SOURCE not in s)
                if deep:
                    src = deep.most_common(1)[0][0]
            if inner and not grad:
                marks = [g for *_, g, _ in inner if g is not None]
                grad = bool(marks) and sum(marks) * 2 > len(marks)
            sizes = set(out_sizes[name])
            for o in operands[name]:
                sizes |= out_sizes.get(o, set())
            table[name] = {"opcode": opcode, "source": src, "grad": bool(grad),
                           "collective": collective, "sizes": sorted(sizes)}
    return table


# ---------------------------------------------------------------------------
# 3. reduction
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Op:
    name: str
    start_ns: float
    dur_ns: float
    layer: str
    known: bool


def classify(info: Optional[dict], bucket_sizes=()) -> str:
    """The layer of one op-table entry. An op that carries no frame of a
    layer (only the step's own frame, or none) but reads or writes an
    array of a compressed bucket's size is the sync engine's: its
    bucket-wide passes (``u = m + eta*g``, ``u - sel``) carry only the
    step's frame."""
    if not info:
        return "unattributed"
    if info["collective"]:
        return "exchange"
    if info["grad"]:
        return "fwd+bwd"
    layer = next((lay for frag, lay in LAYER_SOURCES if frag in info["source"]),
                 "unattributed")
    if layer in ("jitted step", "unattributed") and \
            set(info.get("sizes", ())) & set(bucket_sizes):
        return "sync engine"
    return layer


def layer_of(op: "Op") -> str:
    return op.layer


def _union(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _leaves(ops) -> list:
    """Ops that contain no other op of the same device: a parent (a loop
    or a call) is counted through its children, never twice."""
    ops = sorted(ops, key=lambda o: (o.start_ns, -o.dur_ns))
    out = []
    for i, o in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt.start_ns < o.start_ns + o.dur_ns \
                and nxt.start_ns + nxt.dur_ns <= o.start_ns + o.dur_ns:
            continue
        out.append(o)
    return out


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader may read about the traced window."""

    window_s: float
    steps: int
    chips: int
    peaks: dict
    facts: dict  # counts the harness computed from shapes (flops, elements, bucket sizes)
    ops: dict  # device -> [Op], leaves only, clipped to the window
    busy: dict  # device -> busy seconds in the window
    gaps: list  # [(seconds, host span name)] of device 0, longest first

    def device_s(self, pick: Callable[[Op], bool]) -> float:
        """Device seconds of the picked ops, averaged over the chips."""
        tot = [sum(o.dur_ns for o in ops if pick(o)) for ops in self.ops.values()]
        return sum(tot) / len(tot) / 1e9

    def per_step_ms(self, pick: Callable[[Op], bool]) -> Optional[float]:
        if not self.steps:
            return None
        return 1e3 * self.device_s(pick) / self.steps

    @property
    def busy_s(self) -> float:
        return sum(self.busy.values()) / len(self.busy)

    def breakdown(self, layer_of: Callable[[Op], str], n: int = 10) -> dict:
        per = {}
        for ops in self.ops.values():
            for o in ops:
                key = f"{layer_of(o)}: {o.name}"
                per[key] = per.get(key, 0.0) + o.dur_ns / 1e9 / len(self.ops)
        top = sorted(per.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[name, s] for s, name in self.gaps[:n]]}


def _host_name(host, t_ns) -> str:
    """The innermost span around ``t_ns`` on the thread that runs
    ``train()`` (the one that carries the harness's own spans); the
    train() loop itself where neither the harness nor the runtime
    shows anything."""
    threads = {h[3] for h in host if h[0] == FEED_SPAN}
    around = [h for h in host if h[1] <= t_ns <= h[1] + h[2] and h[2] > 0
              and (not threads or h[3] in threads)]
    if not around:
        return "train() host loop"
    name, *_ = max(around, key=lambda h: h[1])
    return "harness batch iterator" if name == FEED_SPAN else name


def reduce(events: dict, table: dict, *, steps: int, chips: int,
           peaks: dict, facts: dict) -> Context:
    w0, w1 = events["window"]
    buckets = facts.get("bucket_sizes", ())
    ops, busy, gaps = {}, {}, []
    first = min(events["devices"])
    for dev, evs in sorted(events["devices"].items()):
        clipped = []
        for name, s, d in evs:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 <= s2:
                continue
            info = table.get(name)
            clipped.append(Op(name, s2, e2 - s2, classify(info, buckets),
                              info is not None))
        ops[dev] = _leaves(clipped)
        union = _union([[o.start_ns, o.start_ns + o.dur_ns] for o in clipped])
        busy[dev] = sum(e - s for s, e in union) / 1e9
        if dev == first:
            edges = [w0] + [x for iv in union for x in iv] + [w1]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append(((b - a) / 1e9,
                                 _host_name(events["host"], (a + b) / 2)))
    gaps.sort(key=lambda g: -g[0])
    return Context(window_s=(w1 - w0) / 1e9, steps=steps, chips=chips,
                   peaks=peaks, facts=facts, ops=ops, busy=busy, gaps=gaps)
