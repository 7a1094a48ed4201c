"""The program's own host spans and scoped device operations, from the
profile of a traced run.

The program marks its steps with ``serve.*`` host spans
(``repro.serving.tracing.span``) that carry integer counters, and names
parts of its jitted steps with ``jax.named_scope`` (``kv_window``,
``attention``, ``mlp``, ``head``). Both land in the ``.xplane.pb`` that
``cell.Tracer`` writes, on the profiler's clock: a device operation's
scope path is the ``tf_op`` stat of its metadata. ``of(w)`` loads the
newest such file under ``.bench_out/*/trace`` once per process and shifts
its times onto the stamps' wall clock through the ``bench.trace_begin``
marker, as ``TraceView`` does. A profile with no ``serve.*`` span, from a
program that emits none, gives None, and every reader of it reports
nothing. ``scripts/program_breakdown.py`` prints a traced run's device
time by jitted step and scope and its idle time by span from the same
``ProgramTrace``.
"""
from __future__ import annotations

import bisect
import json
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from yardstick import xtrace

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"           # cell.Tracer writes <cell>/trace here
SPAN_PREFIX = "serve."
MARK_BEGIN, MARK_END = "bench.trace_begin", "bench.trace_end"
# the steps that hold a scheduling round's device work, and the scopes a
# step's operations are split by
STEPS = ("serve.prefill", "serve.insert", "serve.decode")
SCOPES = ("kv_window", "attention", "mlp", "head")
# the stat of an operation's metadata that holds its scope path
# ("jit(decode_step_paged)/while/body/closed_call/kv_window/gather:")
SCOPE_STAT = "tf_op"

Span = Tuple[str, float, float, Dict[str, int]]   # name, start, end, counters
Op = Tuple[float, float, str]                     # start, end, scope path
Module = Tuple[float, float, str]                 # start, end, HLO module


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message: an int
    for a varint, a memoryview for anything else."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} in a profile")
        yield key >> 3, v


def op_scopes(raw: bytes) -> Dict[str, str]:
    """{operation name: scope path} over the device planes of a profile.

    The path is the ``tf_op`` stat of each event's metadata, which
    ``jax.profiler.ProfileData`` does not expose, so it is read from the
    ``XSpace`` protobuf (``tsl/profiler/protobuf/xplane.proto``):
    ``XSpace.planes`` 1; ``XPlane`` name 2, event_metadata 4, stat_metadata
    5 (maps: key 1, value 2); ``XEventMetadata`` name 2, stats 5;
    ``XStatMetadata`` id 1, name 2; ``XStat`` metadata_id 1, str_value 5,
    ref_value 7 (the id of a stat metadata whose name is the string)."""
    out: Dict[str, str] = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(dict(_fields(v)).get(2, b""))
            elif pf == 5:
                md = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        scope_id = {n: k for k, n in stat_names.items()}.get(SCOPE_STAT)
        for ev in events:
            ev_name, path = "", ""
            for ef, v in _fields(ev):
                if ef == 2:
                    ev_name = bytes(v).decode()
                elif ef == 5:
                    st = dict(_fields(v))
                    if st.get(1) != scope_id:
                        continue
                    path = (bytes(st[5]).decode() if 5 in st
                            else stat_names.get(st.get(7), ""))
            if path:
                out[ev_name] = path
    return out


def load(path: str) -> Dict[str, object]:
    """{"spans": [serve.* spans], "ops": [device ops of the first chip],
    "modules": [its HLO module runs], "begin"/"end": times of the markers
    or None}, in seconds on the profile's clock."""
    import jax
    raw = Path(path).read_bytes()
    scopes = op_scopes(raw)
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    spans: List[Span] = []
    devices: Dict[str, Dict[str, list]] = {}
    marks: Dict[str, float] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            devices[plane.name] = {
                "ops": [(e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         "" if any(c in e.name for c in xtrace.CONTAINERS)
                         else scopes.get(e.name, ""))
                        for e in lines["XLA Ops"].events],
                "modules": [(e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9, e.name)
                            for ln in lines.values() if ln.name
                            == "XLA Modules" for e in ln.events]}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      {k: v for k, v in e.stats
                                       if isinstance(v, int)}))
                    elif e.name in (MARK_BEGIN, MARK_END):
                        marks[e.name] = e.start_ns * 1e-9
    chip = devices[sorted(devices)[0]] if devices else {}
    return {"spans": spans, "ops": chip.get("ops", []),
            "modules": chip.get("modules", []),
            "begin": marks.get(MARK_BEGIN), "end": marks.get(MARK_END)}


class ProgramTrace:
    """The program's spans that lie in the window [a, b] and the chip's
    operations, on one clock. Operations inside a loop or call are events
    of their own; the loop's event has no scope path, so scoped sums count
    each operation once."""

    def __init__(self, spans: Sequence[Span], ops: Sequence[Op], a: float,
                 b: float, block_size: int, modules: Sequence[Module] = ()):
        self.a, self.b, self.block_size = a, b, block_size
        self.modules = modules
        self.spans = sorted((s for s in spans if s[1] >= a and s[2] <= b),
                            key=lambda s: s[1])
        self._starts = [s[1] for s in self.spans]
        self.busy = xtrace.Busy([(s, e) for s, e, _ in ops])
        # scoped operations run one at a time on a core: disjoint
        self.scoped = sorted(op for op in ops if op[2])
        self._op_starts = [s for s, _, _ in self.scoped]

    def named(self, name: str) -> List[Span]:
        """Spans called ``name``, in time order."""
        return [s for s in self.spans if s[0] == name]

    def inside(self, outer: Span, names: Sequence[str]) -> List[Span]:
        """Spans called one of ``names`` that lie inside ``outer``."""
        lo = bisect.bisect_left(self._starts, outer[1])
        hi = bisect.bisect_right(self._starts, outer[2])
        return [s for s in self.spans[lo:hi] if s[0] in names
                and s[2] <= outer[2] and s is not outer]

    def device_s(self, a: float, b: float) -> float:
        """Seconds in [a, b] in which the chip ran an operation."""
        return self.busy.within(a, b)

    def scope_s(self, a: float, b: float, scope: str) -> float:
        """Device seconds in [a, b] of operations under ``scope``."""
        tot = 0.0
        i = max(bisect.bisect_left(self._op_starts, a) - 1, 0)
        for s, e, path in self.scoped[i:]:
            if s >= b:
                break
            if e > a and scope in path.split("/"):
                tot += min(e, b) - max(s, a)
        return tot

    def idle_s(self, a: float, b: float) -> float:
        """Seconds in [a, b] in which the chip ran nothing."""
        return b - a - self.device_s(a, b)

    def host_ms(self, span: Span) -> float:
        """Milliseconds of ``span`` in which the chip ran nothing."""
        return 1e3 * self.idle_s(span[1], span[2])

    def idle_by_span(self) -> Dict[str, float]:
        """Seconds of the window in which the chip ran nothing, by the
        innermost span over them (the latest-started one still open),
        "none" where no span is."""
        timed = [i for i, s in enumerate(self.spans) if s[2] > s[1]]
        edges = sorted([(self.spans[i][1], 1, i) for i in timed]
                       + [(self.spans[i][2], 0, i) for i in timed])
        out: Dict[str, float] = defaultdict(float)
        opened: List[int] = []
        t = self.a
        for at, starts, i in edges + [(self.b, 0, -1)]:
            name = self.spans[opened[-1]][0] if opened else "none"
            out[name] += self.idle_s(t, at)
            t = at
            if starts:
                opened.append(i)
            elif i >= 0:
                opened.remove(i)
        return dict(out)

    def device_by_scope(self) -> Dict[str, Dict[str, float]]:
        """Device seconds in the window of each jitted step's operations
        (the first part of a scope path, ``jit(decode_step_paged)``), split
        by the first of ``SCOPES`` on each operation's path, "rest" where
        none is."""
        out: Dict[str, Dict[str, float]] = defaultdict(
            lambda: defaultdict(float))
        for s, e, path in self.scoped:
            d = min(e, self.b) - max(s, self.a)
            if d > 0:
                parts = path.split("/")
                scope = next((p for p in parts if p in SCOPES), "rest")
                out[parts[0]][scope] += d
        return {k: dict(v) for k, v in out.items()}

    def module_s(self) -> Dict[str, float]:
        """Seconds of the window each HLO module ran on the chip, by name
        without the program id (``jit_scatter_blocks(6161…)``): one
        engine's jits for several shapes add up."""
        out: Dict[str, float] = defaultdict(float)
        for s, e, name in self.modules:
            d = min(e, self.b) - max(s, self.a)
            if d > 0:
                out[name.split("(")[0]] += d
        return dict(out)


def breakdown(path: str) -> Dict[str, object]:
    """Where the traced window of the profile at ``path`` went, in % of
    the window between its markers: device time by HLO module
    (``modules``) and by jitted step and scope (``scopes``); and, in % of
    the chip's idle time, by the innermost ``serve.*`` span over it
    (``idle``), with the leaf spans' sum (``leaves``)."""
    raw = load(path)
    a, b = raw["begin"], raw["end"]
    if a is None or b is None:
        raise ValueError(f"{path} holds no trace markers")
    pt = ProgramTrace(raw["spans"], raw["ops"], a, b, 0, raw["modules"])
    win = b - a
    idle = pt.idle_s(a, b)
    by_span = pt.idle_by_span()
    parents = ("none", "serve.round") + STEPS
    return {
        "window_s": win,
        "busy_pct": 100 * (win - idle) / win,
        "modules": {m: 100 * t / win for m, t in sorted(
            pt.module_s().items(), key=lambda kv: -kv[1])},
        "scopes": {step: {k: 100 * t / win for k, t in sorted(sc.items())}
                   for step, sc in pt.device_by_scope().items()},
        "idle": {n: 100 * t / idle for n, t in sorted(
            by_span.items(), key=lambda kv: -kv[1])} if idle > 0 else {},
        "leaves": (100 * sum(t for n, t in by_span.items()
                             if n not in parents) / idle) if idle > 0 else 0.0,
    }


def block_size_of(cell: str) -> int:
    """The KV block size of the deployment the cell builds."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    traffic = {c["name"]: c["traffic"] for c in spec["workloads"]}[cell]
    dep = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return int(dep["deployment"]["block_size"])


_loaded: Dict[Tuple[str, float, float], Optional[ProgramTrace]] = {}


def of(w) -> Optional[ProgramTrace]:
    """The program trace of window ``w``'s traced run, or None where the
    run was not traced or the program emitted no ``serve.*`` span."""
    tr = w.trace
    if tr is None:
        return None
    files = sorted(OUT.glob("*/trace/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return None
    path = files[-1]
    key = (str(path), path.stat().st_mtime, tr.a)
    if key not in _loaded:
        raw = load(str(path))
        pt = None
        if raw["spans"] and raw["begin"] is not None:
            off = tr.a - raw["begin"]
            cell = path.relative_to(OUT).parts[0]
            pt = ProgramTrace(
                [(n, s + off, e + off, c) for n, s, e, c in raw["spans"]],
                [(s + off, e + off, p) for s, e, p in raw["ops"]],
                tr.a, tr.b, block_size_of(cell))
        _loaded[key] = pt
    return _loaded[key]
