"""One benchmark cell: set up, measure one window, check what it served.

``run_cell`` is the whole of a run after the device check: weights from
the seed, the cell's deployment built from the program's engines and
policies, every shape the traffic reaches served once, then one window of
wall-paced traffic, the metrics, and the comparison of a sample of the
served requests with the plain reference.
"""
from __future__ import annotations

import gc
import importlib.util
import math
import shutil
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from yardstick import xtrace
from yardstick.family import family_of
from yardstick.pacing import (WallPaced, WallRecorder, WallStamps,
                              WindowClosed, instrument)
from yardstick.traffic import grid_lengths, rng_for

BENCH = Path(__file__).resolve().parents[1]
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Counts JAX's traces, lowerings and compilations since it was made
    (the ``jax.monitoring`` listener of ``chip_smoke.py``)."""

    def __init__(self):
        from jax import monitoring
        self.events = 0
        self.secs = 0.0
        self.live = True
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if self.live and event in COMPILE_EVENTS:
            self.events += 1
            self.secs += secs


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100): a value that was observed."""
    if not values:
        return math.nan
    xs = sorted(values)
    k = max(0, math.ceil(q / 100 * len(xs)) - 1)
    return float(xs[k])


class Window:
    """What the metric readers see of one run: the counts come from the
    configuration's family (``yardstick/family.py``)."""

    def __init__(self, family, dims, peaks: Dict, stamps: WallStamps,
                 t0: float, seconds: float, setup_s: float):
        self.family, self.dims = family, dims
        self.peaks, self.stamps = peaks, stamps
        self.t0, self.seconds = t0, seconds
        self.t_end = t0 + seconds
        self.setup_s = setup_s
        self.trace: Optional["TraceView"] = None
        # host-clock layer metrics read the window up to here: in a traced
        # run, HOST_MARGIN_S before the profiler starts, whose start stalls
        # the host for seconds
        self.host_end = self.t_end

    def due_in_window(self, end: Optional[float] = None) -> List[int]:
        end = self.t_end if end is None else end
        return [rid for rid, d in self.stamps.due.items()
                if self.t0 <= d < end]

    def prefill_flops(self, prefills) -> float:
        return float(sum(self.family.prefill_flops(self.dims, isl)
                         for _, _, isl in prefills))

    def decode_flops(self, decodes) -> float:
        return float(sum(self.family.decode_flops(self.dims, c)
                         for _, _, ctx in decodes for c in ctx))

    def decode_bound_s(self, ctx) -> float:
        """Least time one decode step could take on this chip."""
        p, f = self.peaks, self.family
        return max(sum(f.decode_flops(self.dims, c) for c in ctx)
                   / p["bf16_flops"],
                   f.decode_step_bytes(self.dims, ctx) / p["hbm_bytes_per_s"])


class TraceView:
    """The traced part of a window, on the wall clock of the stamps."""

    def __init__(self, raw: Dict, pc_begin: float, pc_end: float):
        spans = raw["spans"]
        marks = {n: s for n, s, _ in spans if n in (MARK_BEGIN, MARK_END)}
        if MARK_BEGIN not in marks:
            raise RuntimeError("trace holds no begin marker")
        off = pc_begin - marks[MARK_BEGIN]
        self.a, self.b = pc_begin, pc_end
        self.spans = [(n, s + off, e + off) for n, s, e in spans]
        devs = raw["devices"]
        if not devs:
            raise RuntimeError("trace holds no device operations")
        self.ops = {d: [(n, s + off, e + off) for n, s, e in evs]
                    for d, evs in devs.items()}
        self.busy = {d: xtrace.Busy([(s, e) for _, s, e in evs])
                     for d, evs in self.ops.items()}
        first = sorted(self.busy)[0]
        self.chip = self.busy[first]
        self.chip_ops = self.ops[first]

    @property
    def window_s(self) -> float:
        return self.b - self.a

    def busy_s(self) -> float:
        """Device-busy seconds in the traced window, mean over chips."""
        return float(np.mean([b.within(self.a, self.b)
                              for b in self.busy.values()]))

    def inside(self, records):
        """Stamped (start, end, ...) records that lie in the traced window."""
        return [r for r in records if r[0] >= self.a and r[1] <= self.b]

    def device_s(self, records) -> float:
        return sum(self.chip.within(r[0], r[1]) for r in records)

    def breakdown(self) -> Dict:
        return {"device_ops": [[n, s] for n, s in xtrace.top_ops(
                    self.chip_ops, self.a, self.b)],
                "idle_gaps": [[n, s] for n, s in xtrace.named_gaps(
                    self.chip, self.spans, self.a, self.b)]}


MARK_BEGIN, MARK_END = "bench.trace_begin", "bench.trace_end"
HOST_MARGIN_S = 2.0
TRACE_SECONDS = 6.0     # a traced run profiles the window's last seconds


class Tracer:
    """Profiles [begin_at, end of window] of the wall clock: started and
    stopped from the workload's poll, with a marker span at each end."""

    def __init__(self, outdir: Path, begin_at: float, end_at: float):
        self.outdir, self.begin_at, self.end_at = outdir, begin_at, end_at
        self.pc_call = self.pc_begin = self.pc_end = None

    def tick(self, now: float):
        import jax
        if self.pc_begin is None and now >= self.begin_at:
            self.pc_call = now
            shutil.rmtree(self.outdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.outdir), profiler_options=opts)
            with jax.profiler.TraceAnnotation(MARK_BEGIN):
                self.pc_begin = time.perf_counter()
        elif (self.pc_begin is not None and self.pc_end is None
              and now >= self.end_at):
            with jax.profiler.TraceAnnotation(MARK_END):
                self.pc_end = time.perf_counter()
            jax.profiler.stop_trace()

    def view(self) -> TraceView:
        if self.pc_end is None:
            raise RuntimeError("the window closed before the trace ended")
        files = sorted(self.outdir.rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError(f"no trace written under {self.outdir}")
        return TraceView(xtrace.load(str(files[-1])), self.pc_begin,
                         self.pc_end)


def load_reader(name: str):
    """The reader of metric ``name``: ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def capacity_for(traffic: Dict) -> int:
    prompt = traffic["prompt"]
    return int(grid_lengths(prompt, int(prompt["grid"]))[-1]
               + traffic["output"]["max"])


def build_cluster(cfg, params, deployment: Dict, capacity: int, recorder):
    """The cell's deployment from the program's own engines and policies."""
    from repro.serving import policies
    from repro.serving.backends import make_engine
    from repro.serving.cluster import Cluster
    pools = {}
    for eid, (role, spec) in enumerate(deployment["pools"].items()):
        kw = {"slots": int(spec["slots"]), "capacity": capacity,
              "chunk_size": int(spec.get("chunk", 0)),
              "block_size": int(deployment["block_size"])}
        if "pool_blocks" in spec:
            kw["pool_blocks"] = int(spec["pool_blocks"])
        pools[role] = [make_engine("real", eid, cfg, params, **kw)]

    def policy(key):
        p = deployment[key]
        return getattr(policies, p["name"])(*p.get("args", []))
    return Cluster(pools, scheduler=policy("scheduler"),
                   router=policy("router"), rate_matcher=None,
                   sanitize=False, recorder=recorder)


def decode_window(pos: int, block: int, nb_max: int) -> int:
    """The program's decode attention window (blocks) at position pos:
    the smallest power of two of blocks past pos, capped at capacity."""
    nb = 1
    while nb * block <= pos:
        nb *= 2
    return min(nb, nb_max)


def warmup_requests(traffic: Dict, block: int, capacity: int):
    """(prompt length, output length) pairs that, served one at a time,
    reach every prompt length and every decode window of the traffic.

    A window that only decoding reaches is warmed by one prompt on the
    grid that lands in it, longer than the traffic's: one prefill instead
    of a decode up to it. Where no such prompt fits, the longest prompt
    below the window decodes up to it."""
    grid = int(traffic["prompt"]["grid"])
    isls = grid_lengths(traffic["prompt"], grid)
    max_osl = int(traffic["output"]["max"])
    nb_max = -(-capacity // block)
    need = {decode_window(p, block, nb_max)
            for p in range(min(isls), max(isls) + max_osl - 1)}
    jobs = [(isl, 2) for isl in isls]
    need -= {decode_window(isl, block, nb_max) for isl in isls}
    for w in sorted(need):
        p0 = next(p for p in range(min(isls), capacity)
                  if decode_window(p, block, nb_max) == w)
        isl = -(-p0 // grid) * grid
        if decode_window(isl, block, nb_max) != w or isl + 2 > capacity:
            isl = max(i for i in isls if i <= p0)
        jobs.append((isl, max(p0 - isl, 0) + 2))
    return jobs


def warm_up(cluster, jobs, vocab: int, seed: int):
    from repro.serving.request import Request
    from repro.workloads.base import StaticWorkload
    rng = rng_for(seed, 4)
    for i, (isl, osl) in enumerate(jobs):
        req = Request(rid=i, prompt=rng.integers(0, vocab, isl,
                                                 dtype=np.int32), osl=osl)
        cluster.serve(StaticWorkload([req]))
        if len(req.output) != osl:
            raise RuntimeError(f"warm-up request ({isl}, {osl}) served "
                               f"{len(req.output)} tokens")


def check_served(params, family, dims, done: Dict, limits: Dict, seed: int,
                 *, control: bool = False):
    """Compare a seeded sample of the finished requests, the longest among
    them, with the family's reference. Returns the numbers compared for
    the program and, with ``control``, the same numbers for the control
    put in its place: at the same positions, the token the control puts
    first."""
    reqs = sorted(done.values(), key=lambda r: r.rid)
    short = sum(len(r.output) != r.osl for r in reqs)
    if not reqs:
        empty = {"requests_checked": 0, "short_outputs": short}
        return empty, (dict(empty) if control else None)
    longest = max(reqs, key=lambda r: (len(r.output), r.isl, -r.rid))
    rest = [r for r in reqs if r is not longest]
    k = min(int(limits["sample"]) - 1, len(rest))
    pick = rng_for(seed, 3).choice(len(rest), size=k, replace=False)
    sample = [longest] + [rest[i] for i in sorted(pick)]
    gaps, ctl = [], []
    for r in sample:
        g, c = family.served_gaps(params, dims, r.prompt, r.output,
                                  control=control)
        gaps.append(g)
        ctl.append(c)

    def numbers(parts, short_outputs):
        allg = np.concatenate(parts)
        return {"requests_checked": len(sample),
                "served_tokens_checked": int(allg.size),
                "max_logit_gap": float(allg.max()),
                "mean_logit_gap": float(allg.mean()),
                "exact_share": float((allg == 0).mean()),
                "short_outputs": short_outputs}
    # the control reads a gap at every served position: it is never short
    return numbers(gaps, short), (numbers(ctl, 0) if control else None)


def limits_of(limits: Dict) -> Dict:
    """The limit of each number compared."""
    lim = {k: float(limits[k]) for k in ("max_logit_gap", "mean_logit_gap")
           if k in limits}
    lim["short_outputs"] = 0
    return lim


def passes(checked: Dict, lim: Dict) -> bool:
    return checked["requests_checked"] > 0 and all(
        checked[k] <= v for k, v in lim.items())


class Rig:
    """A cell's program, set up and warm: weights, engines, cluster."""

    def __init__(self, conf: Dict, traffic: Dict, seed: int, trace: bool,
                 log=print):
        import jax
        from repro.models import transformer as T
        self.family = family = family_of(conf)
        self.dims = dims = family.dims(conf)
        cfg = family.program_config(conf, dims)
        self.params = family.make_params(dims, seed, conf["torch_dtype"])
        want = jax.tree.map(lambda a: (a.shape, a.dtype),
                            T.abstract_params(cfg))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), self.params)
        if want != got:
            raise RuntimeError("the benchmark's weight tree differs from "
                               "the program's parameter layout")
        dep = traffic["deployment"]
        capacity = capacity_for(traffic)
        self.rec = WallRecorder(WallStamps())
        self.cluster = build_cluster(cfg, self.params, dep, capacity,
                                     self.rec)
        instrument(self.cluster, self.rec, trace=trace)
        jobs = warmup_requests(traffic, int(dep["block_size"]), capacity)
        warm_up(self.cluster, jobs, dims.vocab, seed)
        log(f"warm-up: {len(jobs)} requests (prompt, output): {jobs}")

    def measure(self, traffic: Dict, seconds: float, seed: int,
                tracer: Optional[Tracer] = None, log=print):
        """One window of the traffic. Returns (stamps, t0, compilations)."""
        from repro.serving.request import Request
        stamps = WallStamps()
        self.rec.stamps = stamps
        work = WallPaced(traffic, seconds, seed, self.dims.vocab, stamps,
                         Request)
        if tracer is not None:
            work.tick = tracer.tick
        gc.collect()
        gc.disable()
        compiles = CompileClock()
        t0 = work.start()
        if tracer is not None:
            tracer.begin_at = t0 + max(seconds - TRACE_SECONDS, 0.0)
            tracer.end_at = t0 + seconds
        try:
            self.cluster.serve(work)
        except WindowClosed:
            pass
        finally:
            gc.enable()
        compiles.live = False
        if tracer is not None:
            tracer.tick(float("inf"))   # the program drained before the end
        log(f"window: {compiles.events} compilations during the window and "
            f"its drain ({compiles.secs:.3f} s)")
        return stamps, t0, compiles.events

    def free_program(self):
        """Drop the program's state, keeping the weights for the reference."""
        for eng in self.cluster.engines():
            for attr in ("pool", "cache"):
                if getattr(eng, attr, None) is not None:
                    setattr(eng, attr, None)
        self.cluster = self.rec = None
        gc.collect()


def read_metrics(names, cell: Dict, w: Window) -> Dict:
    out = {}
    for m in names:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        v = load_reader(m["name"])(w)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_cell(*, cell: Dict, spec: Dict, conf: Dict, traffic: Dict,
             limits: Dict, seed: int, seconds: float, trace: bool,
             peaks: Dict, t_start: float, device=None, out_dir: Path,
             control: bool = False, log=print) -> Dict:
    """One run of one cell. Returns the result line's fields. With
    ``control`` the control stands in the program's place for ``correct``
    and ``checked``, and the program's own verdict is under ``program``."""
    rig = Rig(conf, traffic, seed, trace, log)
    tracer = Tracer(out_dir / "trace", 0.0, 0.0) if trace else None
    stamps, t0, compiles = rig.measure(traffic, seconds, seed, tracer, log)
    w = Window(rig.family, rig.dims, peaks, stamps, t0, seconds,
               t0 - t_start)
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use", 0) \
        if device is not None else 0
    if tracer is not None:
        w.trace = tracer.view()
        w.host_end = min(w.t_end, tracer.pc_call - HOST_MARGIN_S)
    metrics = read_metrics(spec["per_layer"] if trace else spec["end_to_end"],
                           cell, w)
    due = w.due_in_window()
    failed = sum(stamps.first_token(r) is None for r in due)
    dev = {"memory_peak_bytes": int(mem)}
    extra = {}
    if w.trace is not None:
        dev.update(busy_s=w.trace.busy_s(), window_s=w.trace.window_s)
        extra["breakdown"] = w.trace.breakdown()

    # the program's state goes before the reference runs beside the weights
    rig.free_program()
    prog, ctl = check_served(rig.params, rig.family, rig.dims, stamps.done,
                             limits, seed, control=control)
    lim = limits_of(limits)
    out = {"attempted": len(due), "failed": int(failed), "metrics": metrics,
           "device": dev, **extra, "limits": lim,
           "compiles_in_window": compiles, "setup_s": w.setup_s}
    if control:
        # the control in the program's place; the program's own verdict
        # beside it, for the readings the limits are set from
        out.update(correct=passes(ctl, lim), checked=ctl,
                   program={"correct": passes(prog, lim), "checked": prog})
    else:
        out.update(correct=passes(prog, lim), checked=prog)
    return out
