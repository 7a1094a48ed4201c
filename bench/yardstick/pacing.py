"""Wall-clock pacing and stamping around the program's public seams.

``WallPaced`` is a ``Workload`` for ``Cluster.serve``: it releases each
request once its due time has passed on ``time.perf_counter`` and, when
the cluster is idle, sleeps until the next one is due, so the cluster's
virtual clock never runs ahead of the wall. ``WallStamps`` is a recorder
for the cluster's hooks that stamps, on the same wall clock, each
request's first token (``on_prefill``), every later token
(``on_decode_step``) and its KV insert (``on_insert``). ``instrument``
wraps the engines' and scheduler's calls to time them and, when tracing,
to mark them as host spans in the profiler's trace.

Nothing here reads the program's own clock (``Cluster.now``,
``Engine.step_times``) or its metrics.
"""
from __future__ import annotations

import contextlib
import time
from collections import deque
from typing import Dict, List, Optional

from yardstick.traffic import ClosedLoopSizes, Job, Tokens, open_loop_jobs

SPAN_PREFILL, SPAN_INSERT, SPAN_DECODE, SPAN_ROUND = (
    "bench.prefill", "bench.insert", "bench.decode", "bench.round")


class WindowClosed(Exception):
    """Raised out of ``Cluster.serve`` once the window has closed and every
    request released in it has its first token."""


class WallStamps:
    """Wall-clock stamps of one window, keyed by request id."""

    def __init__(self):
        self.due: Dict[int, float] = {}
        self.released: Dict[int, float] = {}
        self.prefill_start: Dict[int, float] = {}
        self.inserted: Dict[int, float] = {}
        self.tokens: Dict[int, List[float]] = {}    # first token, then each
        self.inserts: List[tuple] = []              # (start, end)
        self.prefills: List[tuple] = []             # (start, end, isl)
        self.decodes: List[tuple] = []              # (start, end, contexts)
        self.done: Dict[int, object] = {}           # rid -> finished Request

    def first_token(self, rid: int) -> Optional[float]:
        ts = self.tokens.get(rid)
        return ts[0] if ts else None


class WallRecorder:
    """Cluster recorder that stamps tokens on the wall clock. It has the
    hook surface of ``serving.tracing.NullRecorder`` and is enabled."""

    enabled = True
    flight = None

    def __init__(self, stamps: WallStamps, clock=time.perf_counter):
        self.stamps = stamps
        self.clock = clock

    def on_prefill(self, req, eng, t0, t1):
        self.stamps.tokens[req.rid] = [self.clock()]

    def on_decode_step(self, eng, t0, t1, batch):
        now = self.clock()
        tokens = self.stamps.tokens
        for req in eng.slot_req.values():
            tokens[req.rid].append(now)

    def on_insert(self, req, eng, src, t, nbytes):
        self.stamps.inserted[req.rid] = self.clock()

    def on_complete(self, req, t):
        self.stamps.done[req.rid] = req

    def __getattr__(self, name):
        if name.startswith("on_"):
            return _ignore
        raise AttributeError(name)


def _ignore(*_a, **_k):
    return None


class WallPaced:
    """Open- or closed-loop traffic released on the wall clock.

    ``start()`` opens the window; ``poll`` releases the requests due by
    now, stamped with the cluster's current virtual time as their
    ``arrival_t`` so that the cluster admits them at once. After the
    window's end no request is released, and ``poll`` raises
    ``WindowClosed`` once every released request has its first token (or
    ``drain_s`` has passed)."""

    def __init__(self, traffic: Dict, seconds: float, seed: int, vocab: int,
                 stamps: WallStamps, request_cls, *, drain_s: float = 60.0,
                 clock=time.perf_counter, sleep=time.sleep):
        self.seconds = float(seconds)
        self.stamps = stamps
        self.request_cls = request_cls
        self.drain_s = drain_s
        self.clock, self.sleep = clock, sleep
        arr = traffic["arrivals"]
        self.closed = arr["kind"] == "closed"
        if not self.closed and arr["kind"] != "poisson":
            raise ValueError(f"unknown arrival kind {arr['kind']!r}")
        self.clients = int(arr.get("clients", 0))
        self.tokens = Tokens(vocab, seed)
        if self.closed:
            self._sizes = ClosedLoopSizes(traffic, seed)
            jobs: List[Job] = []
        else:
            jobs = open_loop_jobs(traffic, self.seconds, seed)
        self.pending = deque(jobs)
        self.t0 = self.t_end = None
        self.horizon = 0.0
        self._rid = 0
        self.tick = None        # called with the wall time at every poll

    def start(self) -> float:
        self.t0 = self.clock()
        self.t_end = self.t0 + self.seconds
        if self.closed:
            for _ in range(self.clients):
                j = self._sizes.next()
                self.pending.append(Job(0.0, j.isl, j.osl))
        return self.t0

    def _drained(self, now: float) -> bool:
        if now >= self.t_end + self.drain_s:
            return True
        first = self.stamps.tokens
        return all(rid in first for rid in self.stamps.released)

    def poll(self, horizon: float):
        self.horizon = horizon
        now = self.clock()
        if self.tick is not None:
            self.tick(now)
        if now >= self.t_end and self._drained(now):
            raise WindowClosed
        out = []
        st = self.stamps
        while self.pending and self.t0 + self.pending[0].due <= now:
            job = self.pending.popleft()
            rid = self._rid
            self._rid += 1
            st.due[rid] = self.t0 + job.due
            st.released[rid] = now
            out.append(self.request_cls(rid=rid,
                                        prompt=self.tokens.prompt(job.isl),
                                        osl=job.osl, arrival_t=horizon))
        return out

    def next_arrival(self) -> Optional[float]:
        if not self.pending:
            return None
        wait = self.t0 + self.pending[0].due - self.clock()
        if wait > 0:
            self.sleep(wait)
        return self.horizon

    def on_complete(self, req, now) -> None:
        if not self.closed:
            return
        t = self.clock()
        if t < self.t_end:
            j = self._sizes.next()
            self.pending.append(Job(t - self.t0, j.isl, j.osl))

    def exhausted(self) -> bool:
        return not self.pending and (not self.closed
                                     or self.clock() >= self.t_end)


def _span(name: str, trace: bool):
    if trace:
        import jax
        return jax.profiler.TraceAnnotation(name)
    return contextlib.nullcontext()


def instrument(cluster, rec: WallRecorder, *, trace: bool,
               clock=time.perf_counter):
    """Time the program's calls from outside: each engine's prefill,
    chunked prefill, insert and decode step, the scheduler's prefill
    admission, and the cluster's scheduling round, into ``rec.stamps``
    (read at each call, so a new set of stamps takes over at once). With
    ``trace`` each is also a host span in the profiler's trace, and
    ``insert`` waits for its scatter so that its span covers the
    handoff's device work."""
    for eng in cluster.engines():
        _wrap_engine(eng, rec, trace, clock)
    sched = cluster.scheduler
    run_prefill = sched.run_prefill

    def timed_run_prefill(cl, engine, req):
        rec.stamps.prefill_start[req.rid] = clock()
        return run_prefill(cl, engine, req)
    sched.run_prefill = timed_run_prefill
    step = cluster._step

    def round_():
        with _span(SPAN_ROUND, trace):
            return step()
    cluster._step = round_


def _wrap_engine(eng, rec: WallRecorder, trace: bool, clock):
    prefill, chunked = eng.prefill, eng.prefill_chunked
    insert, decode = eng.insert, eng.decode_step

    def timed_prefill(prompt, *a, **k):
        t = clock()
        with _span(SPAN_PREFILL, trace):
            out = prefill(prompt, *a, **k)
        rec.stamps.prefills.append((t, clock(), len(prompt)))
        return out

    def timed_chunked(prompt, *a, **k):
        t = clock()
        with _span(SPAN_PREFILL, trace):
            out = chunked(prompt, *a, **k)
        rec.stamps.prefills.append((t, clock(), len(prompt)))
        return out

    def timed_insert(req, payload):
        t = clock()
        with _span(SPAN_INSERT, trace):
            out = insert(req, payload)
            if trace:
                import jax
                jax.block_until_ready(getattr(eng, "pool", None)
                                      or getattr(eng, "cache", None))
        rec.stamps.inserts.append((t, clock()))
        return out

    def timed_decode(tokens_by_slot):
        ctx = [r.isl + len(r.output) for r in eng.slot_req.values()]
        t = clock()
        with _span(SPAN_DECODE, trace):
            out = decode(tokens_by_slot)
        rec.stamps.decodes.append((t, clock(), ctx))
        return out

    eng.prefill, eng.prefill_chunked = timed_prefill, timed_chunked
    eng.insert, eng.decode_step = timed_insert, timed_decode
