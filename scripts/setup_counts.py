"""Run one benchmark cell and count what its set-up compiled.

    python scripts/setup_counts.py [--root DIR] [--profile-window]
        -- --workload <cell> --seed <n> --seconds <s> --trace 0

Runs ``DIR/bench/run.py`` (default: this checkout's) in this process with
the given arguments, after registering ``jax.monitoring`` listeners.
When the window opens it snapshots, for the set-up phase: the backend
compiles (``/jax/core/compile/backend_compile_duration``, one per
executable built or read from the persistent cache), traces, lowerings,
and the persistent cache's hits and misses (a miss is counted when an
entry is written), with the seconds of each of the first three
(``*_s``), and ``import_s``, this process's start to the run's. After
the run it prints one JSON line with those and ``setup_s`` from this
process's start to the window's.

``--profile-window`` starts the JAX profiler just before the window opens
and stops it when the window and its drain end, so the cell's end-to-end
metrics are taken with every host span and device operation recorded.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import runpy  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402

EVENTS = {"/jax/core/compile/backend_compile_duration": "backend_compiles",
          "/jax/core/compile/jaxpr_trace_duration": "traces",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowerings",
          "/jax/compilation_cache/cache_hits": "cache_hits",
          "/jax/compilation_cache/cache_misses": "cache_misses"}


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=".")
    ap.add_argument("--profile-window", action="store_true")
    ap.add_argument("bench_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    root = Path(args.root).resolve()
    bench_args = [a for a in args.bench_args if a != "--"]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax import monitoring
    counts: Counter = Counter()

    def on_event(event, **_):
        if event in EVENTS:
            counts[EVENTS[event]] += 1

    def on_duration(event, secs, **_):
        if event in EVENTS:
            counts[EVENTS[event]] += 1
            counts[EVENTS[event][:-1] + "_s"] += secs
    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)

    sys.path.insert(0, str(root / "bench"))
    from yardstick import cell, pacing
    setup = {}
    start, measure = pacing.WallPaced.start, cell.Rig.measure
    trace_dir = tempfile.mkdtemp(prefix="setup_counts_")

    def start_window(self):
        if args.profile_window:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t = start(self)
        setup.update(counts, setup_s=t - T0, import_s=t_run - T0)
        return t

    def measure_window(self, *a, **k):
        try:
            return measure(self, *a, **k)
        finally:
            if args.profile_window and setup:
                jax.profiler.stop_trace()
                shutil.rmtree(trace_dir, ignore_errors=True)
    pacing.WallPaced.start = start_window
    cell.Rig.measure = measure_window

    sys.argv = [str(root / "bench" / "run.py")] + bench_args
    os.chdir(root)
    t_run = time.perf_counter()
    runpy.run_path(sys.argv[0], run_name="__main__")
    print(json.dumps({"setup": setup, "total": dict(counts)}))


if __name__ == "__main__":
    main()
