"""Disaggregated serving launcher.

Runs a real (small) model through the policy-driven ``Cluster`` runtime —
role-tagged engine pools + KV handoff + IFB + pluggable scheduler/router/
rate-matcher — fed by a composable ``repro.workloads`` scenario, and
prints SLA metrics. It serves the smoke-sized config by default (the CPU
tests' size); ``--full`` serves the registry config at its published
widths, which is what one accelerator chip runs (``chip_smoke.py``). On an
accelerator the real engines clock device time on the detected chip.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --prefill-engines 1 --decode-engines 2 --requests 16 --isl 64 --osl 16 \
      --scheduler fcfs --router least-loaded --rate-matcher elastic \
      --workload poisson        # or burst / diurnal / sessions / a trace

``--backend sim`` swaps every engine for the analytic-time ``SimEngine``
(serving/simengine.py): the same policies and workload run ~100x faster on
roofline-clocked O(1) steps — no params, no jit. ``--calibrate`` first
fits the roofline scale against a short real run (persisted to
``--calibration-path``, reused by later sim runs).
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.core.hardware import CHIP_NAMES, get_chip
from repro.serving.backends import (BACKENDS, init_real_params, local_chip,
                                    make_engine)
from repro.serving.cluster import Cluster
from repro.serving.elastic import ElasticConfig, ElasticRateMatcher
from repro.serving.policies import (ChunkedPiggybackScheduler, ElasticPolicy,
                                    FCFSScheduler, FirstFitRouter,
                                    KVLocalityRouter, LeastLoadedRouter,
                                    PrefixAffinityScheduler, PriorityScheduler,
                                    RoundRobinRouter, StaticSplitRateMatcher)
from repro.serving.simengine import calibrate, load_calibration
from repro.workloads import (Burst, Diurnal, FixedShape, OpenLoopWorkload,
                             Poisson, SessionWorkload, TraceReplay)

SCHEDULERS = {
    "fcfs": lambda chunk: FCFSScheduler(),
    "priority": lambda chunk: PriorityScheduler(),
    "prefix-affinity": lambda chunk: PrefixAffinityScheduler(chunk=chunk),
}
ROUTERS = {
    "first-fit": FirstFitRouter,
    "round-robin": RoundRobinRouter,
    "least-loaded": LeastLoadedRouter,
    "kv-locality": KVLocalityRouter,
}
WORKLOADS = ("poisson", "burst", "diurnal", "sessions")


def build_workload(args, vocab: int):
    """(workload, expected_completions) from the CLI axis."""
    shape = FixedShape(args.isl, args.osl)
    if args.trace:
        w = TraceReplay(args.trace, vocab=vocab, seed=args.seed)
        if not w.requests:
            raise SystemExit(f"--trace {args.trace}: no records found")
        return w, len(w.requests)
    if args.workload == "sessions":
        w = SessionWorkload(vocab=vocab, seed=args.seed,
                            sessions=args.requests, turns=args.turns,
                            families=max(args.requests // 2, 1),
                            system_prefix_len=args.isl // 2,
                            user_isl=max(args.isl // 2, 1), osl=args.osl,
                            think_time=args.think_time)
        return w, args.requests * args.turns
    arrivals = {
        "poisson": lambda: Poisson(args.rate),
        "burst": lambda: Burst(args.requests),
        "diurnal": lambda: Diurnal(args.rate, amplitude=0.8,
                                   period=args.requests / args.rate),
    }[args.workload]()
    w = OpenLoopWorkload(arrivals, shape, vocab=vocab, seed=args.seed,
                         max_requests=args.requests, horizon_s=3600.0)
    return w, args.requests


def model_config(arch: str, full: bool):
    """The registry config at published widths, or its smoke twin."""
    return get_config(arch) if full else get_smoke_config(arch)


def main(argv=None, *, recorder=None):
    """Parse ``argv``, serve, print the metrics JSON; returns the metrics.
    ``recorder`` (a ``serving.tracing`` recorder) sees every request
    event; ``--trace-out`` attaches a ``TraceRecorder`` instead."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen2.5-3b",
                    help="architecture family")
    ap.add_argument("--full", action="store_true",
                    help="serve the published widths (default: the "
                    "smoke-sized config)")
    ap.add_argument("--backend", choices=BACKENDS, default="real",
                    help="'real' runs jit'd forwards; 'sim' runs the "
                    "analytic-time SimEngine (no params, ~100x faster)")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit (and persist) the sim roofline scale from a "
                    "short real run before serving (--backend sim)")
    ap.add_argument("--calibration-path", default=".sim_calibration.json",
                    help="JSON table of per-(model, chip) roofline scales")
    ap.add_argument("--mode", choices=["disagg", "coloc"], default="disagg")
    ap.add_argument("--scheduler", choices=sorted(SCHEDULERS), default="fcfs")
    ap.add_argument("--router", choices=sorted(ROUTERS),
                    default=None, help="default: round-robin (disagg) / "
                    "kv-locality (coloc)")
    ap.add_argument("--rate-matcher", choices=["none", "elastic", "static"],
                    default="elastic")
    ap.add_argument("--workload", choices=WORKLOADS, default="poisson",
                    help="arrival/scenario shape; 'sessions' is closed-loop "
                    "multi-turn (--requests = #conversations)")
    ap.add_argument("--trace", default=None,
                    help="JSONL trace to replay (overrides --workload)")
    ap.add_argument("--turns", type=int, default=3,
                    help="turns per conversation for --workload sessions")
    ap.add_argument("--think-time", type=float, default=0.05,
                    help="seconds between a turn's completion and the next")
    ap.add_argument("--static-alpha", type=float, default=0.5,
                    help="prefill:decode ratio for --rate-matcher static")
    ap.add_argument("--prefill-engines", type=int, default=1)
    ap.add_argument("--decode-engines", type=int, default=2)
    ap.add_argument("--prefill-chip", choices=CHIP_NAMES, default=None,
                    help="hardware class of the prefill pool (on the CPU "
                    "and the sim backend, virtual step times scale by the "
                    "chip's relative speed; default: the detected chip "
                    "with --backend real on an accelerator, else v5e)")
    ap.add_argument("--decode-chip", choices=CHIP_NAMES, default=None,
                    help="hardware class of the decode pool")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--isl", type=int, default=48)
    ap.add_argument("--osl", type=int, default=12)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--piggyback-chunk", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="attach a span TraceRecorder and write a Chrome/"
                    "Perfetto trace (one track per engine, async slices "
                    "per request, counter tracks) to this path")
    args = ap.parse_args(argv)
    if args.calibrate and args.backend != "sim":
        ap.error("--calibrate fits the sim roofline scale; pass "
                 "--backend sim with it")

    cfg = model_config(args.arch, args.full)
    # real engines on an accelerator run on the chip that is there (the
    # engine refuses any other); elsewhere the modelled default is v5e
    detected = local_chip() if args.backend == "real" else None
    default_chip = detected.name if detected else "v5e"
    args.prefill_chip = args.prefill_chip or default_chip
    args.decode_chip = args.decode_chip or default_chip
    params = None
    if args.backend == "real":          # sim serves without params
        params = init_real_params(cfg, args.seed)
    work, expected = build_workload(args, cfg.vocab_size)
    # size engines for the workload's actual shapes (traces, growing
    # multi-turn contexts), falling back to the CLI pattern
    max_ctx = getattr(work, "max_context", lambda: None)()
    capacity = (max_ctx or (args.isl + args.osl)) + 8
    if args.scheduler == "prefix-affinity" and args.piggyback_chunk <= 0:
        ap.error("--scheduler prefix-affinity needs --piggyback-chunk > 0 "
                 "(engines must be built with a PrefixCache)")
    # one chunk value feeds both the engines' PrefixCache and the scheduler
    chunk = (args.piggyback_chunk
             if args.scheduler == "prefix-affinity" else 0)

    # one calibration load per distinct chip; a chip with no persisted fit
    # runs on the raw roofline scale (announced, never silently borrowed
    # from another chip's fit)
    cal_by_chip = {}
    if args.backend == "sim":
        cal_params = None
        if args.calibrate:      # params are chip-independent: init once
            cal_params = init_real_params(cfg, args.seed)
        # only the chips this mode actually builds (coloc runs one mixed
        # pool on the prefill chip — no decode-chip engines to calibrate)
        chips_needed = ({args.prefill_chip} if args.mode == "coloc"
                       else {args.prefill_chip, args.decode_chip})
        for chip_name in sorted(chips_needed):
            if args.calibrate:
                cal = calibrate(cfg, cal_params, chip=get_chip(chip_name),
                                path=args.calibration_path, seed=args.seed)
                print(f"# calibrated {cfg.name}/{chip_name}: "
                      f"prefill x{cal.prefill_scale:.3g} "
                      f"decode x{cal.decode_scale:.3g}", file=sys.stderr)
            cal_by_chip[chip_name] = load_calibration(
                args.calibration_path, cfg.name, get_chip(chip_name))
        missing = sorted(c for c, v in cal_by_chip.items() if v is None)
        if missing:
            print(f"note: no calibration for {cfg.name} on "
                  f"{'/'.join(missing)} in {args.calibration_path}; those "
                  "engines use raw roofline scales (run --calibrate to "
                  "fit)", file=sys.stderr)

    def mk(i, chip_name):
        return make_engine(args.backend, i, cfg, params, slots=args.slots,
                           capacity=capacity, chunk_size=chunk,
                           chip=get_chip(chip_name),
                           calibration=cal_by_chip.get(chip_name))

    if args.trace_out:
        from repro.serving.tracing import TraceRecorder
        recorder = TraceRecorder()

    scheduler = SCHEDULERS[args.scheduler](chunk)
    sched_name = args.scheduler
    rate_matcher = {
        "none": lambda: None,
        "elastic": lambda: ElasticPolicy(
            ElasticRateMatcher(ElasticConfig())),
        "static": lambda: StaticSplitRateMatcher(args.static_alpha),
    }[args.rate_matcher]()

    if args.mode == "disagg":
        router = ROUTERS[args.router or "round-robin"]()
        cluster = Cluster(
            {"prefill": [mk(i, args.prefill_chip)
                         for i in range(args.prefill_engines)],
             "decode": [mk(100 + i, args.decode_chip)
                        for i in range(args.decode_engines)]},
            scheduler=scheduler, router=router, rate_matcher=rate_matcher,
            recorder=recorder)
        metrics = cluster.serve(work)
        extra = {"transfers": cluster.stats.transfers,
                 "transferred_MB": cluster.stats.transferred_bytes / 2**20,
                 "prefill_pool": len(cluster.prefill_pool),
                 "decode_pool": len(cluster.decode_pool),
                 "hardware": cluster.pool_hardware()}
        if rate_matcher is not None:
            extra["rate_matcher_moves"] = rate_matcher.moves
        router_name = args.router or "round-robin"
        rm_name = args.rate_matcher
    else:
        if args.scheduler == "fcfs" and args.piggyback_chunk:
            scheduler = ChunkedPiggybackScheduler(args.piggyback_chunk)
            sched_name = f"chunked-piggyback:{args.piggyback_chunk}"
        if args.rate_matcher != "none":
            print(f"note: --rate-matcher {args.rate_matcher} ignored in "
                  "coloc mode (a single mixed pool has no split to size)",
                  file=sys.stderr)
        router_name = args.router or "kv-locality"
        rm_name = "none"
        if args.decode_chip != args.prefill_chip:
            print("note: coloc mode runs one mixed pool; using "
                  f"--prefill-chip {args.prefill_chip} for every engine",
                  file=sys.stderr)
        router = ROUTERS[router_name]()
        cluster = Cluster(
            {"mixed": [mk(i, args.prefill_chip)
                       for i in range(args.prefill_engines
                                      + args.decode_engines)]},
            scheduler=scheduler, router=router, rate_matcher=None,
            recorder=recorder)
        metrics = cluster.serve(work)
        extra = {"transfers": cluster.stats.transfers,
                 "hardware": cluster.pool_hardware()}

    if args.trace_out:
        from repro.serving.obs import export_perfetto
        counts = export_perfetto(recorder, args.trace_out, metrics=metrics)
        print(f"# trace: {args.trace_out} ({counts['total']} events, "
              f"{counts['X']} slices, {counts['b']} request phases, "
              f"{len(recorder.dumps)} flight dumps) — load in "
              "ui.perfetto.dev or chrome://tracing", file=sys.stderr)

    print(json.dumps({"arch": cfg.name, "mode": args.mode,
                      "backend": args.backend,
                      "workload": ("trace" if args.trace else args.workload),
                      "scheduler": sched_name,
                      "router": router_name,
                      "rate_matcher": rm_name,
                      **{k: round(v, 4) for k, v in metrics.items()},
                      **extra}, indent=1, default=str))
    assert metrics["completed"] == expected
    return metrics


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
