"""Find the highest open-loop rate a cell sustains, on this machine's chip.

  python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \\
      --rates 2.0,2.5,3.0

One process sets the cell up once and serves one window per rate. For
each it prints one JSON line: the requests waiting for a decode slot (not
yet prefilled, or prefilled and not yet inserted) at the middle and at
the end of the window, and the end-to-end metrics. A rate is sustained
when the backlog at the end is no larger than at the middle.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys

from run import BENCH, ROOT, accelerator, cell_files, enable_cache


def backlog(stamps, t: float) -> int:
    rel = sum(1 for v in stamps.released.values() if v <= t)
    ins = sum(1 for v in stamps.inserted.values() if v <= t)
    return rel - ins


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    spec, cell, conf, traffic, _ = cell_files(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    devices = accelerator(int(cell["chips"]))
    enable_cache()
    from yardstick.cell import Rig, Window, read_metrics
    from yardstick.peaks import peaks_for
    peaks = peaks_for(devices[0].device_kind)
    rig = Rig(conf, traffic, args.seed, False,
              log=lambda m: print(m, file=sys.stderr, flush=True))
    for rate in [float(r) for r in args.rates.split(",")]:
        t = copy.deepcopy(traffic)
        t["arrivals"]["rate"] = rate
        stamps, t0, compiles = rig.measure(t, args.seconds, args.seed)
        w = Window(rig.family, rig.dims, peaks, stamps, t0, args.seconds,
                   0.0)
        m = read_metrics([e for e in spec["end_to_end"]
                          if e["name"] != "setup_s"], cell, w)
        mid = backlog(stamps, t0 + args.seconds / 2)
        end = backlog(stamps, t0 + args.seconds)
        print(json.dumps({
            "rate": rate, "due": len(w.due_in_window()),
            "backlog_mid": mid, "backlog_end": end, "sustained": end <= mid,
            "compiles": compiles,
            **{k: v["value"] for k, v in m.items()}}), flush=True)


if __name__ == "__main__":
    main()
