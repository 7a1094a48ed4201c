"""Readings that the limits of ``correct`` are set from, on this chip.

  python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, in one process, one run of the cell as ``run.py`` makes
it (weights from the seed, warm-up, one window of its traffic), with the
control put in the program's place: the same reference computed with
float8 inputs to every product, read at the same positions of the same
served requests (the gap of the token it puts first). One JSON line per
seed: the program's numbers and verdict, and the control's, against the
cell's limits file. The program's largest reading of a number over the
seeds is its lower reading; the control's smallest is the upper one.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

from run import BENCH, ROOT, accelerator, cell_files, enable_cache


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec, cell, conf, traffic, limits = cell_files(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    dev = accelerator(int(cell["chips"]))[0]
    enable_cache()
    from yardstick.cell import run_cell
    from yardstick.peaks import peaks_for

    def log(m):
        print(m, file=sys.stderr, flush=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = run_cell(cell=cell, spec=spec, conf=conf, traffic=traffic,
                     limits=limits, seed=seed, seconds=args.seconds,
                     trace=False, peaks=peaks_for(dev.device_kind),
                     t_start=time.perf_counter(), device=dev,
                     out_dir=ROOT / ".bench_out" / cell["name"],
                     control=True, log=log)
        print(json.dumps({
            "seed": seed, "compiles": r["compiles_in_window"],
            "limits": r["limits"],
            "program_correct": r["program"]["correct"],
            "program": r["program"]["checked"],
            "control_correct": r["correct"], "control": r["checked"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
            flush=True)
        del r
        gc.collect()


if __name__ == "__main__":
    main()
