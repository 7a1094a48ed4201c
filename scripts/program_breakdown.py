"""Run one benchmark cell traced and print where its traced window went,
by the program's own spans and scopes.

    python scripts/program_breakdown.py [--root DIR]
        -- --workload <cell> --seed <n> --seconds <s>

Runs ``DIR/bench/run.py`` (default: this checkout's) with ``--trace 1``
in a child process, which holds the chip, and passes its output through.
Then it reads the profile the run wrote under ``DIR/.bench_out/<cell>``
with ``bench/yardstick/program_trace.breakdown`` and prints one JSON line:
device time by HLO module and by jitted step and scope (``kv_window``,
``attention``, ``mlp``, ``head``, ``rest``) in % of the traced window, and
the chip's idle time by the innermost ``serve.*`` span over it, in % of
the idle time, with the leaf spans' sum.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=".")
    ap.add_argument("bench_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    root = Path(args.root).resolve()
    bench_args = [a for a in args.bench_args if a != "--"]
    cell = bench_args[bench_args.index("--workload") + 1]
    subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                    *bench_args, "--trace", "1"], cwd=root, check=True)
    files = sorted((root / ".bench_out" / cell / "trace").rglob(
        "*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not files:
        sys.exit(f"no profile under {root / '.bench_out' / cell}")
    os.environ["JAX_PLATFORMS"] = "cpu"     # the child has let the chip go
    sys.path.insert(0, str(root / "bench"))
    from yardstick import program_trace
    print(json.dumps(dict(program_trace.breakdown(str(files[-1])),
                          workload=cell)))


if __name__ == "__main__":
    main()
