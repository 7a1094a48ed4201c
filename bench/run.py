"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. ``BENCHMARK.json`` names the cell; its
configuration (``bench/configs/<config>.json``), the architecture family
that configuration names (``bench/families/<family>.py``: sizes,
weights, reference, operation counts), traffic and deployment
(``bench/traffic/<traffic>.json``), limits of the comparison
(``bench/limits/<workload>.json``) and one reader per metric
(``bench/metrics/<metric>.py``) are found by name. The system under test
is the program under ``src/``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
its per-layer ones with ``--trace 1``), ``device``, ``breakdown`` (with
``--trace 1``) and, last, ``checked``: each number compared with its
limit. The same numbers are the last lines of standard error. Without an
accelerator, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str, code: int = 1):
    print(f"bench: {msg}", file=sys.stderr)
    raise SystemExit(code)


def cell_files(name: str):
    """The cell's entry and the data it is built from, by name."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json; known: "
             f"{sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    conf = json.loads((ROOT / config["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((BENCH / "limits" / f"{name}.json").read_text())
    return spec, cell, conf, traffic, limits


def accelerator(chips: int):
    """The device to measure on: a TPU with at least ``chips`` chips."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu":
        fail("no accelerator: JAX found only the CPU", 2)
    if len(devices) < chips:
        fail(f"the cell asks for {chips} chips; JAX found {len(devices)}", 2)
    return devices


def enable_cache():
    """JAX's persistent compilation cache, where the program keeps it (a
    fixed directory in the checkout unless JAX_COMPILATION_CACHE_DIR is
    set), for every program however short its compile."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def report(result: dict):
    """Numbers compared, last on stderr; the result line, last on stdout."""
    checked, limits = result["checked"], result["limits"]
    print("served: " + ", ".join(f"{k} {v}" for k, v in checked.items()
                                 if k not in limits), file=sys.stderr)
    for k, lim in limits.items():
        print(f"check: {k} {checked.get(k)} limit {lim}", file=sys.stderr)
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checked"] = {k: {"value": checked.get(k), "limit": lim}
                       for k, lim in limits.items()}
    sys.stdout.flush()
    sys.stderr.flush()
    print(json.dumps(line))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, conf, traffic, limits = cell_files(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    devices = accelerator(int(cell["chips"]))
    from yardstick.cell import run_cell
    from yardstick.peaks import peaks_for
    dev = devices[0]
    peaks = peaks_for(dev.device_kind)
    cache = enable_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)
    log(f"device: {dev.device_kind} x{len(devices)}; compile cache {cache}")
    result = run_cell(cell=cell, spec=spec, conf=conf, traffic=traffic,
                      limits=limits, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), peaks=peaks, t_start=T_START,
                      device=dev, out_dir=ROOT / ".bench_out" / cell["name"],
                      log=log)
    log(f"compilations in the window: {result['compiles_in_window']}; "
        f"set-up {result['setup_s']:.3f} s")
    result["device"].update(platform=dev.platform, kind=dev.device_kind,
                            count=len(devices))
    report(result)


if __name__ == "__main__":
    main()
