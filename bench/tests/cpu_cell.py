"""Runs a cell end to end on the CPU at a test size, past the chip check."""
import json
import time
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
SPEC = {"end_to_end": [{"name": n, "unit": "s"} for n in
                       ("ttft_p90_s", "itl_p99_s", "output_tokens_per_s",
                        "setup_s")],
        "per_layer": []}
# test-only stand-ins for a chip's peaks: CPU runs report no device metric
PEAKS = {"bf16_flops": 1.0, "hbm_bytes_per_s": 1.0}
# at these sizes on the CPU the program (bf16 against the f32 reference)
# reads at most 0.005 as its widest gap and 4e-5 as its mean gap; the fp8
# control in its place, over 8 requests or more, at least 0.03 and 0.0009:
# the limits sit between
LIMITS = {"sample": 4, "max_logit_gap": 0.02, "mean_logit_gap": 0.0005}


def run(config: str, traffic: str, *, seed: int = 5, seconds: float = 1.5,
        control: bool = False, sample: int = 4, family: str = "",
        tmp: Path = Path("/nonexistent")):
    """One run of test configuration ``config`` under test traffic
    ``traffic``; ``family`` names another family for the configuration."""
    from yardstick.cell import run_cell
    conf = json.loads((DATA / f"{config}.json").read_text())
    if family:
        conf["family"] = family
    traf = json.loads((DATA / f"{traffic}.json").read_text())
    return run_cell(cell={"name": f"{config}.{traffic}"}, spec=SPEC,
                    conf=conf, traffic=traf,
                    limits=dict(LIMITS, sample=sample), seed=seed,
                    seconds=seconds, trace=False, peaks=PEAKS,
                    t_start=time.perf_counter(), out_dir=tmp,
                    control=control, log=lambda m: None)
