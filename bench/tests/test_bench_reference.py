"""The plain reference against what ``Cluster.serve`` served, at smoke
sizes on the CPU: both model families, disaggregated and colocated."""
import pytest

from cpu_cell import run


@pytest.mark.parametrize("config", ["qwen2-smoke", "qwen3-smoke"])
@pytest.mark.parametrize("traffic", ["disagg", "coloc"])
def test_served_tokens_match_reference(config, traffic):
    r = run(config, traffic)
    c = r["checked"]
    assert r["correct"], c
    assert r["compiles_in_window"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["requests_checked"] >= 2 and c["served_tokens_checked"] >= 20
    assert c["short_outputs"] == 0
    m = r["metrics"]
    assert set(m) == {"ttft_p90_s", "itl_p99_s", "output_tokens_per_s",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_fp8_control_is_not_correct(seed):
    """The control (the reference with float8 inputs to every product, put
    in the program's place) comes out not correct, on each number
    compared, at the same positions of the same served requests; the
    program passes the same limits."""
    control_fails_each_number("disagg", seed)


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_fp8_control_is_not_correct_colocated(seed):
    """The same on the colocated deployment (chunked prefill, the KV
    round trip through insert, batched paged decode)."""
    control_fails_each_number("coloc", seed)


def control_fails_each_number(traffic, seed):
    r = run("qwen2-wide", traffic, seed=seed, seconds=3, sample=16,
            control=True)
    prog, ctl, lim = r["program"]["checked"], r["checked"], r["limits"]
    assert r["program"]["correct"], prog
    assert not r["correct"], ctl
    assert ctl["served_tokens_checked"] == prog["served_tokens_checked"]
    for k in ("max_logit_gap", "mean_logit_gap"):
        assert prog[k] <= lim[k] < ctl[k], (k, prog, ctl)
    assert ctl["exact_share"] < prog["exact_share"], ctl
