"""BENCHMARK.json and the files it names: every cell finds its
configuration, traffic, limits and a reader for each of its metrics."""
import json
import re
from pathlib import Path

import pytest

from yardstick.family import INTERFACE, family_of

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_keys():
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_each_cell_finds_its_files(cell):
    conf = {c["name"]: c for c in SPEC["configs"]}[cell["config"]]
    assert (ROOT / conf["file"]).is_file()
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    assert traffic["arrivals"]["kind"] in ("poisson", "closed")
    limits = json.loads((BENCH / "limits" / f"{cell['name']}.json")
                        .read_text())
    gaps = {"max_logit_gap", "mean_logit_gap"} & set(limits)
    assert gaps and all(limits[k] > 0 for k in gaps)
    assert limits["sample"] >= 2
    mine = [m for k in ("end_to_end", "per_layer") for m in SPEC[k]
            if cell["name"] in m.get("workloads", [cell["name"]])]
    assert {m["name"] for m in mine} >= {"setup_s"}
    for m in mine:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("config", SPEC["configs"], ids=lambda c: c["name"])
def test_each_configuration_resolves_its_family(config):
    """The family its file names is a module of the whole interface, and
    reads the file's sizes."""
    conf = json.loads((ROOT / config["file"]).read_text())
    fam = family_of(conf)
    assert all(callable(getattr(fam, f)) for f in INTERFACE)
    d = fam.dims(conf)
    assert d.layers > 0 and d.vocab > 0 and d.kv_bytes_per_token > 0
    hash(d)
