"""``mla_decode_attn_roofline`` and ``moe_decode_roofline`` on a small
synthetic trace with known answers, with the DeepSeek-V3 family's counts
of the benchmark's share; and both read nothing without their marks or
on a family that has no latent attention or held experts."""
import json
import types
from pathlib import Path

import pytest

from yardstick import program_trace
from yardstick.cell import load_reader
from yardstick.family import load_family
from yardstick.program_trace import ProgramTrace

BENCH = Path(__file__).resolve().parents[1]
FAM = load_family("deepseek_v3")
DIMS = FAM.dims(json.loads((BENCH / "configs" / "deepseek-v3-ep32-l7.json")
                           .read_text()))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
STEP = "jit(decode_step_paged)/while/body/closed_call/"
COUNTERS = {"window_blocks": 320, "slots": 128, "live_tokens": 90000,
            "expert_tokens": 120, "experts_touched": 31, "moe_tokens": 512}
SPANS = [("serve.decode", 1.0, 2.0, COUNTERS),
         ("serve.decode", 3.0, 4.0, COUNTERS),
         ("serve.prefill", 5.0, 6.0, {})]
OPS = [(1.10, 1.11, STEP + "attention/pallas_call"),
       (1.20, 1.21, STEP + "mlp/moe/dot_general"),
       (1.30, 1.40, STEP + "mlp/dot_general"),
       (3.10, 3.11, STEP + "attention/pallas_call"),
       (3.20, 3.21, STEP + "mlp/moe/dot_general"),
       (5.10, 5.90, "jit(prefill_chunked_paged)/while/body/mlp/moe/dot")]


def window(family=FAM, dims=DIMS):
    return types.SimpleNamespace(trace=types.SimpleNamespace(a=0.0, b=10.0),
                                 family=family, dims=dims, peaks=PEAKS)


def test_latent_attention_roofline(monkeypatch):
    monkeypatch.setattr(program_trace, "of",
                        lambda w: ProgramTrace(SPANS, OPS, 0.0, 10.0, 8))
    live = 2 * COUNTERS["live_tokens"]
    flops = 7 * 2 * 128 * (576 + 512) * live        # 1.95 MFLOP a key
    nbytes = 7 * 576 * 2 * live                     # 8,064 B a key
    least = max(flops / 197e12, nbytes / 819e9)
    got = load_reader("mla_decode_attn_roofline")(window())
    assert got == pytest.approx(100 * least / 0.02)


def test_moe_roofline(monkeypatch):
    monkeypatch.setattr(program_trace, "of",
                        lambda w: ProgramTrace(SPANS, OPS, 0.0, 10.0, 8))
    expert = 3 * 7168 * 2048                        # one expert's weights
    flops = (2 * expert * 2 * COUNTERS["expert_tokens"]
             + 2 * COUNTERS["moe_tokens"] * (2 * 7168 * 256 + 2 * expert))
    nbytes = 2 * (2 * COUNTERS["experts_touched"] * expert
                  + 2 * 4 * (expert + 2 * 7169 * 256))
    least = max(flops / 197e12, nbytes / 819e9)
    got = load_reader("moe_decode_roofline")(window())
    assert got == pytest.approx(100 * least / 0.02)


@pytest.mark.parametrize("name", ["mla_decode_attn_roofline",
                                  "moe_decode_roofline"])
def test_reads_nothing_without_its_marks(name, monkeypatch):
    reader = load_reader(name)
    monkeypatch.setattr(program_trace, "of", lambda w: None)
    assert reader(window()) is None
    # spans without the expert counters, ops without the scopes
    bare = [(n, s, e, {k: v for k, v in c.items() if "expert" not in k})
            for n, s, e, c in SPANS]
    unscoped = [(s, e, STEP + "pallas_call") for s, e, _ in OPS]
    monkeypatch.setattr(program_trace, "of", lambda w: ProgramTrace(
        bare, unscoped, 0.0, 10.0, 8))
    assert reader(window()) is None
    # a family with neither (dense GQA)
    monkeypatch.setattr(program_trace, "of",
                        lambda w: ProgramTrace(SPANS, OPS, 0.0, 10.0, 8))
    assert reader(window(family=load_family("dense_gqa"))) is None
