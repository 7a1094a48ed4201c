"""``decode_attn_roofline`` on small synthetic traces with known answers:
the window gather plus XLA attention, and a kernel that reads the live
blocks in place, count the same work."""
import types

import pytest

from yardstick import program_trace
from yardstick.cell import load_reader
from yardstick.program_trace import ProgramTrace

STEP = "jit(decode_step_paged)/while/body/"
SPANS = [
    ("serve.decode", 1.0, 2.0, {"window_blocks": 4, "slots": 4,
                                "live_tokens": 40}),
    ("serve.decode", 3.0, 4.0, {"window_blocks": 4, "slots": 4,
                                "live_tokens": 60}),
    ("serve.prefill", 5.0, 6.0, {}),
]
# 1000 bytes of KV a token at 1e6 bytes/s: the 100 live keys need 0.1 s
WINDOW = types.SimpleNamespace(
    trace=types.SimpleNamespace(a=0.0, b=10.0),
    dims=types.SimpleNamespace(kv_bytes_per_token=1000),
    peaks={"hbm_bytes_per_s": 1e6})
GATHER_OPS = [
    (1.1, 1.3, STEP + "kv_window/gather"),
    (1.3, 1.4, STEP + "attention/dot_general"),
    (1.4, 1.8, STEP + "mlp/dot_general"),
    (3.1, 3.3, STEP + "kv_window/gather"),
    (3.3, 3.4, STEP + "attention/fusion"),
    (5.1, 5.9, "jit(prefill_chunked_paged)/while/body/kv_window/gather"),
]
KERNEL_OPS = [
    (1.1, 1.15, STEP + "attention/pallas_call"),
    (1.4, 1.8, STEP + "mlp/dot_general"),
    (3.1, 3.15, STEP + "attention/pallas_call"),
]


@pytest.mark.parametrize("ops,expected", [
    (GATHER_OPS, 100 * 0.1 / 0.6),      # 0.1 s of 0.3 + 0.3 s
    (KERNEL_OPS, 100 * 0.1 / 0.1),      # 0.1 s of 0.05 + 0.05 s
], ids=["gather", "kernel"])
def test_decode_attn_roofline_reads_either_implementation(ops, expected,
                                                          monkeypatch):
    pt = ProgramTrace(SPANS, ops, 0.0, 10.0, block_size=8)
    monkeypatch.setattr(program_trace, "of", lambda w: pt)
    got = load_reader("decode_attn_roofline")(WINDOW)
    assert got == pytest.approx(expected)


def test_decode_attn_roofline_reports_nothing_without_its_marks(monkeypatch):
    monkeypatch.setattr(program_trace, "of", lambda w: None)
    reader = load_reader("decode_attn_roofline")
    assert reader(WINDOW) is None
    bare = ProgramTrace([], GATHER_OPS, 0.0, 10.0, block_size=8)
    monkeypatch.setattr(program_trace, "of", lambda w: bare)
    assert reader(WINDOW) is None
    unscoped = ProgramTrace(SPANS, [(1.1, 1.3, STEP + "gather")], 0.0, 10.0,
                            block_size=8)
    monkeypatch.setattr(program_trace, "of", lambda w: unscoped)
    assert reader(WINDOW) is None
