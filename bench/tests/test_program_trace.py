"""The readers of the program's own spans and scopes, on a small synthetic
trace with known answers; the loading of a real profile on the CPU; and
the scope paths read from a profile's protobuf."""
import types

import pytest

from yardstick import program_trace
from yardstick.cell import load_reader
from yardstick.program_trace import ProgramTrace

DECODE = "jit(decode_step_paged)/while/body/"


def _span(name, a, b, **counters):
    return (name, a, b, counters)


# window [0, 10]: one round with a colocated prefill, its insert (whose
# scatter runs on after it returns) and a decode step; a second round with
# one decode step; a decode step that ends after the window
SPANS = [
    _span("serve.round", 1.0, 2.0),
    _span("serve.prefill", 1.1, 1.4),
    _span("serve.prefill.kv_out", 1.1, 1.15),
    _span("serve.prefill.kv_out", 1.3, 1.35),
    _span("serve.insert", 1.5, 1.6),
    _span("serve.insert.upload", 1.52, 1.58),
    _span("serve.decode", 1.7, 1.9, window_blocks=4, slots=4,
          live_tokens=40),
    _span("serve.round", 3.0, 3.5),
    _span("serve.decode", 3.1, 3.3, window_blocks=2, slots=4,
          live_tokens=30),
    _span("serve.decode", 9.9, 10.5, window_blocks=2, slots=4,
          live_tokens=33),
]
OPS = [
    (1.55, 1.57, "jit(scatter_blocks)/scatter"),
    (1.60, 1.62, "jit(scatter_blocks)/copy"),
    (1.70, 1.90, ""),                           # the layer loop itself
    (1.72, 1.80, DECODE + "kv_window/gather"),
    (1.80, 1.86, DECODE + "attention/dot_general"),
    (3.12, 3.20, DECODE + "kv_window/gather"),
    (3.20, 3.26, DECODE + "mlp/dot_general"),
    (9.95, 9.99, DECODE + "kv_window/gather"),
]
# readings of the trace above, worked by hand
EXPECTED = {
    # kv_window 0.08 + 0.08 s of the busy 0.20 + 0.14 s in decode spans
    "decode_kv_window_pct": 100 * 0.16 / 0.34,
    # 40 + 30 live keys of 4 * 4 * 8 + 4 * 2 * 8 gathered
    "decode_kv_live_pct": 100 * 70 / 192,
    # (0.2 - 0.2) and (0.2 - 0.14) s
    "decode_host_ms_mean": 30.0,
    # 1.0 - (0.3 + 0.1 + 0.2) - 0.02 of scatter and 0.5 - 0.2 s
    "round_host_ms_mean": 340.0,
    # 0.1 s with 0.02 s of scatter
    "kv_handoff_host_ms_mean": 80.0,
    # 0.05 + 0.05 s out, 0.06 s back
    "coloc_kv_roundtrip_ms_mean": 160.0,
}
WINDOW = types.SimpleNamespace(trace=types.SimpleNamespace(a=0.0, b=10.0))


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_on_a_synthetic_trace(name, monkeypatch):
    pt = ProgramTrace(SPANS, OPS, 0.0, 10.0, block_size=8)
    monkeypatch.setattr(program_trace, "of", lambda w: pt)
    assert load_reader(name)(WINDOW) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_reader_reports_nothing_without_program_spans(name, monkeypatch):
    monkeypatch.setattr(program_trace, "of", lambda w: None)
    assert load_reader(name)(WINDOW) is None
    empty = ProgramTrace([], OPS, 0.0, 10.0, block_size=8)
    monkeypatch.setattr(program_trace, "of", lambda w: empty)
    assert load_reader(name)(WINDOW) is None


def test_idle_time_goes_to_the_innermost_span_over_it():
    pt = ProgramTrace(SPANS, OPS, 0.0, 10.0, block_size=8)
    # worked by hand: each span's own idle time, less its children's and
    # the chip's; "none" is [0, 1], [2, 3] and [3.5, 10] less 0.04 s busy
    assert pt.idle_by_span() == pytest.approx({
        "none": 8.46, "serve.round": 0.38 + 0.3, "serve.prefill": 0.2,
        "serve.prefill.kv_out": 0.1, "serve.insert": 0.04,
        "serve.insert.upload": 0.04, "serve.decode": 0.06})
    assert sum(pt.idle_by_span().values()) == pytest.approx(
        pt.idle_s(0.0, 10.0))
    # a span that takes no time holds no idle time
    instant = ProgramTrace(SPANS + [_span("serve.select", 2.5, 2.5)], OPS,
                           0.0, 10.0, block_size=8)
    assert instant.idle_by_span() == pytest.approx(pt.idle_by_span())


def test_device_time_by_step_scope_and_module():
    modules = [(1.55, 1.62, "jit_scatter_blocks(61)"),
               (1.70, 1.90, "jit_decode_step_paged(10)"),
               (9.90, 10.3, "jit_decode_step_paged(57)")]
    pt = ProgramTrace(SPANS, OPS, 0.0, 10.0, block_size=8, modules=modules)
    by_scope = pt.device_by_scope()
    assert set(by_scope) == {"jit(scatter_blocks)", "jit(decode_step_paged)"}
    assert by_scope["jit(scatter_blocks)"] == pytest.approx({"rest": 0.04})
    assert by_scope["jit(decode_step_paged)"] == pytest.approx(
        {"kv_window": 0.2, "attention": 0.06, "mlp": 0.06})
    assert pt.module_s() == pytest.approx(
        {"jit_scatter_blocks": 0.07, "jit_decode_step_paged": 0.3})


def _profile(out, spans: bool):
    import jax
    jax.profiler.start_trace(str(out))
    try:
        with jax.profiler.TraceAnnotation("bench.trace_begin"):
            pass
        if spans:
            with jax.profiler.TraceAnnotation("serve.decode",
                                              window_blocks=4, slots=4,
                                              live_tokens=9):
                with jax.profiler.TraceAnnotation("serve.decode.sync"):
                    pass
    finally:
        jax.profiler.stop_trace()


def test_a_profile_loads_onto_the_stamps_clock(tmp_path, monkeypatch):
    cell = "qwen2.5-3b.disagg.prefill-heavy"
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    _profile(tmp_path / cell / "trace", spans=True)
    w = types.SimpleNamespace(trace=types.SimpleNamespace(a=100.0, b=200.0))
    pt = program_trace.of(w)
    assert pt.block_size == 8
    (dec,) = pt.named("serve.decode")
    (sync,) = pt.named("serve.decode.sync")
    assert dec[3] == {"window_blocks": 4, "slots": 4, "live_tokens": 9}
    assert 100.0 <= dec[1] <= sync[1] <= sync[2] <= dec[2] < 101.0
    assert pt.inside(dec, ("serve.decode.sync",)) == [sync]
    assert program_trace.of(w) is pt            # loaded once


def test_breakdown_of_a_profile(tmp_path):
    """On the CPU no device plane is recorded: all the window is idle, and
    the leaf span's share of it is its own time."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.trace_begin"):
            pass
        with jax.profiler.TraceAnnotation("serve.decode"):
            with jax.profiler.TraceAnnotation("serve.decode.sync"):
                sum(range(10 ** 5))
        with jax.profiler.TraceAnnotation("bench.trace_end"):
            pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    out = program_trace.breakdown(str(path))
    assert out["window_s"] > 0 and out["busy_pct"] == 0.0
    assert out["modules"] == {} and out["scopes"] == {}
    assert sum(out["idle"].values()) == pytest.approx(100.0)
    assert out["leaves"] == pytest.approx(out["idle"]["serve.decode.sync"])
    assert out["leaves"] > 50


def test_a_profile_without_program_spans_gives_none(tmp_path, monkeypatch):
    monkeypatch.setattr(program_trace, "OUT", tmp_path)
    _profile(tmp_path / "qwen2.5-3b.disagg.prefill-heavy" / "trace",
             spans=False)
    w = types.SimpleNamespace(trace=types.SimpleNamespace(a=5.0, b=9.0))
    assert program_trace.of(w) is None
    assert program_trace.of(types.SimpleNamespace(trace=None)) is None


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _msg(*fields):
    """A protobuf message of (field number, int or bytes or str) pairs."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def test_op_scopes_reads_each_operations_tf_op_stat():
    """An XSpace with a device plane whose operations carry their scope
    path in the ``tf_op`` stat, as a string and as a reference to an
    interned string, beside a host plane that is skipped."""
    stat_md = [(5, _msg((1, key), (2, _msg((1, sid), (2, name)))))
               for key, sid, name in ((1, 7, "tf_op"), (2, 8, "flops"),
                                      (3, 9, "jit(f)/head/dot:"))]
    gather = _msg((1, 1), (2, "%fusion.1 = bf16[8,8]"),
                  (5, _msg((1, 8), (4, 12))),
                  (5, _msg((1, 7), (5, DECODE + "kv_window/gather:"))))
    head = _msg((1, 2), (2, "%fusion.2 = f32[8]"),
                (5, _msg((1, 7), (7, 9))))
    bare = _msg((1, 3), (2, "%copy.3 = f32[8]"))
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, _msg((2, "XLA Ops"))),
                  (4, _msg((1, 1), (2, gather))),
                  (4, _msg((1, 2), (2, head))),
                  (4, _msg((1, 3), (2, bare))), *stat_md)
    host = _msg((1, 2), (2, "/host:CPU"), (4, _msg((1, 1), (2, gather))),
                *stat_md)
    space = _msg((1, host), (1, device), (4, "localhost"))
    assert program_trace.op_scopes(space) == {
        "%fusion.1 = bf16[8,8]": DECODE + "kv_window/gather:",
        "%fusion.2 = f32[8]": "jit(f)/head/dot:"}
