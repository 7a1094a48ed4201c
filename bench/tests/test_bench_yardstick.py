"""The yardstick's own parts on the CPU: wall pacing, metrics from stamps,
the trace reduction, and operation and byte counts."""
import json
import math
import subprocess
import sys
from pathlib import Path

import pytest

from yardstick import xtrace
from yardstick.cell import (Window, capacity_for, decode_window,
                             load_reader, percentile, warmup_requests)
from yardstick.family import load_family
from yardstick.pacing import WallPaced, WallRecorder, WallStamps, WindowClosed
from yardstick.traffic import open_loop_jobs, stratified_lengths

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class Req:
    def __init__(self, rid, prompt, osl, arrival_t):
        self.rid, self.prompt, self.osl = rid, prompt, osl
        self.arrival_t = arrival_t
        self.output = []

    @property
    def isl(self):
        return len(self.prompt)


OPEN = {"arrivals": {"kind": "poisson", "rate": 4.0},
        "prompt": {"median": 64, "sigma": 0.5, "min": 16, "max": 256,
                   "grid": 16},
        "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32}}
CLOSED = dict(OPEN, arrivals={"kind": "closed", "clients": 3})


def test_open_loop_releases_nothing_before_it_is_due():
    clk = FakeClock()
    st = WallStamps()
    w = WallPaced(OPEN, 10.0, 3, 1000, st, Req, clock=clk, sleep=clk.sleep)
    t0 = w.start()
    released = []
    while True:
        for r in w.poll(0.0):
            assert st.due[r.rid] <= clk.t
            released.append(r)
        if w.next_arrival() is None:
            break
    assert len(released) == len(open_loop_jobs(OPEN, 10.0, 3)) == 40
    assert all(t0 <= st.due[r.rid] < t0 + 10.0 for r in released)
    assert all(st.released[r.rid] >= st.due[r.rid] for r in released)


def test_seeds_share_sizes_and_gaps_in_another_order():
    a, b = open_loop_jobs(OPEN, 10.0, 1), open_loop_jobs(OPEN, 10.0, 2**33)
    assert sorted(j.isl for j in a) == sorted(j.isl for j in b)
    assert [j.isl for j in a] != [j.isl for j in b]
    assert all(j.isl % 16 == 0 and 16 <= j.isl <= 256 for j in a)
    lens = stratified_lengths(OPEN["prompt"], 1000)
    assert abs(float(sorted(lens)[500]) - 64) <= 16


def test_closed_loop_holds_its_client_count():
    clk = FakeClock()
    st = WallStamps()
    w = WallPaced(CLOSED, 5.0, 9, 1000, st, Req, clock=clk, sleep=clk.sleep)
    w.start()
    live = {r.rid: r for r in w.poll(0.0)}
    assert len(live) == 3
    for _ in range(20):
        clk.t += 0.1
        done = live.pop(min(live))
        st.tokens[done.rid] = [clk.t]
        w.on_complete(done, 0.0)
        live.update({r.rid: r for r in w.poll(0.0)})
        assert len(live) == 3
    clk.t = w.t_end
    for r in live.values():
        st.tokens[r.rid] = [clk.t]
    with pytest.raises(WindowClosed):
        w.poll(0.0)


def test_ttft_and_itl_come_from_the_recorder_stamps():
    st = WallStamps()
    rec = WallRecorder(st, clock=lambda: clock[0])
    clock = [0.0]
    reqs = [Req(i, [1] * 4, 3, 0.0) for i in range(2)]
    for i, r in enumerate(reqs):
        st.due[r.rid] = st.released[r.rid] = 1.0 + i
    clock[0] = 1.5
    rec.on_prefill(reqs[0], None, 0, 0)
    clock[0] = 2.75
    rec.on_prefill(reqs[1], None, 0, 0)

    class Eng:
        slot_req = {0: reqs[0], 1: reqs[1]}
    for t in (3.0, 3.5):
        clock[0] = t
        rec.on_decode_step(Eng, 0, 0, 2)
    w = Window(None, None, {}, st, 1.0, 10.0, 7.0)
    assert load_reader("ttft_p90_s")(w) == pytest.approx(0.75)
    # gaps: 1.5 and 0.5 (request 0), 0.25 and 0.5 (request 1)
    assert load_reader("itl_p99_s")(w) == pytest.approx(1.5)
    assert load_reader("output_tokens_per_s")(w) == pytest.approx(0.6)
    assert load_reader("setup_s")(w) == 7.0
    del st.tokens[1]                    # never served: counts as infinite
    assert math.isinf(load_reader("ttft_p90_s")(w))


def test_percentile_is_nearest_rank():
    assert percentile(list(range(1, 101)), 90) == 90
    assert percentile([5.0], 99) == 5.0


def test_trace_reduction_on_a_small_synthetic_trace():
    ops = [("fusion", 0.0, 1.0), ("copy", 0.5, 1.5), ("fusion", 3.0, 4.0),
           ("dot", 6.0, 7.0)]
    busy = xtrace.Busy([(s, e) for _, s, e in ops])
    assert busy.iv == [(0.0, 1.5), (3.0, 4.0), (6.0, 7.0)]
    assert busy.within(0.0, 10.0) == pytest.approx(3.5)
    assert busy.within(1.0, 3.5) == pytest.approx(1.0)
    assert busy.gaps(0.0, 8.0) == [(1.5, 3.0), (4.0, 6.0), (7.0, 8.0)]
    assert 1 - busy.within(0.0, 8.0) / 8.0 == pytest.approx(0.5625)
    spans = [("bench.round", 0.0, 8.0), ("bench.decode", 1.0, 2.0),
             ("bench.prefill", 4.5, 5.5)]
    assert xtrace.named_gaps(busy, spans, 0.0, 8.0, n=2) == [
        ("host: bench.prefill", 2.0), ("host: bench.round", 1.5)]
    assert xtrace.top_ops(ops, 0.0, 8.0)[0] == ("fusion", 2.0)
    loop = ("%while.5 = (s32[]) while((s32[]) %t), body=%b", 0.0, 7.0)
    assert xtrace.top_ops(ops + [loop], 0.0, 8.0)[0] == ("fusion", 2.0)


def test_counts_match_hand_counts():
    flops = load_family("dense_gqa")

    def dims(name):
        return flops.dims(json.loads(
            (BENCH / "configs" / f"{name}.json").read_text()))
    q25, q3 = dims("qwen2.5-3b"), dims("qwen3-14b-l10")
    # qwen3-14b: 5120 x 128 x (40 + 40 + 8 + 8) + 3 x 5120 x 17408
    assert flops.layer_matmul_params(q3) == 62_914_560 + 267_386_880
    assert q3.kv_bytes_per_token == 10 * 2 * 8 * 128 * 2 == 40 * 1024
    assert q25.kv_bytes_per_token == 36 * 1024
    # every weight of a qwen2.5-3b decode step: 36 layers and the head
    assert flops.weight_bytes_per_step(q25) == pytest.approx(6.17e9,
                                                             rel=0.01)
    # one token, no context: 2 FLOP per weight of the products and head
    per_tok = 2 * (36 * flops.layer_matmul_params(q25) + 2048 * 151936)
    assert flops.decode_flops(q25, 0) == per_tok
    # a 3-token prompt attends over 6 (query, key) pairs per head
    attn = 36 * 4 * 16 * 128 * 6
    assert flops.prefill_flops(q25, 3) == (3 * (per_tok - 2 * 2048 * 151936)
                                           + attn + 2 * 2048 * 151936)
    b = flops.decode_step_bytes(q25, [100, 300])
    assert b == flops.weight_bytes_per_step(q25) + 400 * 36 * 1024 \
        + 2 * (2048 * 2 + 36 * 1024)


@pytest.mark.parametrize("name", ["disagg.prefill-heavy",
                                  "coloc.decode-heavy"])
def test_warm_up_reaches_every_shape_of_the_traffic(name):
    traffic = json.loads((BENCH / "traffic" / f"{name}.json").read_text())
    block = traffic["deployment"]["block_size"]
    cap = capacity_for(traffic)
    nb_max = -(-cap // block)
    grid = traffic["prompt"]["grid"]
    isls = range(-(-traffic["prompt"]["min"] // grid) * grid,
                 traffic["prompt"]["max"] + 1, grid)
    last = traffic["prompt"]["max"] + traffic["output"]["max"] - 2
    need = {decode_window(p, block, nb_max) for p in range(min(isls), last)}
    jobs = warmup_requests(traffic, block, cap)
    got = {decode_window(p, block, nb_max)
           for isl, osl in jobs for p in range(isl, isl + osl - 1)}
    assert set(isls) <= {isl for isl, _ in jobs}
    assert need <= got
    # a window past the traffic's prompts is reached by one prefill
    assert all(osl == 2 and isl % grid == 0 and isl + osl <= cap
               for isl, osl in jobs)


def test_warm_up_decodes_up_to_a_window_no_prompt_reaches():
    traffic = {"prompt": {"min": 16, "max": 32, "grid": 16},
               "output": {"max": 40}}
    # block 8, capacity 65: windows of 4, 8 and 9 blocks (the cap); a
    # 64-token prompt would land in the last, but 64 + 2 is past capacity
    jobs = warmup_requests(traffic, 8, 65)
    assert jobs == [(16, 2), (32, 2), (32, 34)]


def test_run_refuses_the_cpu_and_prints_no_result():
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        "qwen2.5-3b.disagg.prefill-heavy", "--seed", "1",
                        "--seconds", "1"], cwd=ROOT, capture_output=True,
                       text=True, env={"JAX_PLATFORMS": "cpu",
                                       "PATH": "/usr/bin:/bin"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
