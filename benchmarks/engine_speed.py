"""Paged vs dense KV layout on the real engine: decode-heavy churn.

Serves one seeded workload twice through a prefill->decode engine pair —
once with the paged block-pool layout (the default) and once with the
dense per-slot layout (``paged=False``) — on the same params, and
compares decode tokens per wall-second. The workload is decode-heavy
(OSL >> mean ISL) with slot churn (requests >> slots), the regime where
the paged layout wins: decode attention reads a pow2-bucketed window
covering the *active* context instead of the full slot capacity, the KV
handoff ships block-rounded true length instead of capacity-padded
tensors, and evict is a refcount decrement instead of tensor traffic.

Token streams must be byte-identical across layouts (sha256 over every
request's stream): the engine capacity is a power of two, so both
attention widths are pow2 and the masked columns contribute exact float
zeros (tests/test_paged.py pins the same property corpus-wide).

Emits ``BENCH_engine.json``:

  PYTHONPATH=src python benchmarks/engine_speed.py           # full
  PYTHONPATH=src python benchmarks/engine_speed.py --smoke   # CI

Both fleets are warmed with one full serve episode first so jit
compilation (every prompt shape and every decode window bucket) is
excluded from measured wall time.
"""
import argparse
import hashlib
import json
import sys
import time

SPEEDUP_FLOOR = 2.0


def main(argv=None):
    sys.path.insert(0, "src")
    import numpy as np

    from repro.models.config import ModelConfig
    from repro.serving.backends import init_real_params
    from repro.serving.cluster import kv_bytes
    from repro.serving.engine import Engine
    from repro.serving.request import Request

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_engine.json",
                    help="artifact path; '-' disables")
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default 24, smoke 10)")
    ap.add_argument("--osl", type=int, default=None,
                    help="decode tokens per request (default 48, smoke 16)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=1024,
                    help="slot capacity; power of two keeps the layouts "
                         "bit-identical")
    ap.add_argument("--floor", type=float, default=SPEEDUP_FLOOR,
                    help="minimum paged/dense decode tokens/s ratio")
    ap.add_argument("--smoke", action="store_true",
                    help="small workload for CI")
    args = ap.parse_args(argv)
    n = args.requests or (10 if args.smoke else 24)
    osl = args.osl or (16 if args.smoke else 48)
    assert args.capacity & (args.capacity - 1) == 0, \
        "capacity must be a power of two (bit-identity across layouts)"

    cfg = ModelConfig(name="engine-bench", family="dense", num_layers=2,
                      d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                      vocab_size=256, remat=False, logits_chunk=128,
                      dtype="float32")
    params = init_real_params(cfg, seed=0)

    # few distinct odd prompt lengths: block rounding is exercised and the
    # warm episode covers every jit shape
    isls = [24, 33, 40, 17]
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, isls[i % len(isls)])
               .astype(np.int32) for i in range(n)]

    def serve(src, dst):
        """Churn loop: prefill on one engine, continuous batching on the
        other; returns (streams, decode_wall_s, total_wall_s, payload
        bytes)."""
        streams = [None] * n
        payload_bytes = []
        queue = list(range(n))
        active = {}                       # slot -> (rid, last_token)
        decode_wall = 0.0
        t_all = time.perf_counter()
        while queue or active:
            while queue and dst.has_free_slot():
                rid = queue.pop(0)
                tok, cache = src.prefill(prompts[rid])
                payload_bytes.append(kv_bytes(cache))
                req = Request(rid=rid, prompt=prompts[rid], osl=osl)
                slot = dst.insert(req, cache)
                streams[rid] = [tok]
                active[slot] = rid
            t0 = time.perf_counter()
            out = dst.decode_step({s: streams[r][-1]
                                   for s, r in active.items()})
            decode_wall += time.perf_counter() - t0
            for s, r in list(active.items()):
                streams[r].append(out[s])
                if len(streams[r]) > osl:
                    dst.evict(s)
                    del active[s]
        total_wall = time.perf_counter() - t_all
        if dst.paged:                     # no leaked blocks after churn
            assert dst._alloc.used == 0, dst._alloc.used
        return streams, decode_wall, total_wall, payload_bytes

    def run(paged):
        # one engine pair per layout: the warm episode walks the identical
        # schedule, so every jit shape (prompt lengths, decode window
        # buckets) is compiled before the timed episode
        src = Engine(0, cfg, params, slots=2, capacity=args.capacity,
                     paged=paged)
        dst = Engine(1, cfg, params, slots=args.slots,
                     capacity=args.capacity, paged=paged)
        serve(src, dst)                   # warm: compile off-clock
        streams, dec_wall, wall, payload = serve(src, dst)
        digest = hashlib.sha256(
            b"".join(np.asarray(s, np.int32).tobytes()
                     for s in streams)).hexdigest()
        toks = sum(len(s) for s in streams)
        return {
            "decode_wall_s": round(dec_wall, 6),
            "wall_s": round(wall, 6),
            "decode_tokens_per_s": round(n * osl / dec_wall, 1),
            "tokens_per_s": round(toks / wall, 1),
            "kv_payload_bytes_mean": int(np.mean(payload)),
        }, digest

    dense, digest_d = run(paged=False)
    paged, digest_p = run(paged=True)

    speedup = paged["decode_tokens_per_s"] / dense["decode_tokens_per_s"]
    report = {
        "bench": "engine_speed",
        "smoke": bool(args.smoke),
        "model": cfg.name,
        "workload": {"requests": n, "isl": isls, "osl": osl,
                     "slots": args.slots, "capacity": args.capacity},
        "dense": dense,
        "paged": paged,
        "speedup": round(speedup, 2),
        "floor": args.floor,
        "streams_identical": digest_d == digest_p,
        "stream_sha256": digest_p,
        "payload_ratio": round(dense["kv_payload_bytes_mean"]
                               / max(paged["kv_payload_bytes_mean"], 1), 1),
    }
    print(json.dumps(report, indent=1, sort_keys=True))
    if args.out != "-":
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"# wrote {args.out}")

    assert report["streams_identical"], (
        f"paged and dense token streams diverged: {digest_p} vs {digest_d}")
    assert speedup >= args.floor, (
        f"paged decode {paged['decode_tokens_per_s']:.0f} tok/s is only "
        f"{speedup:.2f}x dense {dense['decode_tokens_per_s']:.0f} tok/s "
        f"(floor {args.floor:.1f}x)")
    print(f"# OK: paged {paged['decode_tokens_per_s']:.0f} tok/s vs dense "
          f"{dense['decode_tokens_per_s']:.0f} tok/s -> {speedup:.1f}x "
          f"(floor {args.floor:.1f}x), payload {report['payload_ratio']}x "
          f"smaller")
    return report


if __name__ == "__main__":
    sys.path.insert(0, "src")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
