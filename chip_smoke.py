"""Bring-up check: serve full-width qwen2.5-3b end to end on one TPU chip.

  python chip_smoke.py [--seed N]

One process drives the chip and starts no other. Each phase prints one
line; a failed phase exits non-zero before the verdict is printed.

  device     a TPU must be present, and its ``device_kind`` must map to a
             ``core.hardware`` chip
  kernels    each Pallas kernel, compiled for the chip, against its
             ``ref.py`` twin at real head widths (the latent paged kernel
             at DeepSeek-V3's)
  disagg     ``repro.launch.serve`` with ``--full``: 1 prefill + 2 decode
             engines serve a burst of 8 requests (ISL 512, OSL 32)
  coloc      the same workload on one mixed pool of 3 engines
  reference  the served streams against an unbatched greedy reference
             (``prefill_full`` then dense ``decode_step`` at B=1, fed
             the served tokens)
  report     compile seconds per phase, peak device memory, attention path

The last line of stdout is the verdict,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Weights are random, drawn from ``--seed``. JAX's persistent compilation
cache goes where ``repro.launch.compile_cache`` says.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from importlib import metadata
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "qwen2.5-3b"
ISL, OSL, REQUESTS = 512, 32, 8
COLOC_CHUNK = 128           # chunked-piggyback prefill chunk in coloc mode
# The served engines (batched, paged) and the B=1 dense reference round
# differently in bf16, and 36 random-weight layers amplify that. On a
# v5e the reference batched at B=8 disagrees with itself at B=1 on 38 of
# 256 tokens, by up to 0.168 logits: with random weights over a
# 151936-token vocab the top-2 gap is often smaller than that, so either
# side of such a near-tie is a correct result. A real fault (wrong KV
# block, mask or position) picks tokens whose deficit is of the order of
# the logits' spread (~4) and leaves few tokens exact; these limits sit
# well away from both.
LOGIT_TOL = 0.5
EXACT_MIN = 0.5             # share of served tokens equal to the argmax
# bf16 kernels against their f32 twins: the tolerance of
# tests/test_kernels.py (a few bf16 ulps of O(1) outputs)
BF16_TOL = 2e-2
# f32 recurrence against the sequential scan, relative to the output
# scale: room for TPU exp/matmul rounding, far below any indexing fault
WKV_TOL = 1e-3
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    raise SystemExit(1)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (persistent
    cache reads included), and persistent-cache hits, since start."""

    def __init__(self):
        from jax import monitoring
        self.secs = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            self.secs += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.secs, self.hits


def phase_device():
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU found: jax's first device is {dev.platform} "
             f"({dev.device_kind})")
    from repro.core.hardware import chip_for_device_kind
    chip = chip_for_device_kind(dev.device_kind)
    print(f"device: {dev.device_kind} x{len(devices)} -> {chip.name}; "
          f"jax {jax.__version__}, libtpu {metadata.version('libtpu')}")
    return dev, len(devices), chip


def _check(name, ok, err, tol):
    if not ok:
        fail(f"kernel {name}: max abs err {err:.3g} exceeds {tol:g}")
    return f"{name} {err:.3g}"


def phase_kernels(seed: int):
    """Each kernel once, compiled (interpret=False), at the widths the
    registry gives qwen2.5-3b (H=16, Hkv=2, dh=128, bf16), deepseek-v3
    (128 heads over 640-lane latent rows, 512 value lanes, bf16) and
    rwkv6-1.6b (32 heads of 64, f32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.decode_attention.ops import (decode_attention,
                                                    decode_attention_mla,
                                                    decode_attention_paged)
    from repro.kernels.decode_attention.ref import (
        decode_attention_mla_ref, decode_attention_paged_ref,
        decode_attention_ref)
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rwkv6.ops import wkv
    from repro.kernels.rwkv6.ref import wkv_ref

    q_cfg, r_cfg = get_config(ARCH), get_config("rwkv6-1.6b")
    H, Hkv, dh = q_cfg.num_heads, q_cfg.num_kv_heads, q_cfg.dh
    rng = np.random.default_rng(seed)

    def randn(*shape):
        return rng.standard_normal(shape, np.float32)

    def bf(a):
        return jnp.asarray(a, jnp.bfloat16)

    def f32(a):
        return np.asarray(jnp.asarray(a, jnp.float32))

    def close(name, out, ref, tol=BF16_TOL):
        out, ref = f32(out), f32(ref)
        err = float(np.max(np.abs(out - ref)))
        ok = bool(np.all(np.abs(out - ref) <= tol + tol * np.abs(ref)))
        return _check(name, ok, err, tol)

    def exact():
        return jax.default_matmul_precision("highest")

    lines = []

    # prefill: causal GQA over one ISL-long prompt
    q, k, v = (bf(randn(1, ISL, h, dh)) for h in (H, Hkv, Hkv))
    out = flash_attention(q, k, v, causal=True)
    with exact():
        ref = attention_ref(f32(q), f32(k), f32(v), causal=True)
    lines.append(close("flash_attention", out, ref))

    # dense split-KV decode: 8 sequences of ragged length in a 2048 cache
    B, Smax = 8, 2048
    lengths = rng.integers(1, Smax + 1, B).astype(np.int32)
    q = bf(randn(B, H, dh))
    kc, vc = bf(randn(B, Smax, Hkv, dh)), bf(randn(B, Smax, Hkv, dh))
    out = decode_attention(q, kc, vc, jnp.asarray(lengths))
    with exact():
        ref = decode_attention_ref(f32(q), f32(kc), f32(vc),
                                   jnp.asarray(lengths))
    lines.append(close("decode_attention", out, ref))

    # paged decode over the engine's pool layout (block_size 8), with the
    # blocks of each sequence scattered through the pool; block 0 is trash
    Bs = 8
    nbs = [-(-int(n) // Bs) for n in lengths]
    nb = max(nbs)
    N = 1 + sum(nbs)
    ids = iter(rng.permutation(np.arange(1, N)).tolist())
    tables = np.zeros((B, nb), np.int32)
    for b, n in enumerate(nbs):
        tables[b, :n] = [next(ids) for _ in range(n)]
    pool = bf(randn(N, Bs, 2 * Hkv * dh))          # a row: K heads, V heads
    out = decode_attention_paged(q, pool, jnp.asarray(tables),
                                 jnp.asarray(lengths))
    ref = decode_attention_paged_ref(f32(q), f32(pool), tables, lengths)
    lines.append(close("paged_decode", out, ref))

    # latent paged decode (MLA, absorbed) over the same tables: one
    # 640-lane row a token, its first 576 lanes used, its first 512 the
    # values; 128 heads in the latent space
    m_cfg = get_config("deepseek-v3")
    Hm, lanes, used = m_cfg.num_heads, m_cfg.latent_lanes, m_cfg.latent_dim
    pool = np.zeros((N, Bs, lanes), np.float32)
    pool[..., :used] = randn(N, Bs, used)
    qm = np.zeros((B, Hm, lanes), np.float32)
    qm[..., :used] = randn(B, Hm, used)
    qm, pool = bf(qm), bf(pool)
    scale = 1.0 / np.sqrt(used)
    out = decode_attention_mla(qm, pool, jnp.asarray(tables),
                               jnp.asarray(lengths), scale=scale,
                               value_lanes=m_cfg.kv_lora_rank)
    ref = decode_attention_mla_ref(f32(qm), f32(pool), tables, lengths,
                                   scale=scale,
                                   value_lanes=m_cfg.kv_lora_rank)
    lines.append(close("latent_decode", out, ref))

    # rwkv6 WKV recurrence: 4 chunks of 64 tokens
    Hr, Nr, S = r_cfg.num_heads, r_cfg.dh, 256
    r, kk, vv = (jnp.asarray(randn(1, S, Hr, Nr)) for _ in range(3))
    logw = -jnp.exp(jnp.asarray(randn(1, S, Hr, Nr)) - 0.5)
    u = jnp.asarray(randn(Hr, Nr)) * 0.5
    s0 = jnp.asarray(randn(1, Hr, Nr, Nr)) * 0.1
    y, s = wkv(r, kk, vv, logw, u, s0)

    def flat(a):
        return a.transpose(0, 2, 1, 3).reshape(Hr, S, Nr)
    with exact():
        y_ref, s_ref = wkv_ref(flat(r), flat(kk), flat(vv), flat(logw), u,
                               s0[0])
    y_ref = y_ref.reshape(1, Hr, S, Nr).transpose(0, 2, 1, 3)
    scale = max(float(jnp.max(jnp.abs(y_ref))), 1.0)
    err = max(float(jnp.max(jnp.abs(y - y_ref))) / scale,
              float(jnp.max(jnp.abs(s[0] - s_ref)))
              / max(float(jnp.max(jnp.abs(s_ref))), 1.0))
    lines.append(_check("wkv", err <= WKV_TOL, err, WKV_TOL) + " (relative)")
    print(f"kernels: compiled, max abs err vs ref (tol {BF16_TOL:g} bf16, "
          f"{WKV_TOL:g} f32): " + ", ".join(lines))


def phase_serve(mode: str, seed: int, chip):
    from repro.launch import serve
    from repro.serving.tracing import NullRecorder

    class Completions(NullRecorder):
        """Keeps finished requests and what the engines say of themselves."""

        enabled = True

        def __init__(self):
            self.requests, self.engines = [], []

        def on_episode_begin(self, cluster):
            self.engines = [dict(e.describe(), decode_impl=e.decode_impl)
                            for e in cluster.engines()]

        def on_complete(self, req, t):
            self.requests.append(req)

    rec = Completions()
    argv = ["--arch", ARCH, "--full", "--backend", "real", "--mode", mode,
            "--prefill-engines", "1", "--decode-engines", "2",
            "--workload", "burst", "--requests", str(REQUESTS),
            "--isl", str(ISL), "--osl", str(OSL), "--seed", str(seed)]
    if mode == "coloc":
        argv += ["--piggyback-chunk", str(COLOC_CHUNK)]
    metrics = serve.main(argv, recorder=rec)
    done = [r for r in rec.requests if len(r.output) == OSL]
    if metrics["completed"] != REQUESTS or len(done) != REQUESTS:
        fail(f"{mode}: {len(done)}/{REQUESTS} requests completed")
    for e in rec.engines:
        if e["hardware"] != chip.name or e["speed_factor"] != 1.0:
            fail(f"{mode}: engine {e['engine_id']} clocks {e['hardware']} "
                 f"x{e['speed_factor']}, not the detected {chip.name}")
    impls = sorted({e["decode_impl"] for e in rec.engines})
    cfg = serve.model_config(ARCH, full=True)
    print(f"{mode}: {cfg.name} ({cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, vocab {cfg.vocab_size}), {len(rec.engines)} "
          f"engines on {chip.name} (speed factor 1), {len(done)}/{REQUESTS} "
          f"requests x {OSL} tokens done; smoke tokens/s on the engine "
          f"clock, compile included (not a benchmark): "
          f"{metrics['tokens_per_s']}")
    return sorted(done, key=lambda r: r.rid), impls


def phase_reference(served: dict, seed: int):
    """Teacher-forced greedy reference, unbatched: ``prefill_full`` then
    dense ``decode_step`` at B=1, fed the served tokens. A served token's
    deficit is the reference's best logit minus the logit of the served
    token: 0 where they agree. Every deficit must be within LOGIT_TOL,
    and at least EXACT_MIN of all served tokens must be exact. The same
    reference run batched, against itself unbatched, shows the bf16
    noise floor those limits stand on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serving.backends import init_real_params

    cfg = get_config(ARCH)
    V = cfg.vocab_size
    params = init_real_params(cfg, seed)
    # serve.py's engine capacity: the same attention width as the engines
    cap = ISL + OSL + 8
    prefill = jax.jit(lambda p, t: T.prefill_full(p, cfg, {"tokens": t},
                                                  capacity=cap))
    decode = jax.jit(lambda p, c, t: T.decode_step(p, cfg, c, t))

    def deficits(context, logits, cache, *picks):
        """Per step, the B=1 reference's best logit minus the logit of
        each pick, feeding it ``context``: one array per pick sequence."""
        out = [[] for _ in picks]
        for i, tok in enumerate(context):
            lg = np.asarray(logits[0, :V], np.float32)
            for o, pick in zip(out, picks):
                o.append(float(lg.max() - lg[pick[i]]))
            if i + 1 < len(context):
                logits, cache = decode(params, cache,
                                       jnp.asarray([tok], jnp.int32))
        return [np.asarray(o) for o in out]

    # the noise floor: the same reference at B=8, fed the same disagg
    # tokens, against itself at B=1
    base = served["disagg"]
    logits, cache = prefill(params, jnp.asarray(np.stack(
        [r.prompt for r in base])))
    batched = []
    for i in range(OSL):
        batched.append(np.asarray(jnp.argmax(logits[:, :V], axis=-1)))
        if i + 1 < OSL:
            logits, cache = decode(params, cache, jnp.asarray(
                [r.output[i] for r in base], jnp.int32))
    batched = np.stack(batched, axis=1)                   # [B, OSL]
    del logits, cache

    prompts = {r.rid: r.prompt for r in base}
    prefilled, found, floor = {}, {}, []
    for mode, reqs in served.items():
        for row, req in enumerate(reqs):
            if not np.array_equal(prompts[req.rid], req.prompt):
                fail(f"reference: {mode} rid {req.rid} has another prompt")
            if req.rid not in prefilled:
                prefilled[req.rid] = prefill(
                    params, jnp.asarray(req.prompt)[None])
            picks = (req.output,) + ((batched[row],) if mode == "disagg"
                                     else ())
            got = deficits(req.output, *prefilled[req.rid], *picks)
            found[mode, req.rid] = got[0]
            floor += got[1:]

    parts = []
    for mode, reqs in served.items():
        rows = [found[mode, r.rid] for r in reqs]
        parts.append(f"{mode} {sum(int((d == 0).sum()) for d in rows)}/"
                     f"{OSL * len(rows)} tokens exact, "
                     f"{sum(bool((d == 0).all()) for d in rows)}/{len(rows)} "
                     "streams identical")
    every = np.concatenate(list(found.values()))
    worst, exact = float(every.max()), float((every == 0).mean())
    missed = every[every > 0]
    p50 = float(np.median(missed)) if missed.size else 0.0
    rid = served["disagg"][0].rid
    d0 = found["disagg", rid]
    prefix = int(np.argmax(d0 > 0)) if (d0 > 0).any() else OSL
    floor = np.concatenate(floor)
    print("reference (B=1, fed the served tokens): " + ", ".join(parts)
          + f"; largest deficit {worst:.3g} (tolerance {LOGIT_TOL}), "
          f"median nonzero deficit {p50:.3g}; rid {rid} exact for its "
          f"first {prefix} tokens; the reference itself at B={len(base)}: "
          f"{int((floor == 0).sum())}/{floor.size} tokens exact, largest "
          f"deficit {float(floor.max()):.3g}")
    if worst > LOGIT_TOL:
        fail(f"reference: a served token's logit is {worst:.3g} below the "
             f"reference's best (tolerance {LOGIT_TOL})")
    if exact < EXACT_MIN:
        fail(f"reference: {exact:.0%} of served tokens are exact, below "
             f"{EXACT_MIN:.0%}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}; run from a checkout")
    sys.path.insert(0, str(SRC))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no libtpu log files

    dev, count, chip = phase_device()
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock()
    compile_s, hits = {}, {}

    def timed(name, fn, *a):
        s0, h0 = clock.mark()
        out = fn(*a)
        s1, h1 = clock.mark()
        compile_s[name], hits[name] = s1 - s0, h1 - h0
        gc.collect()            # engines hold the params: free them now
        return out

    timed("kernels", phase_kernels, args.seed)
    served = {}
    for mode in ("disagg", "coloc"):
        served[mode], impls = timed(mode, phase_serve, mode, args.seed, chip)
    timed("reference", phase_reference, served, args.seed)

    peak = dev.memory_stats()["peak_bytes_in_use"]
    per_phase = ", ".join(f"{k} {v:.1f}s ({hits[k]} cache hits)"
                          for k, v in compile_s.items())
    print(f"report: compile {sum(compile_s.values()):.1f}s [{per_phase}]; "
          f"cache {cache_dir}; peak device memory {peak / 2**30:.2f} GiB; "
          f"engine attention: {'/'.join(impls)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
