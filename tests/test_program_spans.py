"""The serving program's own measurement points, checked on the CPU.

- Every jitted serving step is a named function (its HLO module reads
  ``jit_<name>``), holds no host callback (a callback keeps JAX from
  writing the executable to the persistent compilation cache), and
  carries the named scopes a profile attributes device time by:
  ``kv_window`` (the paged KV gather), ``attention``, ``mlp``, ``head``.
- A fixed warm-up compiles exactly as many backend programs as the
  steps did before they were named and scoped.
- ``serve.*`` host spans land in the profiler's trace, nested as the
  program nests them, and ``serve.decode`` carries its counters.
- Unbound or with no profiler session, ``span`` is one shared no-op, and
  a fleet of simulated engines never imports jax.
"""
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import transformer as T
from repro.serving import tracing
from repro.serving.cluster import Cluster
from repro.serving.engine import Engine
from repro.serving.policies import (ChunkedPiggybackScheduler,
                                    KVLocalityRouter)
from repro.serving.request import Request
from repro.workloads.base import StaticWorkload

SRC = Path(__file__).resolve().parents[1] / "src"
CFG = get_smoke_config("qwen2.5-3b")
CHUNK = 16


@pytest.fixture(scope="module")
def params():
    return T.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engines(params):
    """A paged decode engine, a chunked mixed engine, a dense engine."""
    return {"paged": Engine(1, CFG, params, slots=4, capacity=64),
            "mixed": Engine(2, CFG, params, slots=4, capacity=64,
                            chunk_size=CHUNK),
            "dense": Engine(3, CFG, params, slots=4, capacity=64,
                            paged=False)}


def _lowered(engines, step):
    """One of the engines' jitted steps, lowered at a small shape."""
    paged, mixed, dense = engines["paged"], engines["mixed"], engines["dense"]
    p = paged.params
    toks = {"tokens": jnp.zeros((1, 2 * CHUNK), jnp.int32)}
    slots = jnp.zeros((paged.slots,), jnp.int32)
    tables = jnp.zeros((CFG.num_layers, paged.slots, 2), jnp.int32)
    ids = jnp.ones((CFG.num_layers, 4), jnp.int32)
    if step == "decode_step_paged":
        return paged._decode_paged.lower(p, paged.pool, tables, slots, slots)
    if step == "prefill_chunked_paged":
        tbl = jnp.ones((CFG.num_layers, 4), jnp.int32)
        return mixed._paged_chunked_fn(CHUNK).lower(p, toks, mixed.pool,
                                                    tbl, start=0)
    if step == "_prefill_payload_impl":
        return paged._prefill_payload.lower(p, toks)
    if step == "prefill_full":
        return paged._prefill.lower(p, toks)
    if step == "decode_step":
        return dense._decode.lower(p, dense.cache, slots)
    if step == "prefill_chunked":
        return dense._chunked_fn(CHUNK, False).lower(p, toks)
    if step == "scatter_blocks":
        blocks = jax.eval_shape(T.gather_blocks, paged.pool, ids)
        return paged._scatter.lower(paged.pool, ids, blocks)
    if step == "gather_blocks":
        return paged._gather.lower(paged.pool, ids)
    raise KeyError(step)


MODEL = ("attention", "mlp", "head")
STEPS = {"decode_step_paged": ("kv_window",) + MODEL,
         "prefill_chunked_paged": ("kv_window",) + MODEL,
         "_prefill_payload_impl": MODEL,
         "prefill_full": MODEL,
         "decode_step": MODEL,
         "prefill_chunked": MODEL,
         "scatter_blocks": (),
         "gather_blocks": ()}


@pytest.mark.parametrize("step", sorted(STEPS))
def test_jitted_step_is_named_scoped_and_free_of_callbacks(engines, step):
    lowered = _lowered(engines, step)
    text = lowered.as_text()
    assert text.startswith(f"module @jit_{step} "), text[:80]
    assert "callback" not in text.lower()
    # op locations name the scope path; inside a loop body it is relative
    paths = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    scopes = {part for path in paths for part in path.split("/")[:-1]}
    for scope in STEPS[step]:
        assert scope in scopes, f"{step}: no {scope!r} scope"


WARM_UP = textwrap.dedent("""
    import jax, numpy as np
    from jax import monitoring
    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.serving.cluster import Cluster
    from repro.serving.engine import Engine
    from repro.serving.policies import (ChunkedPiggybackScheduler,
                                        KVLocalityRouter)
    from repro.serving.request import Request
    from repro.workloads.base import StaticWorkload
    n = [0]
    monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: n.__setitem__(0, n[0] + (
            ev == "/jax/core/compile/backend_compile_duration")))
    cfg = get_smoke_config("qwen2.5-3b")
    p = T.init_params(cfg, jax.random.PRNGKey(0))
    disagg = Cluster({"prefill": [Engine(0, cfg, p, slots=1, capacity=64,
                                         pool_blocks=2)],
                      "decode": [Engine(1, cfg, p, slots=4, capacity=64)]},
                     sanitize=False)
    coloc = Cluster({"mixed": [Engine(0, cfg, p, slots=4, capacity=64,
                                      chunk_size=16)]},
                    scheduler=ChunkedPiggybackScheduler(16),
                    router=KVLocalityRouter(), sanitize=False)
    for cl in (disagg, coloc):
        n0 = n[0]
        cl.serve(StaticWorkload([
            Request(rid=i, osl=4, prompt=np.arange(1, 1 + isl,
                                                   dtype=np.int32))
            for i, isl in enumerate((12, 30))]))
        print(n[0] - n0)
""")


def _run(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=str(SRC), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_warm_up_compiles_as_many_programs_as_before():
    """Two requests through a fresh disaggregated and a fresh colocated
    cluster, in a fresh process. The counts were read on the program
    before its steps were named and scoped: 13 and 8."""
    assert _run(WARM_UP).split() == ["13", "8"]


INVENTORY = {
    "serve.round": None,
    "serve.select": "serve.round",
    "serve.route": "serve.round",
    "serve.tokens": "serve.round",
    "serve.prefill": "serve.round",
    "serve.prefill.blocks": "serve.prefill",
    "serve.prefill.dispatch": "serve.prefill",
    "serve.prefill.kv_out": "serve.prefill",
    "serve.prefill.sample": "serve.prefill",
    "serve.insert": "serve.round",
    "serve.insert.blocks": "serve.insert",
    "serve.insert.upload": "serve.insert",
    "serve.decode": "serve.round",
    "serve.decode.blocks": "serve.decode",
    "serve.decode.upload": "serve.decode",
    "serve.decode.dispatch": "serve.decode",
    "serve.decode.sync": "serve.decode",
}


def _requests(n, seed):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, osl=int(rng.integers(3, 8)),
                    prompt=rng.integers(0, CFG.vocab_size,
                                        int(rng.integers(8, 40)),
                                        dtype=np.int32))
            for i in range(n)]


@pytest.fixture(scope="module")
def spans(params, tmp_path_factory):
    """Every serve.* span of a traced disaggregated and colocated run:
    (name, start_ns, end_ns, counters), in start order."""
    disagg = Cluster({"prefill": [Engine(0, CFG, params, slots=1,
                                         capacity=64, pool_blocks=2)],
                      "decode": [Engine(1, CFG, params, slots=4,
                                        capacity=64)]}, sanitize=False)
    coloc = Cluster({"mixed": [Engine(0, CFG, params, slots=4, capacity=64,
                                      chunk_size=CHUNK)]},
                    scheduler=ChunkedPiggybackScheduler(CHUNK),
                    router=KVLocalityRouter(), sanitize=False)
    for cl in (disagg, coloc):      # compile outside the trace
        cl.serve(StaticWorkload(_requests(4, 1)))
    out = tmp_path_factory.mktemp("profile")
    jax.profiler.start_trace(str(out))
    try:
        for cl in (disagg, coloc):
            cl.serve(StaticWorkload(_requests(4, 1)))
    finally:
        jax.profiler.stop_trace()
    (path,) = out.rglob("*.xplane.pb")
    found = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("serve."):
                    found.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return sorted(found, key=lambda s: (s[1], -s[2]))


def test_every_span_of_the_inventory_is_recorded(spans):
    assert {s[0] for s in spans} == set(INVENTORY)


def test_children_lie_inside_their_parent(spans):
    for name, s, e, _ in spans:
        parent = INVENTORY[name]
        if parent is None:
            continue
        assert any(p[0] == parent and p[1] <= s and e <= p[2]
                   for p in spans), f"{name} at {s} outside any {parent}"


def test_decode_span_carries_its_counters(spans):
    dec = [c for n, _, _, c in spans if n == "serve.decode"]
    assert dec
    for c in dec:
        assert set(c) == {"window_blocks", "slots", "live_tokens"}
        assert c["slots"] == 4
        window = c["window_blocks"] * 8        # the engines' block size
        assert 1 <= c["live_tokens"] <= c["slots"] * window
    rounds = [c for n, _, _, c in spans if n == "serve.round"]
    assert rounds and not any(rounds)       # counters no metric reads


def test_span_is_a_shared_no_op_with_no_profiler_session():
    assert not tracing.span_enabled()
    assert tracing.span("serve.decode", slots=1) is tracing.span("serve.x")


def test_simulated_fleet_never_imports_jax():
    code = textwrap.dedent("""
        import sys
        from repro.core.paper_models import LLAMA31_8B
        from repro.serving import tracing
        from repro.serving.cluster import Cluster
        from repro.serving.request import Request
        from repro.serving.simengine import SimEngine
        from repro.workloads.base import StaticWorkload
        import numpy as np
        cl = Cluster({"prefill": [SimEngine(0, LLAMA31_8B, capacity=512)],
                      "decode": [SimEngine(1, LLAMA31_8B, capacity=512)]})
        reqs = [Request(rid=i, prompt=np.arange(64, dtype=np.int32), osl=4)
                for i in range(3)]
        cl.serve(StaticWorkload(reqs))
        assert all(len(r.output) == 4 for r in reqs)
        assert tracing.span("serve.round") is tracing.span("serve.decode")
        print(sorted(m for m in sys.modules if m.split(".")[0] == "jax"))
    """)
    assert _run(code).strip() == "[]"
