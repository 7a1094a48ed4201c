"""The DeepSeek-V3 family (``families/deepseek_v3.py``) against its plain
reference (``reference/deepseek_v3.py``), at a smoke size on the CPU:
served logits through ``Cluster.serve`` (full prefill and the KV handoff,
chunked paged prefill and batched paged decode over the latent pool), the
fp8 control, the grouped router, and the expert-parallel shares."""
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cpu_cell import DATA, PEAKS, SPEC
from reference import deepseek_v3 as R
from yardstick.family import load_family

FAM = load_family("deepseek_v3")
SMOKE = json.loads((DATA / "deepseek-v3-smoke.json").read_text())
# At this size on the CPU, over 16 runs of 16 requests (8 per deployment,
# ~140-180 served tokens each), the program (bf16 against the f32
# reference, 8 of 32 experts held) read a mean gap of at most 0.0030 and
# the fp8 control in its place, at the same positions, at least 0.0061:
# the limit sits between. No limit on the widest gap: the
# program read up to 0.51 there and the control as little as 0.17. A
# token whose routing sits at the edge of its top-k can go the other way
# in the f32 reference, and that one token then reads a wide gap; the
# mean over enough tokens still tells rounding from a lower precision.
LIMITS = {"mean_logit_gap": 0.0045}


def run(traffic, *, seed=5, seconds=3, sample=16, control=False):
    from yardstick.cell import run_cell
    traf = json.loads((DATA / f"{traffic}.json").read_text())
    return run_cell(cell={"name": f"deepseek-v3-smoke.{traffic}"}, spec=SPEC,
                    conf=SMOKE, traffic=traf,
                    limits=dict(LIMITS, sample=sample), seed=seed,
                    seconds=seconds, trace=False, peaks=PEAKS,
                    t_start=time.perf_counter(), out_dir=DATA / "unused",
                    control=control, log=lambda m: None)


@pytest.mark.parametrize("traffic", ["disagg", "coloc"])
def test_served_tokens_match_reference(traffic):
    """disagg: full prefill, the paged handoff, batched paged decode;
    coloc: chunked paged prefill into the latent pool, the round trip
    through insert, batched paged decode."""
    r = run(traffic)
    c = r["checked"]
    assert r["correct"], c
    assert r["compiles_in_window"] == 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert c["requests_checked"] >= 8 and c["served_tokens_checked"] >= 80
    assert c["short_outputs"] == 0


@pytest.mark.parametrize("seed", [7, 8])
def test_fp8_control_is_not_correct(seed):
    """The control (the reference with float8 inputs to every product, the
    router's included) in the program's place fails each limit at the
    same positions of the same served requests; the program passes."""
    r = run("coloc", seed=seed, control=True)
    prog, ctl, lim = r["program"]["checked"], r["checked"], r["limits"]
    assert r["program"]["correct"], prog
    assert not r["correct"], ctl
    assert ctl["served_tokens_checked"] == prog["served_tokens_checked"]
    assert prog["mean_logit_gap"] <= lim["mean_logit_gap"] \
        < ctl["mean_logit_gap"], (prog, ctl)


def test_configuration_reads_the_published_keys():
    conf = json.loads((DATA.parents[1] / "configs"
                       / "deepseek-v3-ep32-l7.json").read_text())
    d = FAM.dims(conf)
    assert (d.layers, d.dense_layers, d.experts, d.held, d.held_offset,
            d.vocab) == (7, 3, 256, 8, 0, 16160)
    # one latent row of 576 lanes a layer, bf16
    assert d.kv_bytes_per_token == 7 * 576 * 2 == 8064
    # the absorbed core: 2 * 128 heads * (576 + 512) a key and layer
    assert FAM.mla_decode_attn_flops(d, 1) == 7 * 2 * 128 * 1088
    cfg = FAM.program_config(conf, d)
    assert cfg.kv_bytes_per_token() == 8064 and cfg.latent_lanes == 640
    # 4.32 G parameters: 7 x 187.1 M attention, 3 x 396.4 M dense FFN,
    # 4 x 398.2 M MoE (8 held + shared + router), 231.7 M embed and head
    assert cfg.param_count() == pytest.approx(4.3237e9, rel=1e-4)
    with pytest.raises(ValueError, match="deepseek_v3 family"):
        FAM.dims(dict(conf, scoring_func="softmax"))
    with pytest.raises(ValueError, match="does not cover"):
        FAM.dims(dict(conf, sliding_window=4096))


def _program_routing(logits, bias, cfg):
    from repro.models.moe import _sigmoid_topk
    w, ids = _sigmoid_topk(jnp.asarray(logits), jnp.asarray(bias), cfg)
    out = np.zeros(logits.shape, np.float32)
    np.put_along_axis(out, np.asarray(ids), np.asarray(w), axis=-1)
    return out


def test_router_matches_the_reference_grouped_topk():
    """The program's noaux_tc router gives the reference's weights, on
    random scores and where the group limit changes the choice."""
    from repro.models.config import MoEConfig
    d = FAM.dims(SMOKE)
    cfg = MoEConfig(num_experts=d.experts, top_k=d.top_k, d_ff_expert=16,
                    router="sigmoid", n_group=d.n_group,
                    topk_group=d.topk_group,
                    routed_scaling=d.routed_scaling)
    rng = np.random.default_rng(3)
    x = rng.standard_normal((64, 8)).astype(np.float32)
    router = rng.standard_normal((8, d.experts)).astype(np.float32)
    bias = 0.1 * rng.standard_normal(d.experts).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(R.routing(jnp.asarray(x), jnp.asarray(router),
                                    jnp.asarray(bias), d))
    got = _program_routing(x @ router, bias, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    # 4 groups of 8, 2 kept: group 2 holds the single best expert (7.0)
    # but the lowest best-two sum, so it is dropped whole
    scores = np.full((1, d.experts), -4.0, np.float32)
    scores[0, 0:2] = [3.0, 2.9]                     # group 0: 0.95 + 0.95
    scores[0, 8:10] = [2.8, 2.7]                    # group 1: 0.94 + 0.94
    scores[0, 16] = 7.0                             # group 2: 1.00 + 0.02
    zero = np.zeros(d.experts, np.float32)
    want = np.asarray(R.routing(jnp.ones((1, 1)), jnp.asarray(scores),
                                jnp.asarray(zero), d))
    got = _program_routing(scores, zero, cfg)
    assert np.argmax(scores[0]) == 16
    assert want[0, 16] == 0 and got[0, 16] == 0
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    assert (got[0] > 0).sum() == d.top_k


def test_expert_shares_sum_to_the_uncut_layer():
    """EP over 32 devices of one expert each: the held-expert parts of the
    program's MoE layer, plus the shared expert once, add up to the
    reference's whole layer (every expert held). In float32 both sides
    differ only in summation order: 1e-5."""
    from repro.configs import get_smoke_config
    from repro.models import layers as L
    from repro.models import moe as M
    from repro.models import transformer as T
    cfg = get_smoke_config("deepseek-v3").replace(dtype="float32")
    E = cfg.moe.num_experts
    p = T.init_params(cfg, jax.random.PRNGKey(4))["blocks"]
    p = jax.tree.map(lambda a: a[0], p)["moe"]
    key = jax.random.PRNGKey(5)
    p["router_bias"] = 0.1 * jax.random.normal(key, (E,))
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 24, cfg.d_model))
    parts = []
    for e in range(E):
        share = dataclasses.replace(cfg.moe, held=(e, 1),
                                    num_shared_experts=0)
        pe = dict(p, wg=p["wg"][e:e + 1], wu=p["wu"][e:e + 1],
                  wd=p["wd"][e:e + 1])
        with jax.default_matmul_precision("highest"):
            parts.append(M.moe_ffn(x, pe, share)[0])
        assert parts[-1].shape == x.shape
    with jax.default_matmul_precision("highest"):
        total = sum(parts) + L.swiglu(x, p["shared_wg"], p["shared_wu"],
                                      p["shared_wd"])
        d = dataclasses.replace(FAM.dims(SMOKE), held=E, held_offset=0,
                                experts=E)
        want = R.moe(p, x[0], d)
    np.testing.assert_allclose(np.asarray(total[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_yarn_matches_the_reference():
    """The program's YaRN frequencies, cos/sin scale and softmax scale
    against the reference's float64 ones, at the published settings:
    float32 rounding only."""
    from repro.configs import get_config
    from repro.models.layers import yarn_frequencies
    from repro.models.transformer import _mla_scale
    cfg = get_config("deepseek-v3")
    conf = json.loads((DATA.parents[1] / "configs"
                       / "deepseek-v3-ep32-l7.json").read_text())
    inv, cs, mscale = R.yarn(FAM.dims(conf))
    got, got_cs = yarn_frequencies(cfg.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    np.testing.assert_allclose(np.asarray(got), inv, rtol=1e-6)
    assert got_cs == cs == 1.0
    # mscale = 0.1 ln 40 + 1, squared on (128 + 64)^-1/2
    assert mscale == pytest.approx(0.1 * np.log(40) + 1)
    assert _mla_scale(cfg) == pytest.approx(mscale ** 2 / np.sqrt(192))
