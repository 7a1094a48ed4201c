"""The architecture-family seam (``yardstick/family.py``).

The dense family's sizes, weights, counts and reference gaps are pinned
to the values the harness read before the family seam existed; a family
written as a new file serves a cell by name alone; an unknown one fails
at set-up, naming the file to add."""
import hashlib
import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from cpu_cell import DATA, run
from yardstick import family

BENCH = Path(__file__).resolve().parents[1]
CONFIGS = {"qwen2.5-3b": BENCH / "configs" / "qwen2.5-3b.json",
           "qwen3-14b-l10": BENCH / "configs" / "qwen3-14b-l10.json",
           "qwen2-smoke": DATA / "qwen2-smoke.json",
           "qwen3-smoke": DATA / "qwen3-smoke.json"}
DENSE = family.load_family("dense_gqa")

GOLDEN_DIMS = {
    "qwen2.5-3b": dict(layers=36, d_model=2048, heads=16, kv_heads=2,
                       head_dim=128, d_ff=11008, vocab=151936,
                       rope_theta=1e6, eps=1e-6, qkv_bias=True,
                       qk_norm=False, tied=False),
    "qwen3-14b-l10": dict(layers=10, d_model=5120, heads=40, kv_heads=8,
                          head_dim=128, d_ff=17408, vocab=151936,
                          rope_theta=1e6, eps=1e-6, qkv_bias=False,
                          qk_norm=True, tied=False),
    "qwen2-smoke": dict(layers=2, d_model=64, heads=4, kv_heads=1,
                        head_dim=16, d_ff=160, vocab=128, rope_theta=1e6,
                        eps=1e-6, qkv_bias=True, qk_norm=False, tied=False),
    "qwen3-smoke": dict(layers=2, d_model=64, heads=4, kv_heads=2,
                        head_dim=16, d_ff=160, vocab=128, rope_theta=1e6,
                        eps=1e-6, qkv_bias=False, qk_norm=True, tied=False),
}
# kv_bytes_per_token; prefill_flops at 1, 128, 1536, 4096 tokens;
# decode_flops at contexts 1, 256, 2560; decode_step_bytes of the steps
# STEPS
GOLDEN_COUNTS = {
    "qwen2.5-3b": (36864,
                   [6171688960, 713337339904, 8872103772160,
                    25204094402560],
                   [6171688960, 6246891520, 6926368768],
                   [6171955200, 6186704896, 6980067328]),
    "qwen3-14b-l10": (40960,
                      [8162058240, 848818339840, 10390165258240,
                       28778256138240],
                      [8162058240, 8214282240, 8686141440],
                      [8162165760, 8178560000, 9060244480]),
    "qwen2-smoke": (128, [180736, 25214976, 856047616, 4967120896],
                    [180736, 311296, 1490944], [181632, 232960, 2991104]),
    "qwen3-smoke": (256, [188928, 26263552, 868630528, 5000675328],
                    [188928, 319488, 1499136], [189824, 292352, 5804800]),
}
STEPS = ([1], [100, 300], list(range(64, 64 + 32 * 40, 40)))
# sha256 over every leaf of make_params (path, dtype, shape, bytes)
GOLDEN_PARAMS = {
    ("qwen2-smoke", 20261019):
        "42cf6f16964659a1dfe57d05e5d73220519af7ad13f39e5c2f9ad26c5a5f2890",
    ("qwen2-smoke", 2**33 + 7):
        "d5a9f0ecd91c38e1a58d927c3a5d8dc0975ccb13a7690b23727f92a66576f756",
    ("qwen3-smoke", 20261019):
        "c9bc16e611c6a422a7d7fe683d594341bbe35ab69a2a53d4751c6e879ab37f6a",
    ("qwen3-smoke", 2**33 + 7):
        "2f0e328ed6159ff860e9d37e8d20bcfb6d7ae41c3fb65b8224b914fe890c8e57",
}
# (max_logit_gap, mean_logit_gap) of the program and of the fp8 control
# over the 41 served tokens of STATIC, seed 11
GOLDEN_GAPS = {
    "qwen2-smoke": ((0.0, 0.0), (0.06879520416259766, 0.0027950683142989874)),
    "qwen3-smoke": ((0.0, 0.0), (0.09577083587646484, 0.0024836789816617966)),
}
STATIC = [(16, 16), (48, 12), (32, 9), (16, 5), (32, 16), (48, 4)]


def conf_of(name):
    return json.loads(CONFIGS[name].read_text())


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_dims_are_the_parents(name):
    d = DENSE.dims(conf_of(name))
    assert {f: getattr(d, f) for f in GOLDEN_DIMS[name]} == GOLDEN_DIMS[name]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_dense_counts_are_the_parents(name):
    d = DENSE.dims(conf_of(name))
    kv, pre, dec, step = GOLDEN_COUNTS[name]
    assert d.kv_bytes_per_token == kv
    assert [DENSE.prefill_flops(d, s) for s in (1, 128, 1536, 4096)] == pre
    assert [DENSE.decode_flops(d, c) for c in (1, 256, 2560)] == dec
    assert [DENSE.decode_step_bytes(d, cs) for cs in STEPS] == step


def digest(tree):
    import jax
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,seed", sorted(GOLDEN_PARAMS))
def test_dense_weights_are_the_parents(name, seed):
    d = DENSE.dims(conf_of(name))
    assert digest(DENSE.make_params(d, seed, "bfloat16")) == \
        GOLDEN_PARAMS[(name, seed)]


@pytest.mark.parametrize("name", sorted(GOLDEN_GAPS))
def test_dense_reference_gaps_are_the_parents(name):
    """The CPU cell's program and control gaps over a fixed set of
    requests, served all at once through the colocated test deployment."""
    from repro.serving.request import Request
    from repro.workloads.base import StaticWorkload
    from yardstick.cell import Rig, check_served
    from yardstick.pacing import WallStamps
    from yardstick.traffic import Tokens
    seed = 11
    traffic = json.loads((DATA / "coloc.json").read_text())
    rig = Rig(conf_of(name), traffic, seed, False, log=lambda m: None)
    stamps = WallStamps()
    rig.rec.stamps = stamps
    toks = Tokens(rig.dims.vocab, seed)
    rig.cluster.serve(StaticWorkload([
        Request(rid=i, prompt=toks.prompt(isl), osl=osl)
        for i, (isl, osl) in enumerate(STATIC)]))
    rig.free_program()
    prog, ctl = check_served(rig.params, rig.family, rig.dims, stamps.done,
                             {"sample": 4}, seed, control=True)
    assert prog["served_tokens_checked"] == ctl["served_tokens_checked"] == 41
    for got, (mx, mean) in zip((prog, ctl), GOLDEN_GAPS[name]):
        assert got["max_logit_gap"] == pytest.approx(mx, rel=1e-6, abs=1e-9)
        assert got["mean_logit_gap"] == pytest.approx(mean, rel=1e-6,
                                                      abs=1e-9)


def test_a_family_added_as_a_file_serves_a_cell(tmp_path, monkeypatch):
    """A family module that exists only as a new file (here the dense
    family under another name) serves a CPU cell by its name."""
    shutil.copy(family.FAMILIES / "dense_gqa.py", tmp_path / "dense_copy.py")
    monkeypatch.setattr(family, "FAMILIES", tmp_path)
    r = run("qwen3-smoke", "coloc", family="dense_copy")
    assert r["correct"], r["checked"]
    assert r["checked"]["requests_checked"] >= 2


def test_an_unknown_family_fails_at_set_up_naming_the_file():
    with pytest.raises(ValueError, match=r"add bench/families/mla_moe\.py"):
        run("qwen2-smoke", "disagg", family="mla_moe")


@pytest.mark.parametrize("extra", [{"num_experts": 8},
                                   {"kv_lora_rank": 512},
                                   {"use_sliding_window": True},
                                   {"rope_scaling": {"type": "yarn"}}],
                         ids=["experts", "latent", "window", "rope"])
def test_dense_family_refuses_keys_it_does_not_cover(extra):
    with pytest.raises(ValueError, match="dense_gqa family"):
        DENSE.dims(dict(conf_of("qwen2.5-3b"), **extra))
