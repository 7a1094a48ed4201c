"""A run whose timed path is broken underneath must come out not correct:
one test per fault a serving cell can have."""
import pytest

from cpu_cell import run


def alter_third_token(monkeypatch):
    """Every request's third token altered where the engine produces it."""
    from repro.serving.engine import Engine
    decode = Engine.decode_step

    def altered(self, tokens_by_slot):
        out = decode(self, tokens_by_slot)
        for s in out:
            if len(self.slot_req[s].output) == 2:
                out[s] = (out[s] + 1) % self.cfg.vocab_size
        return out
    monkeypatch.setattr(Engine, "decode_step", altered)


def test_altered_token_fails(monkeypatch):
    """Every request's third token altered where the engine produces it."""
    alter_third_token(monkeypatch)
    r = run("qwen2-smoke", "disagg")
    assert not r["correct"], r["checked"]


@pytest.mark.parametrize("traffic", ["disagg", "coloc"])
def test_decode_step_that_keeps_its_state_fails(monkeypatch, traffic):
    """The decode step returns the KV pool it was given: no token's K/V is
    ever written."""
    from repro.models import transformer as T
    step = T.decode_step_paged

    def stale(params, cfg, pool, tables, pos, tokens, impl="xla"):
        logits, _, nxt = step(params, cfg, pool, tables, pos, tokens, impl)
        return logits, pool, nxt
    monkeypatch.setattr(T, "decode_step_paged", stale)
    r = run("qwen3-smoke", traffic)
    assert not r["correct"], r["checked"]


def test_handoff_left_out_fails(monkeypatch):
    """The prefill-to-decode KV handoff writes nothing into the decode
    pool (the exchange between the two engines left out)."""
    from repro.models import transformer as T
    monkeypatch.setattr(T, "scatter_blocks", lambda pool, ids, blocks: pool)
    r = run("qwen2-smoke", "disagg")
    assert not r["correct"], r["checked"]


@pytest.mark.parametrize("fault", ["altered_token", "kv_insert_left_out"])
def test_colocated_dense_gqa_faults_fail(monkeypatch, fault):
    """The same faults on the colocated deployment with QKV bias (the
    qwen2.5-3b decode-heavy cell's path): a token altered where the engine
    produces it, and each prompt's KV never written back by its insert."""
    from repro.models import transformer as T
    if fault == "altered_token":
        alter_third_token(monkeypatch)
    else:
        monkeypatch.setattr(T, "scatter_blocks",
                            lambda pool, ids, blocks: pool)
    r = run("qwen2-smoke", "coloc")
    assert not r["correct"], r["checked"]
