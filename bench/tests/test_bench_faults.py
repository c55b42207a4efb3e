"""The harness with the served path broken underneath, at a tiny size on
the CPU: each fault the cells can have reads not correct."""
import pytest

import bench_tiny


def _faulty_decode(kind):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._decode_paged_fn

    def fn(self, params, pools, page_table, tokens, cache_len, active):
        nxt, new_pools, new_len = real(self, params, pools, page_table,
                                       tokens, cache_len, active)
        if kind == "token":          # a token altered where it is produced
            nxt = (nxt + 1 + (new_len % 2)) % self.cfg.vocab_size
            return nxt, new_pools, new_len
        return nxt, pools, new_len   # the KV state returned unchanged

    return fn


@pytest.mark.parametrize("kind", ["token", "state_unchanged"])
def test_broken_decode_is_not_correct(kind, monkeypatch):
    from repro.serving.engine import ServingEngine

    monkeypatch.setattr(ServingEngine, "_decode_paged_fn",
                        _faulty_decode(kind))
    cell = bench_tiny.tiny_cell("chameleon-34b.l6.chat")
    res, lines = bench_tiny.run_tiny(cell, seed=2**31 + 7)
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("kind", ["token", "state_unchanged"])
def test_broken_decode_of_the_routed_expert_fixture_is_not_correct(
        kind, monkeypatch):
    from repro.serving.engine import ServingEngine

    monkeypatch.setattr(ServingEngine, "_decode_paged_fn",
                        _faulty_decode(kind))
    cell = bench_tiny.fixture_cell("bench/tests/data/tiny-moe.json",
                                   "chameleon-34b.l6.chat")
    res, lines = bench_tiny.run_tiny(cell, seed=2**31 + 9)
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]
