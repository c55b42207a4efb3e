"""The hybrid Mamba-2 / attention configuration (Nemotron-H's layer
pattern): the program on the paged engine against its plain reference, at
the fixture's toy widths in float32 on the CPU (``tiny-hybrid.json``,
pattern ``M-M*-``, two groups), the harness serving the fixture, and the
configuration's hand counts.

The served tokens are held to the reference as the harness holds them: at
each served position, the gap between the reference's best logit and its
logit for the served token.  In float32 a sound path reads 0 there; the
limit of 1e-4 leaves room for a float32 near tie only."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from benchlib import spec
from benchlib.weights import make_weights

FIXTURE_FILE = "bench/tests/data/tiny-hybrid.json"
LIKE = "command-r-35b.l5.rag"
GAP = 1e-4


@pytest.fixture(scope="module")
def tiny():
    from repro.models.model import build_model

    conf = json.loads((bench_tiny.ROOT / FIXTURE_FILE).read_text())
    arch = spec.load_architecture(conf)
    cfg = arch.model_config(conf)
    w = make_weights(build_model(cfg).init, cfg.d_model, 11,
                     dtype=jnp.float32, arch_rule=arch.weight_rule)
    return conf, cfg, w, spec.load_reference(conf)


def _gaps(ref, conf, w, prompt, generated):
    gen = np.asarray(generated, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), gen[:-1]])
    rows = np.arange(gen.size) + len(prompt) - 1
    mx, _am, pk = ref.logits_summary(w, conf, seq, rows, gen, pad_to=128)
    return mx - pk


def _prompts(vocab, lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n, dtype=np.int32) for n in lengths]


def _engine(cfg, w, **kw):
    from repro.serving.engine import ServingEngine

    kw.setdefault("max_slots", 2)
    return ServingEngine(cfg, max_seq=128, params=w, page_size=16, **kw)


@pytest.mark.parametrize("impl", ["blocked", "interpret"])
def test_chunked_prefill_then_decode_matches_the_reference_logits(tiny,
                                                                  impl):
    """A 100-token prompt in 64-token chunks (the second padded to its
    bucket), then decode steps, into slot 1 of the paged pools while slot
    0 sits idle: the logits agree with the reference's full forward, and
    the idle slot's state is left as it was."""
    from repro.kernels import ops
    from repro.models.model import build_model
    from repro.serving.kv_cache import PagedKVCache

    conf, cfg, w, ref = tiny
    model = build_model(cfg)
    kv = PagedKVCache(cfg, 2, 128, page_size=16, dtype=cfg.cdtype)
    kv.pools["mamba"] = jax.tree.map(lambda a: a + 0.5, kv.pools["mamba"])
    idle = jax.tree.map(lambda a: np.asarray(a[:, 0]), kv.pools["mamba"])
    (prompt,) = _prompts(cfg.vocab_size, [100])
    assert kv.alloc(10) is not None                 # slot 0, left idle
    slot, row = kv.alloc(101)
    assert slot == 1
    kv.reset_state(slot)
    rows, logits = [], []
    ops.set_impl(impl)
    try:
        start = 0
        while start < 100:
            real = min(64, 100 - start)
            bucket = 64 if real > 32 else 32
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :real] = prompt[start:start + real]
            lg, kv.pools = model.prefill_chunk(
                w, {"tokens": jnp.asarray(toks)}, kv.pools,
                jnp.asarray([start]), jnp.asarray([start + real]),
                page_table=row[:, :8], state_slot=jnp.asarray([slot]))
            start += real
        rows.append(99)
        logits.append(np.asarray(lg[0]))
        kv.install(slot, row, 100)
        tokens = jnp.zeros((2,), jnp.int32).at[slot].set(
            int(np.argmax(logits[-1])))
        active = jnp.asarray([False, True])
        for step in range(6):
            lg, kv.pools = model.decode_paged(
                w, tokens, kv.pools, kv.page_table, kv.cache_len,
                active=active)
            kv.cache_len = jnp.where(active, kv.cache_len + 1, kv.cache_len)
            rows.append(100 + step)
            logits.append(np.asarray(lg[slot]))
            tokens = tokens.at[slot].set(int(np.argmax(logits[-1])))
    finally:
        ops.set_impl(None)
    lg = np.stack(logits).astype(np.float64)
    nxt = lg.argmax(-1)
    seq = np.concatenate([prompt, nxt[:-1]])
    chosen = np.argsort(lg, -1)[:, -2]              # the runner-up
    mx, am, pk = ref.logits_summary(w, conf, seq, np.asarray(rows), chosen,
                                    pad_to=128)
    np.testing.assert_allclose(mx, lg.max(-1), atol=2e-4)
    np.testing.assert_array_equal(am, nxt)
    np.testing.assert_allclose(pk, lg[np.arange(len(rows)), chosen],
                               atol=2e-4)
    for name, a in kv.pools["mamba"][0].items():
        np.testing.assert_array_equal(np.asarray(a[:, 0]),
                                      idle[0][name], err_msg=name)


def test_engine_serves_like_the_reference(tiny):
    """Prompts that end inside a chunk, on one, and past several, two at
    a time on the paged engine."""
    conf, cfg, w, ref = tiny
    eng = _engine(cfg, w)
    assert eng.paged and eng.recurrent and eng.prefix is None
    prompts = _prompts(cfg.vocab_size, [100, 64, 37, 90], seed=1)
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    eng.run_until_drained()
    for p, h in zip(prompts, handles):
        assert _gaps(ref, conf, w, p, h.result().generated).max() <= GAP
    st = eng.stats()
    assert st["state_resets"] == 4 and st["state_replays"] == 0
    assert st["state_bytes"] == 0                  # every slot released


def _reuse_gaps(tiny):
    conf, cfg, w, ref = tiny
    eng = _engine(cfg, w, max_slots=1)
    prompts = _prompts(cfg.vocab_size, [70, 6], seed=2)
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts]
    eng.run_until_drained()
    return [_gaps(ref, conf, w, p, h.result().generated).max()
            for p, h in zip(prompts, handles)]


def test_a_reused_slot_starts_from_zero_state(tiny):
    """Two requests through one slot: the second matches the reference,
    so the first one's state was gone."""
    assert max(_reuse_gaps(tiny)) <= GAP


def test_a_reused_slot_without_the_reset_does_not_match(tiny, monkeypatch):
    """The same with the admission's reset left out: the second request
    starts from the first one's state and leaves the reference."""
    from repro.serving.kv_cache import PagedKVCache

    monkeypatch.setattr(PagedKVCache, "reset_state", lambda self, slot: None)
    first, second = _reuse_gaps(tiny)
    assert first <= GAP
    assert second > 100 * GAP


def test_a_preempted_request_replays_from_its_start(tiny):
    """Two requests that fill a pool of 8 pages both need a ninth at once;
    the younger is requeued, its state dropped with its slot, and
    re-prefilled from its start: both match the reference."""
    conf, cfg, w, ref = tiny
    eng = _engine(cfg, w, num_pages=9)
    prompts = _prompts(cfg.vocab_size, [50, 50], seed=3)
    handles = [eng.submit(p, max_new_tokens=30) for p in prompts]
    eng.run_until_drained()
    st = eng.stats()
    assert st["state_replays"] >= 1 and st["preemptions"] >= 1
    assert st["state_resets"] == 2 + st["state_replays"]
    for p, h in zip(prompts, handles):
        r = h.result()
        assert len(r.generated) == 30
        assert _gaps(ref, conf, w, p, r.generated).max() <= GAP


def test_speculative_decoding_refuses_recurrent_state(tiny):
    _conf, cfg, w, _ref = tiny
    with pytest.raises(ValueError, match="recurrent state"):
        _engine(cfg, w, draft_cfg=cfg)


def _fixture_cell():
    cell = bench_tiny.fixture_cell(FIXTURE_FILE, LIKE)
    cell.config_dir = spec.CONFIGS_DIR     # its modules are the config's
    return cell


def test_fixture_run_is_correct():
    res, lines = bench_tiny.run_tiny(_fixture_cell(), seed=2**33 + 5)
    assert res["correct"], (res, lines)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"output_tok_s", "setup_s"} <= set(res["metrics"])


def test_fixture_control_is_not_correct():
    res, lines = bench_tiny.run_tiny(_fixture_cell(), seed=2**32 + 13,
                                     control=True)
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"], lines


def test_fixture_decode_that_leaves_the_state_unchanged_is_not_correct(
        monkeypatch):
    from repro.serving.engine import ServingEngine

    real = ServingEngine._decode_paged_fn

    def frozen(self, params, pools, page_table, tokens, cache_len, active):
        nxt, new_pools, new_len = real(self, params, pools, page_table,
                                       tokens, cache_len, active)
        return nxt, dict(new_pools, mamba=pools["mamba"]), new_len

    monkeypatch.setattr(ServingEngine, "_decode_paged_fn", frozen)
    res, lines = bench_tiny.run_tiny(_fixture_cell(), seed=2**31 + 9)
    assert not res["correct"], lines
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_nemotron_params_by_hand():
    from repro.models.model import build_model

    conf = json.loads((spec.CONFIGS_DIR
                       / "nemotron-h-47b.l11.json").read_text())
    arch = spec.load_architecture(conf)
    w = arch.work(conf)
    d, di, gn, H = 8192, 256 * 64, 8 * 256, 256
    mamba = d * (2 * di + 2 * gn + H) + (di + 2 * gn) * (4 + 1) \
        + 3 * H + di + di * d + d     # in, conv + bias, A D dt, norms, out
    mlp = 2 * d * 30720 + d
    attn = 2 * d * 64 * 128 + 2 * d * 8 * 128 + d
    total = 5 * mamba + 5 * mlp + attn + 2 * 32768 * d + d
    assert total == 5_396_668_160
    assert w.num_params() == total
    cfg = arch.model_config(conf)
    assert cfg.num_params() == total
    abstract = jax.eval_shape(build_model(cfg).init, jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(abstract)) == total
    # a slot's recurrent state: bf16 conv tails and float32 SSM states
    assert w.state_bytes() == 5 * (3 * (di + 2 * gn) * 2 + H * 64 * 256 * 4)
    assert w.state_bytes() == 84_500_480
