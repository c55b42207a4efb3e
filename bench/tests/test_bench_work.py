"""Operation and byte counts against hand counts, through each
configuration's architecture module."""
import json
from pathlib import Path

import pytest

import bench_tiny
from benchlib import spec, work

CONFIGS = Path(bench_tiny.BENCH) / "configs"
DENSE = spec.load_architecture({"architecture": "dense_architecture"})


def _conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def _work(name):
    conf = _conf(name)
    return spec.load_architecture(conf).work(conf)


def test_chameleon_params_by_hand():
    w = _work("chameleon-34b.l6")
    attn = 8192 * 64 * 128 * 2 + 8192 * 8 * 128 * 2       # q, o; k, v
    ffn = 3 * 8192 * 22016
    layer = attn + ffn + 2 * 8192 + 2 * 128               # 2 RMSNorms, qk-norm
    total = 6 * layer + 2 * 65536 * 8192 + 8192           # embed, head, final
    assert total == 5_226_210_816
    assert w.num_params() == total
    assert DENSE.layer_matmul_params(w.shapes) == attn + ffn


def test_command_r_params_by_hand():
    w = _work("command-r-35b.l5")
    attn = 8192 * 64 * 128 * 2 + 8192 * 8 * 128 * 2
    ffn = 3 * 8192 * 22528
    layer = attn + ffn + 2 * 8192                          # one LayerNorm
    total = 5 * layer + 256000 * 8192 + 2 * 8192           # tied, final LN
    assert total == 5_620_465_664
    assert w.num_params() == total


@pytest.mark.parametrize("name", ["chameleon-34b.l6", "command-r-35b.l5"])
def test_params_agree_with_the_program(name):
    conf = _conf(name)
    arch = spec.load_architecture(conf)
    assert arch.work(conf).num_params() == \
        arch.model_config(conf).num_params()


SMALL = DENSE.work({
    "num_hidden_layers": 2, "hidden_size": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 4, "intermediate_size": 24,
    "vocab_size": 50, "hidden_act": "silu", "tie_word_embeddings": False,
    "qk_layernorm": False, "norm": "rmsnorm", "parallel_block": False})
PROGRESS = [(10, 0, 10, 0, 5), (7, 3, 7, 0, 1), (9, 9, 9, 2, 6),
            (12, 0, 4, 0, 0)]


def _brute_flops(w, progress):
    s = w.shapes
    mm = 2 * (2 * s.d_model * s.heads * s.head_dim
              + 2 * s.d_model * s.kv_heads * s.head_dim
              + 3 * s.d_model * s.d_ff)
    total = 0
    for plen, p0, p1, g0, g1 in progress:
        for i in range(p0, p1):               # prompt token at position i
            total += s.layers * (mm + 4 * s.heads * s.head_dim * (i + 1))
        for j in range(g0, g1):               # generated token j
            total += 2 * s.d_model * s.vocab
            if j >= 1:
                total += s.layers * (mm + 4 * s.heads * s.head_dim
                                     * (plen + j))
    return total


def test_served_flops_by_brute_force():
    assert SMALL.served_flops(PROGRESS) == _brute_flops(SMALL, PROGRESS)
    assert SMALL.served_flops([]) == 0


def test_decode_attention_work_by_brute_force():
    flops = nbytes = 0
    for plen, _p0, _p1, g0, g1 in PROGRESS:
        for j in range(max(g0, 1), g1):
            ctx = plen + j
            flops += 4 * 4 * 4 * ctx
            nbytes += ctx * 2 * 2 * 4 * 2 + 2 * 4 * 4 * 2
    assert SMALL.decode_attention_work(PROGRESS) == (2 * flops, 2 * nbytes)


def test_prefill_attention_work_by_brute_force():
    flops = nbytes = 0
    chunk = 4
    for _plen, p0, p1, _g0, _g1 in PROGRESS:
        for start in range(p0, p1, chunk):
            end = min(start + chunk, p1)
            flops += sum(4 * 4 * 4 * (i + 1) for i in range(start, end))
            nbytes += end * 2 * 2 * 4 * 2 + (end - start) * 2 * 4 * 4 * 2
    assert SMALL.prefill_attention_work(PROGRESS, chunk) == (
        2 * flops, 2 * nbytes)


def test_least_seconds_is_the_larger_bound():
    assert work.least_seconds(197e12, 0, 197e12, 819e9) == 1.0
    assert work.least_seconds(0, 819e9, 197e12, 819e9) == 1.0
    assert work.least_seconds(197e12, 2 * 819e9, 197e12, 819e9) == 2.0


# The routed-expert fixture (bench/tests/data): d 128, 4 heads, 2 KV heads,
# head dim 32, 3 layers (1 dense at 512, 2 of 8 experts at 256, top-2, one
# shared expert at 256), an untied 512-row head.
FIXTURE = Path(bench_tiny.BENCH) / "tests" / "data"


def _fixture():
    conf = json.loads((FIXTURE / "tiny-moe.json").read_text())
    return conf, spec.load_architecture(conf, FIXTURE)


def test_fixture_params_by_hand():
    conf, arch = _fixture()
    attn = 128 * 4 * 32 * 2 + 128 * 2 * 32 * 2             # q, o; k, v
    dense = attn + 3 * 128 * 512 + 2 * 128                 # FFN, 2 RMSNorms
    expert = attn + 128 * 8 + 8 * 3 * 128 * 256 \
        + 3 * 128 * 256 + 2 * 128                          # router, shared
    total = dense + 2 * expert + 2 * 512 * 128 + 128       # embed, head
    assert total == 2_247_552
    assert arch.work(conf).num_params() == total
    assert arch.model_config(conf).num_params() == total


def test_fixture_served_flops_by_brute_force():
    conf, arch = _fixture()
    attn = 128 * 4 * 32 * 2 + 128 * 2 * 32 * 2
    dense = 2 * (attn + 3 * 128 * 512)
    # the router, the top 2 of the 8 experts and the shared one
    expert = 2 * (attn + 128 * 8 + 2 * 3 * 128 * 256 + 3 * 128 * 256)
    pair = 4 * 4 * 32                     # per (query, key) pair and layer
    total = 0
    for plen, p0, p1, g0, g1 in PROGRESS:
        for i in range(p0, p1):
            total += dense + 2 * expert + 3 * pair * (i + 1)
        for j in range(g0, g1):
            total += 2 * 128 * 512
            if j >= 1:
                total += dense + 2 * expert + 3 * pair * (plen + j)
    w = arch.work(conf)
    assert w.served_flops(PROGRESS) == total
    assert w.served_flops([]) == 0


def test_fixture_attention_work_is_full_attention():
    conf, arch = _fixture()
    w = arch.work(conf)
    flops = nbytes = 0
    for plen, _p0, _p1, g0, g1 in PROGRESS:
        for j in range(max(g0, 1), g1):
            ctx = plen + j
            flops += 4 * 4 * 32 * ctx
            nbytes += ctx * 2 * 2 * 32 * 2 + 2 * 4 * 32 * 2
    assert w.decode_attention_work(PROGRESS) == (3 * flops, 3 * nbytes)
    flops = nbytes = 0
    for _plen, p0, p1, _g0, _g1 in PROGRESS:
        for start in range(p0, p1, 4):
            end = min(start + 4, p1)
            flops += sum(4 * 4 * 32 * (i + 1) for i in range(start, end))
            nbytes += end * 2 * 2 * 32 * 2 + (end - start) * 2 * 4 * 32 * 2
    assert w.prefill_attention_work(PROGRESS, 4) == (3 * flops, 3 * nbytes)
