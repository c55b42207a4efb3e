"""Operation and byte counts against hand counts."""
import json
from pathlib import Path

import pytest

import bench_tiny
from benchlib import work

CONFIGS = Path(bench_tiny.BENCH) / "configs"


def _shapes(name):
    return work.shapes_of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_chameleon_params_by_hand():
    s = _shapes("chameleon-34b.l6")
    attn = 8192 * 64 * 128 * 2 + 8192 * 8 * 128 * 2       # q, o; k, v
    ffn = 3 * 8192 * 22016
    layer = attn + ffn + 2 * 8192 + 2 * 128               # 2 RMSNorms, qk-norm
    total = 6 * layer + 2 * 65536 * 8192 + 8192           # embed, head, final
    assert total == 5_226_210_816
    assert work.num_params(s) == total
    assert work.layer_matmul_params(s) == attn + ffn


def test_command_r_params_by_hand():
    s = _shapes("command-r-35b.l5")
    attn = 8192 * 64 * 128 * 2 + 8192 * 8 * 128 * 2
    ffn = 3 * 8192 * 22528
    layer = attn + ffn + 2 * 8192                          # one LayerNorm
    total = 5 * layer + 256000 * 8192 + 2 * 8192           # tied, final LN
    assert total == 5_620_465_664
    assert work.num_params(s) == total


@pytest.mark.parametrize("name", ["chameleon-34b.l6", "command-r-35b.l5"])
def test_params_agree_with_the_program(name):
    run = bench_tiny.load_run()
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    assert work.num_params(work.shapes_of(conf)) == \
        run.model_config(conf).num_params()


SMALL = work.Shapes(layers=2, d_model=16, heads=4, kv_heads=2, head_dim=4,
                    d_ff=24, vocab=50, gated=True, tied=False,
                    qk_norm=False, layernorm=False, parallel_block=False)
PROGRESS = [(10, 0, 10, 0, 5), (7, 3, 7, 0, 1), (9, 9, 9, 2, 6),
            (12, 0, 4, 0, 0)]


def _brute_flops(s, progress):
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
    assert work.served_flops(SMALL, PROGRESS) == _brute_flops(SMALL,
                                                              PROGRESS)
    assert work.served_flops(SMALL, []) == 0


def test_decode_attention_work_by_brute_force():
    flops = nbytes = 0
    for plen, _p0, _p1, g0, g1 in PROGRESS:
        for j in range(max(g0, 1), g1):
            ctx = plen + j
            flops += 4 * 4 * 4 * ctx
            nbytes += ctx * 2 * 2 * 4 * 2 + 2 * 4 * 4 * 2
    assert work.decode_attention_work(SMALL, PROGRESS) == (2 * flops,
                                                           2 * nbytes)


def test_prefill_attention_work_by_brute_force():
    flops = nbytes = 0
    chunk = 4
    for _plen, p0, p1, _g0, _g1 in PROGRESS:
        for start in range(p0, p1, chunk):
            end = min(start + chunk, p1)
            flops += sum(4 * 4 * 4 * (i + 1) for i in range(start, end))
            nbytes += end * 2 * 2 * 4 * 2 + (end - start) * 2 * 4 * 4 * 2
    assert work.prefill_attention_work(SMALL, PROGRESS, chunk) == (
        2 * flops, 2 * nbytes)


def test_least_seconds_is_the_larger_bound():
    assert work.least_seconds(197e12, 0, 197e12, 819e9) == 1.0
    assert work.least_seconds(0, 819e9, 197e12, 819e9) == 1.0
    assert work.least_seconds(197e12, 2 * 819e9, 197e12, 819e9) == 2.0
