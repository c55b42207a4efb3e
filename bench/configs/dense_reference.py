"""Plain float32 reference of a dense decoder-only transformer, for the
configurations in this directory.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching of requests.  It reads the benchmark's weights by leaf
name and nothing of the program under test.  Equations, per layer:

    h    = norm1(x)
    q,k,v = h·Wq, h·Wk, h·Wv;  qk-norm (RMS over the head dim, scaled)
           where the configuration has it;  rotary embedding on q and k
           (the two halves of the head dim rotate as a pair)
    attn = softmax(q·kᵀ/√hd + causal mask)·v, KV heads shared by groups
    ff   = (silu(g·Wgate) * (g·Wup))·Wdown
    parallel block:  x + attn(h)·Wo + ff(h)
    else:            y = x + attn(h)·Wo;  y + ff(norm2(y))

then the final norm and the LM head (the embedding's transpose when tied).
RMSNorm is x·rsqrt(mean(x²)+eps)·scale; LayerNorm subtracts the mean and
adds a bias.

Departures from the published models, shared with the program and noted in
``PERF.md``: Chameleon's qk-norm is an RMS norm here (the paper's is a
LayerNorm), and Command-R's rotary pairs are the two halves of the head dim
(Cohere's checkpoints interleave them), its LayerNorm carries a bias held at
zero and its ``logit_scale`` is not applied.

``control=True`` computes the same in the precision one step below the
configuration's bf16: every matmul's inputs rounded to float8 (e4m3), the
weights with one scale per output channel and the activations with one
per token.

Memory: the layers run one at a time, each casting only its own weights to
float32; feed-forward and attention go in blocks of tokens, and the LM head
in blocks of the vocabulary, so the whole fits beside the served weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
TOKEN_BLOCK = 512      # feed-forward rows per block
QUERY_BLOCK = 256      # attention queries per block
VOCAB_BLOCKS = 16      # LM head blocks


def _spec(conf: Dict[str, Any]) -> Tuple:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return (d, h, conf["num_key_value_heads"], conf.get("head_dim") or d // h,
            bool(conf.get("qk_layernorm") or conf.get("use_qk_norm")),
            conf["norm"], float(conf["norm_eps"]), bool(conf["parallel_block"]),
            float(conf["rope_theta"]), bool(conf["tie_word_embeddings"]))


def _fp8(w, axes):
    """Round to float8 (e4m3) with one scale per slice: ``axes`` are the
    axes a scale covers (a weight's input axes; an activation's
    features)."""
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0,
                    1e-30)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _norm(p, x, kind, eps):
    if kind == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32) \
            + p["bias"].astype(F32)
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * p["scale"].astype(F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale.astype(F32)


def _rope(x, pos, theta):
    """x [T, H, D]; the halves of D rotate as pairs."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _blocks(n, size):
    return -(-n // size)


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _layer(blocks, i, x, spec, control):
    """One layer over one padded sequence x [T, d] (float32)."""
    d, H, Hkv, hd, qkn, norm, eps, parallel, theta, _ = spec
    p = jax.tree.map(lambda a: a[i].astype(F32), blocks)
    if control:
        p["attn"]["w_q"] = _fp8(p["attn"]["w_q"], (0,))
        p["attn"]["w_k"] = _fp8(p["attn"]["w_k"], (0,))
        p["attn"]["w_v"] = _fp8(p["attn"]["w_v"], (0,))
        p["attn"]["w_o"] = _fp8(p["attn"]["w_o"], (0, 1))
        for k in ("w_gate", "w_up", "w_down"):
            p["mlp"][k] = _fp8(p["mlp"][k], (0,))
    # the control rounds every matmul input, activations per token too
    act = (lambda t, axes=(-1,): _fp8(t, axes)) if control else \
        (lambda t, axes=(-1,): t)
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _norm(p["norm1"], x, norm, eps)
    ha = act(h)
    q = jnp.einsum("td,dhk->thk", ha, p["attn"]["w_q"], precision=HI)
    k = jnp.einsum("td,dhk->thk", ha, p["attn"]["w_k"], precision=HI)
    v = jnp.einsum("td,dhk->thk", ha, p["attn"]["w_v"], precision=HI)
    if qkn:
        q = _rms(q, p["attn"]["q_norm"], eps)
        k = _rms(k, p["attn"]["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    G = H // Hkv
    nq = _blocks(T, QUERY_BLOCK)
    qb = jnp.pad(q, ((0, nq * QUERY_BLOCK - T), (0, 0), (0, 0)))
    qb = qb.reshape(nq, QUERY_BLOCK, Hkv, G, hd)

    def attend(args):
        j, qq = args                          # qq [QB, Hkv, G, hd]
        s = jnp.einsum("qcgk,sck->cgqs", qq, k, precision=HI) / np.sqrt(hd)
        qpos = j * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("cgqs,sck->qcgk", w, v, precision=HI)

    o = jax.lax.map(attend, (jnp.arange(nq), qb))
    o = o.reshape(nq * QUERY_BLOCK, H, hd)[:T]
    attn = jnp.einsum("thk,hkd->td", act(o, (1, 2)), p["attn"]["w_o"],
                      precision=HI)

    def ffn(g):
        g = act(g)
        a = jnp.dot(g, p["mlp"]["w_gate"], precision=HI)
        b = jnp.dot(g, p["mlp"]["w_up"], precision=HI)
        return jnp.dot(act(jax.nn.silu(a) * b), p["mlp"]["w_down"],
                       precision=HI)

    def ffn_blocked(g):
        nb = _blocks(T, TOKEN_BLOCK)
        gb = jnp.pad(g, ((0, nb * TOKEN_BLOCK - T), (0, 0)))
        out = jax.lax.map(ffn, gb.reshape(nb, TOKEN_BLOCK, d))
        return out.reshape(nb * TOKEN_BLOCK, d)[:T]

    if parallel:
        return x + attn + ffn_blocked(h)
    y = x + attn
    return y + ffn_blocked(_norm(p["norm2"], y, norm, eps))


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _embed(table, tokens, spec, control):
    e = table[tokens].astype(F32)
    return _fp8(e, (1,)) if control else e


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _final_and_head(final_norm, head, x, rows, chosen, spec, control):
    """Logits of rows ``rows`` of x, in vocabulary blocks: the max, the
    argmax, and the logit of ``chosen`` (the gap is max − that)."""
    _d, _H, _Hkv, _hd, _q, norm, eps, _p, _t, tied = spec
    h = _norm(final_norm, x[rows], norm, eps)            # [N, d]
    if control:
        h = _fp8(h, (-1,))
    V = head.shape[0] if tied else head.shape[1]
    bs = V // VOCAB_BLOCKS

    def block(j):
        if tied:
            w = jax.lax.dynamic_slice_in_dim(head, j * bs, bs, 0).astype(F32)
            if control:
                w = _fp8(w, (1,))
            lg = jnp.dot(h, w.T, precision=HI)
        else:
            w = jax.lax.dynamic_slice_in_dim(head, j * bs, bs, 1).astype(F32)
            if control:
                w = _fp8(w, (0,))
            lg = jnp.dot(h, w, precision=HI)
        idx = chosen - j * bs
        inside = (idx >= 0) & (idx < bs)
        pick = jnp.take_along_axis(lg, jnp.clip(idx, 0, bs - 1)[:, None],
                                   1)[:, 0]
        return (jnp.max(lg, 1), jnp.argmax(lg, 1) + j * bs,
                jnp.where(inside, pick, -jnp.inf))

    mx, am, pk = jax.lax.map(block, jnp.arange(VOCAB_BLOCKS))
    best = jnp.argmax(mx, 0)
    cols = jnp.arange(mx.shape[1])
    return mx[best, cols], am[best, cols], jnp.max(pk, 0)


def logits_summary(weights, conf, tokens: np.ndarray, rows: np.ndarray,
                   chosen: np.ndarray, pad_to: int, control: bool = False):
    """For one sequence ``tokens``: at each position in ``rows``, the max
    logit, its token and the logit of ``chosen``.  The sequence is padded
    to ``pad_to`` so every sequence uses one compiled program (causal, so
    padding after the end changes nothing before it)."""
    spec = _spec(conf)
    if conf["vocab_size"] % VOCAB_BLOCKS:
        raise ValueError("vocabulary not divisible into head blocks")
    t = np.zeros((pad_to,), np.int32)
    t[:tokens.size] = tokens
    x = _embed(weights["embed"]["embedding"], jnp.asarray(t), spec, control)
    blocks = weights["stack"]["blocks"]
    n_layers = jax.tree.leaves(blocks)[0].shape[0]
    for i in range(n_layers):
        x = _layer(blocks, jnp.asarray(i, jnp.int32), x, spec, control)
    head = weights["embed"]["embedding"] if spec[-1] else \
        weights["head"]["w_head"]
    # rows padded to a power of two: a few compiled programs, not one per
    # request length
    n = rows.size
    width = max(64, 1 << (n - 1).bit_length())
    r = np.zeros((width,), np.int32)
    c = np.zeros((width,), np.int32)
    r[:n], c[:n] = rows, chosen
    mx, am, pk = _final_and_head(weights["stack"]["final_norm"], head, x,
                                 jnp.asarray(r), jnp.asarray(c), spec,
                                 control)
    return np.asarray(mx)[:n], np.asarray(am)[:n], np.asarray(pk)[:n]
