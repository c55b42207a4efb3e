"""Plain float32 reference of a hybrid Mamba-2 / attention decoder laid out
by a per-layer pattern (Nemotron-H), for the configurations in this
directory that name it.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching of requests, and the recurrence token by token (a
``lax.scan`` over positions, not the chunked SSD form the program runs).
It reads the benchmark's weights by leaf name and nothing of the program
under test.  Each layer is x + mixer(rmsnorm(x)), the mixer by the layer's
character in ``hybrid_override_pattern``:

    M  zxbcdt = h·W_in, split into z [di], xBC [di + 2GN], dt [H]
       xBC    = silu(causal depthwise conv of xBC, k taps, with bias)
       x, B, C = split of xBC (B and C in G groups, head h reads group
                 h // (H/G));  dt = softplus(dt + dt_bias);  A = −exp(a_log)
       per token t and head h:  S ← exp(dt·A)·S + (dt·x) ⊗ B
                                 y = S·C + D·x
       y = rmsnorm of y·silu(z) over each of G groups of di/G channels,
           scaled;  out = y·W_out
    -  relu(h·W_up)²·W_down
    *  softmax(q·kᵀ/√hd + causal mask)·v, KV heads shared by groups, no
       rotary embedding (the published config has none), then ·W_o

then the final RMSNorm and the untied LM head.

Departures from the published model, shared with the program and noted in
``PERF.md``: the weights are random from the seed, with ``a_log`` and
``dt_bias`` drawn from normals (the published initialisation draws them
from uniforms) and ``D`` at 1; the vocabulary is the configuration's slice.

``control=True`` computes the same in the precision one step below the
configuration's: every projection's inputs rounded to float8 (e4m3) for a
bf16 configuration, the weights with one scale per output channel and the
activations with one per token, or to bfloat16 for a float32 one (a test
fixture).  The recurrence and the conv stay in float32.

Memory: the layers run one at a time, each casting only its own weights to
float32; the Mamba-2 layers go in blocks of positions, carrying the conv
tail and the state from block to block, the MLPs in blocks of tokens,
attention in blocks of queries and the LM head in blocks of the vocabulary,
so the whole fits beside the served weights.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
POSITION_BLOCK = 256   # Mamba-2 positions per block
TOKEN_BLOCK = 512      # MLP rows per block
QUERY_BLOCK = 256      # attention queries per block
VOCAB_BLOCKS = 16      # LM head blocks


def _spec(conf: Dict[str, Any]) -> Tuple:
    return (conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["attention_head_dim"], float(conf["rms_norm_eps"]),
            conf["mamba_num_heads"], conf["mamba_head_dim"],
            conf["ssm_state_size"], conf["n_groups"], conf["conv_kernel"])


def _runs(pattern: str):
    runs = []
    for ch in pattern:
        if runs and runs[-1][0] == ch:
            runs[-1][1] += 1
        else:
            runs.append([ch, 1])
    return runs


# the precision one step below each configuration's
LOWER = {"bfloat16": "float8", "float32": "bfloat16"}


def _lower(w, axes, control):
    """Round to the control's precision.  float8 (e4m3) takes one scale
    per slice: ``axes`` are the axes a scale covers (a weight's input axes;
    an activation's features)."""
    if control == "bfloat16":
        return w.astype(jnp.bfloat16).astype(F32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 448.0,
                    1e-30)
    return (w / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _act(control):
    return (lambda t, axes=(-1,): _lower(t, axes, control)) if control else \
        (lambda t, axes=(-1,): t)


def _layer_params(blocks, i, control, matmuls):
    p = jax.tree.map(lambda a: a[i].astype(F32), blocks)
    if control:
        for group, name, axes in matmuls:
            p[group][name] = _lower(p[group][name], axes, control)
    return p


def _pad_rows(x, block):
    n = -(-x.shape[0] // block)
    return jnp.pad(x, ((0, n * block - x.shape[0]), (0, 0))), n


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _mamba(blocks, i, x, spec, control):
    """One Mamba-2 layer over one padded sequence x [T, d] (float32)."""
    _H, _Hkv, _hd, eps, H, P, N, G, k = spec
    p = _layer_params(blocks, i, control, [("mamba", "in_proj", (0,)),
                                           ("mamba", "out_proj", (0,))])
    m = p["mamba"]
    act = _act(control)
    T, d = x.shape
    di, gn = H * P, G * N
    h = _rms(x, p["norm"]["scale"], eps)
    hb, nb = _pad_rows(h, POSITION_BLOCK)
    A = -jnp.exp(m["a_log"])

    def token(S, inp):
        xt, dtt, Bt, Ct = inp                  # [H, P], [H], [G, N], [G, N]
        Bh = jnp.repeat(Bt, H // G, axis=0)    # head h reads group h // (H/G)
        Ch = jnp.repeat(Ct, H // G, axis=0)
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * Bh[:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, Ch, precision=HI) \
            + m["d_skip"][:, None] * xt
        return S, y

    def block(carry, hblk):
        tail, S = carry                        # [k-1, di + 2GN], [H, P, N]
        zx = jnp.dot(act(hblk), m["in_proj"], precision=HI)
        z, xbc, dtr = (zx[:, :di], zx[:, di:2 * di + 2 * gn],
                       zx[:, 2 * di + 2 * gn:])
        stream = jnp.concatenate([tail, xbc], 0)
        n = hblk.shape[0]
        conv = m["conv_b"] + sum(stream[j:j + n] * m["conv_w"][:, j]
                                 for j in range(k))
        xbc_c = jax.nn.silu(conv)
        xs = xbc_c[:, :di].reshape(n, H, P)
        Bm = xbc_c[:, di:di + gn].reshape(n, G, N)
        Cm = xbc_c[:, di + gn:].reshape(n, G, N)
        dt = jax.nn.softplus(dtr + m["dt_bias"])
        S, y = jax.lax.scan(token, S, (xs, dt, Bm, Cm))
        g = (y.reshape(n, di) * jax.nn.silu(z)).reshape(n, G, di // G)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        g = g.reshape(n, di) * m["out_norm"]
        out = jnp.dot(act(g), m["out_proj"], precision=HI)
        return (stream[n:], S), out

    init = (jnp.zeros((k - 1, di + 2 * gn), F32), jnp.zeros((H, P, N), F32))
    _, out = jax.lax.scan(block, init,
                          hb.reshape(nb, POSITION_BLOCK, d))
    return x + out.reshape(nb * POSITION_BLOCK, d)[:T]


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _mlp(blocks, i, x, spec, control):
    """One squared-ReLU MLP layer over x [T, d]."""
    eps = spec[3]
    p = _layer_params(blocks, i, control, [("mlp", "w_up", (0,)),
                                           ("mlp", "w_down", (0,))])
    act = _act(control)
    T, d = x.shape
    hb, nb = _pad_rows(_rms(x, p["norm"]["scale"], eps), TOKEN_BLOCK)

    def ffn(g):
        u = jnp.square(jax.nn.relu(jnp.dot(act(g), p["mlp"]["w_up"],
                                           precision=HI)))
        return jnp.dot(act(u), p["mlp"]["w_down"], precision=HI)

    out = jax.lax.map(ffn, hb.reshape(nb, TOKEN_BLOCK, d))
    return x + out.reshape(nb * TOKEN_BLOCK, d)[:T]


@functools.partial(jax.jit, static_argnames=("spec", "control"))
def _attention(blocks, i, x, spec, control):
    """One attention layer (no rotary embedding) over x [T, d]."""
    Hq, Hkv, hd, eps = spec[:4]
    p = _layer_params(blocks, i, control, [("attn", "w_q", (0,)),
                                           ("attn", "w_k", (0,)),
                                           ("attn", "w_v", (0,)),
                                           ("attn", "w_o", (0, 1))])
    act = _act(control)
    T = x.shape[0]
    pos = jnp.arange(T)
    ha = act(_rms(x, p["norm"]["scale"], eps))
    q = jnp.einsum("td,dhk->thk", ha, p["attn"]["w_q"], precision=HI)
    kk = jnp.einsum("td,dhk->thk", ha, p["attn"]["w_k"], precision=HI)
    v = jnp.einsum("td,dhk->thk", ha, p["attn"]["w_v"], precision=HI)
    G = Hq // Hkv
    nq = -(-T // QUERY_BLOCK)
    qb = jnp.pad(q, ((0, nq * QUERY_BLOCK - T), (0, 0), (0, 0)))
    qb = qb.reshape(nq, QUERY_BLOCK, Hkv, G, hd)

    def attend(args):
        j, qq = args                          # qq [QB, Hkv, G, hd]
        s = jnp.einsum("qcgk,sck->cgqs", qq, kk, precision=HI) / np.sqrt(hd)
        qpos = j * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(pos[None, None, None, :] <= qpos[None, None, :, None],
                      s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("cgqs,sck->qcgk", w, v, precision=HI)

    o = jax.lax.map(attend, (jnp.arange(nq), qb))
    o = o.reshape(nq * QUERY_BLOCK, Hq, hd)[:T]
    return x + jnp.einsum("thk,hkd->td", act(o, (1, 2)), p["attn"]["w_o"],
                          precision=HI)


_LAYERS = {"M": _mamba, "-": _mlp, "*": _attention}


@functools.partial(jax.jit, static_argnames=("control",))
def _embed(table, tokens, control):
    e = table[tokens].astype(F32)
    return _lower(e, (1,), control) if control else e


@functools.partial(jax.jit, static_argnames=("eps", "control"))
def _final_and_head(final_norm, head, x, rows, chosen, eps, control):
    """Logits of rows ``rows`` of x, in vocabulary blocks: the max, the
    argmax, and the logit of ``chosen`` (the gap is max − that)."""
    h = _rms(x[rows], final_norm["scale"].astype(F32), eps)     # [N, d]
    if control:
        h = _lower(h, (-1,), control)
    bs = head.shape[1] // VOCAB_BLOCKS

    def block(j):
        w = jax.lax.dynamic_slice_in_dim(head, j * bs, bs, 1).astype(F32)
        if control:
            w = _lower(w, (0,), control)
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
    control = LOWER[conf["torch_dtype"]] if control else None
    if conf["vocab_size"] % VOCAB_BLOCKS:
        raise ValueError("vocabulary not divisible into head blocks")
    if conf["tie_word_embeddings"]:
        raise ValueError("the LM head is untied")
    t = np.zeros((pad_to,), np.int32)
    t[:tokens.size] = tokens
    x = _embed(weights["embed"]["embedding"], jnp.asarray(t), control)
    runs = weights["stack"]["runs"]
    for blocks, (kind, n) in zip(runs, _runs(conf["hybrid_override_pattern"])):
        for i in range(n):
            x = _LAYERS[kind](blocks, jnp.asarray(i, jnp.int32), x, spec,
                              control)
    # rows padded to a power of two: a few compiled programs, not one per
    # request length
    n = rows.size
    width = max(64, 1 << (n - 1).bit_length())
    r = np.zeros((width,), np.int32)
    c = np.zeros((width,), np.int32)
    r[:n], c[:n] = rows, chosen
    mx, am, pk = _final_and_head(weights["stack"]["final_norm"],
                                 weights["head"]["w_head"], x,
                                 jnp.asarray(r), jnp.asarray(c),
                                 float(conf["rms_norm_eps"]), control)
    return np.asarray(mx)[:n], np.asarray(am)[:n], np.asarray(pk)[:n]
