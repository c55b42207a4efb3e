"""Plain float32 reference of a routed-expert decoder with full attention
(``moe_architecture``), at ``Precision.HIGHEST``: no kernels, no cache, no
dispatch buffers.  It reads the benchmark's weights by leaf name and
nothing of the program under test.  Per layer:

    h    = rmsnorm1(x);  q,k,v = h·Wq, h·Wk, h·Wv;  rotary on q and k (the
           two halves of the head dim rotate as a pair)
    y    = x + softmax(q·kᵀ/√hd + causal mask)·v·Wo, KV heads shared by
           groups
    g    = rmsnorm2(y)
    leading dense layers:  y + (silu(g·Wgate) * (g·Wup))·Wdown
    expert layers:         p = softmax(g·Wrouter) over every expert; the
           top k, their weights renormalised to sum 1, each expert's FFN
           computed for every token and the chosen ones summed by weight;
           plus the shared experts' FFN

then the final norm and the untied LM head.  ``control=True`` rounds every
matmul's inputs to bfloat16, one step below the configuration's float32.
The fixture is small, so the whole padded sequence goes through in one
program.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos[:, None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _ffn(g, p, mm):
    return mm(jax.nn.silu(mm(g, p["w_gate"])) * mm(g, p["w_up"]),
              p["w_down"])


def _layer(p, x, conf, mm, experts):
    eps, theta = float(conf["norm_eps"]), float(conf["rope_theta"])
    H, Hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, p["norm1"]["scale"], eps)
    a = p["attn"]
    q = _rope(mm(h, a["w_q"]), pos, theta)             # [T, H, hd]
    k = _rope(mm(h, a["w_k"]), pos, theta)             # [T, Hkv, hd]
    v = mm(h, a["w_v"])
    hd = q.shape[-1]
    q = q.reshape(T, Hkv, H // Hkv, hd)
    s = jnp.einsum("qcgk,sck->cgqs", q, k, precision=HI) / np.sqrt(hd)
    s = jnp.where(pos[None, None, None, :] <= pos[None, None, :, None], s,
                  -jnp.inf)
    o = jnp.einsum("cgqs,sck->qcgk", jax.nn.softmax(s, -1), v,
                   precision=HI).reshape(T, H * hd)
    y = x + mm(o, a["w_o"].reshape(H * hd, -1))
    g = _rms(y, p["norm2"]["scale"], eps)
    if not experts:
        return y + _ffn(g, p["mlp"], mm)
    m = p["moe"]
    probs = jax.nn.softmax(mm(g, m["router"]), -1)         # [T, E]
    top, idx = jax.lax.top_k(probs, conf["num_experts_per_tok"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=F32)
                   * top[..., None], 1)                    # [T, E]
    each = jax.vmap(lambda wg, wu, wd: _ffn(
        g, {"w_gate": wg, "w_up": wu, "w_down": wd}, mm))(
        m["w_gate"], m["w_up"], m["w_down"])               # [E, T, d]
    return y + jnp.einsum("te,etd->td", gate, each, precision=HI) \
        + _ffn(g, m["shared"], mm)


@functools.partial(jax.jit, static_argnames=("conf_items", "control"))
def _logits(weights, tokens, rows, chosen, conf_items, control):
    conf = dict(conf_items)
    w = jax.tree.map(lambda a: a.astype(F32), weights)
    if control:
        def mm(x, wt):
            return jnp.tensordot(_bf16(x), _bf16(wt), 1, precision=HI)
    else:
        def mm(x, wt):
            return jnp.tensordot(x, wt, 1, precision=HI)
    x = w["embed"]["embedding"][tokens]
    stack = w["stack"]
    for name, experts in (("dense_blocks", False), ("blocks", True)):
        if name in stack:
            n = jax.tree.leaves(stack[name])[0].shape[0]
            for i in range(n):
                x = _layer(jax.tree.map(lambda a: a[i], stack[name]), x,
                           conf, mm, experts)
    h = _rms(x[rows], stack["final_norm"]["scale"], float(conf["norm_eps"]))
    lg = mm(h, w["head"]["w_head"])                        # [N, V]
    pick = jnp.take_along_axis(lg, chosen[:, None], 1)[:, 0]
    return jnp.max(lg, 1), jnp.argmax(lg, 1), pick


def logits_summary(weights, conf: Dict[str, Any], tokens: np.ndarray,
                   rows: np.ndarray, chosen: np.ndarray, pad_to: int,
                   control: bool = False):
    """For one sequence ``tokens``: at each position in ``rows``, the max
    logit, its token and the logit of ``chosen``.  The sequence and the
    rows are padded to ``pad_to``, so every sequence uses one compiled
    program (causal, so padding after the end changes nothing before
    it)."""
    if conf["tie_word_embeddings"]:
        raise ValueError("this reference reads an untied head")
    if conf["torch_dtype"] != "float32":
        raise ValueError("this reference's control is one step below float32")
    t, r, c = (np.zeros((pad_to,), np.int32) for _ in range(3))
    n = rows.size
    t[:tokens.size], r[:n], c[:n] = tokens, rows, chosen
    items = tuple((k, conf[k]) for k in (
        "norm_eps", "rope_theta", "num_attention_heads",
        "num_key_value_heads", "num_experts_per_tok"))
    mx, am, pk = _logits(weights, jnp.asarray(t), jnp.asarray(r),
                         jnp.asarray(c), items, control)
    return np.asarray(mx)[:n], np.asarray(am)[:n], np.asarray(pk)[:n]
