"""Seeded random weights, made on the device in one jitted call, in the
dtype they are served in.

Each element is a normal drawn from a hash of its index and the seed:
elementwise arithmetic that compiles in seconds and needs no temporary
buffers (a blocked ``jax.random`` generator took minutes to compile for
the chip at these sizes).

The tree has the program's parameter layout (read with ``jax.eval_shape``
of its ``init``, which allocates nothing); every leaf is filled by the
benchmark from its name, so the plain reference reads the same weights
without taking anything the program made.  The rules every architecture
shares are here; an architecture module gives the rules for the leaves it
adds (``weight_rule``), and a leaf neither knows is an error.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

_GOLDEN = 0x9E3779B9


def _mix(x):
    """murmur3's 32-bit finalizer: a bijection that scrambles every bit."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def _words(seed: int, leaf: int):
    """Two 32-bit stream words for one leaf of one seed (host ints)."""
    seed = int(seed) & (2**64 - 1)
    h = (seed * 0x9E3779B97F4A7C15 + leaf * 0xBF58476D1CE4E5B9) % 2**64
    h ^= h >> 31
    h = (h * 0x94D049BB133111EB) % 2**64
    h ^= h >> 29
    return h & 0xFFFFFFFF, h >> 32


def _normal(shape, w1, w2):
    """Standard normals (float32), one per element, from a hash of the
    element's index and the stream words, by Box-Muller.  Elementwise, so
    XLA fuses it into one pass that writes the leaf and nothing else."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(shape))):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) \
            * jnp.uint32(stride % 2**32)
        stride *= shape[ax]
    a = _mix(_mix(idx ^ w1) + jnp.uint32(_GOLDEN))
    b = _mix(_mix(idx ^ w2) + jnp.uint32(_GOLDEN))
    inv = 1.0 / (1 << 24)
    u1 = ((a >> 8).astype(jnp.float32) + 0.5) * inv     # (0, 1)
    u2 = (b >> 8).astype(jnp.float32) * inv
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2.0 * jnp.pi * u2)


Rule = Tuple[str, float]
# an architecture's rules: (kind, std) for the leaves it adds, else None
ArchRule = Callable[[str, Tuple[int, ...], int], Optional[Rule]]


def _shared_rule(path: str, shape, d_model: int) -> Optional[Rule]:
    """(kind, std) for a leaf every architecture may have, by its name in
    the program's layout."""
    name = path.rsplit("/", 1)[-1]
    if name == "embedding":
        return "normal", 0.02
    if name in ("scale", "q_norm", "k_norm"):
        return "one_plus", 0.1
    if name == "bias":
        return "zeros", 0.0
    if name in ("w_q", "w_k", "w_v", "w_gate", "w_up", "w_head"):
        return "normal", 1.0 / math.sqrt(d_model)
    if name == "w_o":            # [L, H, hd, d]: fan-in H·hd
        return "normal", 1.0 / math.sqrt(shape[-3] * shape[-2])
    if name == "w_down":         # [L, d_ff, d]
        return "normal", 1.0 / math.sqrt(shape[-2])
    return None


def rule(path: str, shape, d_model: int,
         arch_rule: Optional[ArchRule] = None) -> Rule:
    """(kind, std) for a leaf: the shared rules, then the architecture's."""
    found = _shared_rule(path, shape, d_model)
    if found is None and arch_rule is not None:
        found = arch_rule(path, tuple(shape), d_model)
    if found is None:
        raise ValueError(f"no weight rule for leaf {path!r}")
    return found


def make_weights(init_fn: Callable[[Any], Any], d_model: int, seed: int,
                 dtype=jnp.bfloat16, arch_rule: Optional[ArchRule] = None):
    """Weights in the layout of ``init_fn``'s output, from ``seed``, with
    ``arch_rule`` for the leaves the shared rules do not know."""
    abstract = jax.eval_shape(init_fn, jax.random.key(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    paths = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    shapes = [tuple(a.shape) for _, a in flat]
    rules = [rule(p, s, d_model, arch_rule) for p, s in zip(paths, shapes)]

    words = jnp.asarray([_words(seed, i) for i in range(len(shapes))],
                        jnp.uint32)

    def build(words):
        # the words are an argument, not constants, so that the compiler
        # does not try to fold the whole model's weights at compile time
        leaves = []
        for (shape, (kind, std)), (w1, w2) in zip(zip(shapes, rules), words):
            if kind == "zeros":
                leaves.append(jnp.zeros(shape, dtype))
            elif kind == "one_plus":
                leaves.append((1.0 + std * _normal(shape, w1, w2))
                              .astype(dtype))
            else:
                leaves.append((std * _normal(shape, w1, w2)).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(words)
