"""The comparison that decides ``correct``.

After the window, a sample of the requests the engine finished, drawn
from the seed with the longest among them, is run once through the
configuration's plain float32 reference (``spec.load_reference``),
teacher-forced with the served tokens.  The number compared is the widest
gap by which a served token's reference logit lies below the reference's
best at that position.

The control puts the reference, computed one precision step below the
configuration (float8 matmuls), in the program's place: at each position
of the same prompts and served tokens, the token it puts first.
"""
from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Dict, List, Tuple

import numpy as np

from benchlib import traffic as tr


def sample(finished: List[Any], seed: int, max_requests: int,
           tokens: int) -> List[Any]:
    """The longest finished request, then others in an order drawn from
    the seed, until ``tokens`` served tokens or ``max_requests``."""
    if not finished:
        return []
    ordered = sorted(finished, key=lambda r: (-(len(r.prompt)
                                                + len(r.generated)), r.rid))
    rest = ordered[1:]
    rng = tr.seed_rng(seed, 4)
    picked = [ordered[0]] + [rest[i] for i in rng.permutation(len(rest))]
    out, n = [], 0
    for r in picked:
        if len(out) >= max_requests or n >= tokens:
            break
        out.append(r)
        n += len(r.generated)
    return out


def teacher_forced(req) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(sequence, positions, tokens): the prompt and the served tokens but
    the last, and for each served token the position whose logits chose
    it."""
    gen = np.asarray(req.generated, np.int32)
    seq = np.concatenate([np.asarray(req.prompt, np.int32), gen[:-1]])
    rows = np.arange(gen.size) + len(req.prompt) - 1
    return seq, rows, gen


def logit_gaps(ref, weights, conf: Dict[str, Any], requests: List[Any],
               pad_to: int) -> np.ndarray:
    """Per served token, the reference's max logit minus its logit for
    the served token."""
    gaps = []
    for r in requests:
        seq, rows, gen = teacher_forced(r)
        mx, _am, pk = ref.logits_summary(weights, conf, seq, rows, gen,
                                         pad_to)
        gaps.append(mx.astype(np.float64) - pk.astype(np.float64))
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def as_control(ref, weights, conf: Dict[str, Any], requests: List[Any],
               pad_to: int) -> List[Any]:
    """The requests with each served token replaced by the one the
    lower-precision control puts first at that position."""
    out = []
    for r in requests:
        seq, rows, gen = teacher_forced(r)
        _mx, am, _pk = ref.logits_summary(weights, conf, seq, rows, gen,
                                          pad_to, control=True)
        out.append(SimpleNamespace(rid=r.rid, prompt=r.prompt,
                                   generated=[int(t) for t in am]))
    return out
