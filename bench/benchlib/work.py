"""Operations and bytes of the work served: what every architecture's
counts share.  A configuration's architecture module
(``bench/configs/<architecture>.py``) counts its own model's work from the
configuration's shapes and from what the window served, as a ``Work``.
The benchmark divides these by times it measured, whatever code did the
work, so a later change to the program cannot change how its work is
counted.
"""
from __future__ import annotations

from typing import Iterable, Protocol, Tuple

# One request's progress over a window: prompt length, prompt tokens
# prefilled at the window's two edges, tokens generated at its two edges.
Progress = Tuple[int, int, int, int, int]


class Work(Protocol):
    """What an architecture module's ``work(conf)`` returns.  A module
    may carry more, for a kernel of its own; the readers of that kernel's
    metrics call it."""

    def num_params(self) -> int:
        """Every parameter of the model."""

    def served_flops(self, progress: Iterable[Progress]) -> int:
        """Model FLOPs of the work a window served."""

    def decode_attention_work(self, progress: Iterable[Progress]
                              ) -> Tuple[int, int]:
        """(FLOPs, bytes) of the decode attention a window served."""

    def prefill_attention_work(self, progress: Iterable[Progress],
                               chunk: int) -> Tuple[int, int]:
        """(FLOPs, bytes) of the chunked-prefill attention a window
        served, ``chunk`` prompt tokens a chunk."""


def ctx_sum(a: int, b: int, offset: int = 0) -> int:
    """Sum of (offset + i + 1) for i in [a, b)."""
    n = b - a
    if n <= 0:
        return 0
    return n * (offset + 1) + (b * (b - 1) - a * (a - 1)) // 2


def least_seconds(flops: int, nbytes: int, peak_flops: float,
                  peak_bytes_per_s: float) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak_flops, nbytes / peak_bytes_per_s)
