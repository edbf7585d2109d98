"""The program's own host spans (``dstack.*``) in a reduced trace."""
from __future__ import annotations

from typing import Dict, List, Sequence

from harness.trace import Event


def spans_inside(reduced: Dict, name: str) -> List[Event]:
    """The host spans named ``name`` that lie wholly inside the traced
    interval."""
    t0, t1 = reduced["window"]
    return [e for e in reduced["spans"]
            if e[0] == name and t0 <= e[1] and e[1] + e[2] <= t1]


def covered(spans: Sequence[Event], a: int, b: int) -> int:
    """Nanoseconds of [a, b] that ``spans`` lying wholly inside it cover
    (spans of one thread nest or follow each other: they never overlap)."""
    return sum(d for _, s, d in spans if a <= s and s + d <= b)
