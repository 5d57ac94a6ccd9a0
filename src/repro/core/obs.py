"""The round's own names for its work, for a profiler trace to read.

``span(name)`` marks host work as a ``jax.profiler`` trace annotation
(next to free while no trace is running); the device phases are
``jax.named_scope``s in the round programs.  ``count(name)`` counts
events in this process; ``round_traces`` counts every trace, and so every
compile, of a resident round program.  The profiler is the only switch.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict

import jax

_COUNTS: Counter = Counter()


def span(name: str):
    return jax.profiler.TraceAnnotation(name)


def count(name: str) -> None:
    _COUNTS[name] += 1


def counts() -> Dict[str, int]:
    return dict(_COUNTS)
