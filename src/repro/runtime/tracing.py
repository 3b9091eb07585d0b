"""The program's own spans and counters.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: under
``jax.profiler.trace`` it lands in the profiler's host plane, on the same
clock as the device's ops; with no profiler session it costs one check. A
counter is a count kept in memory for the life of the process; a reader
takes ``counters()`` before and after the work it looks at and subtracts.
"""
from __future__ import annotations

import collections

import jax

PREFIX = "repro."

_counts: collections.Counter[str] = collections.Counter()


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``repro.<name>``, to be entered with ``with``."""
    return jax.profiler.TraceAnnotation(PREFIX + name)


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the counter ``name``."""
    _counts[name] += n


def counters() -> collections.Counter[str]:
    """A copy of every counter."""
    return collections.Counter(_counts)
