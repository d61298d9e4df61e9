"""The two shapes of work a traffic mix can ask for, by its ``kind``.

A driver is built with ``(config, traffic, seed)`` and does all of its
set-up there: data, compilation and warm-up.  ``window(seconds)`` measures;
``release()`` drops the program's state; ``check()`` compares what the
window produced with the reference and returns ``(checks, info lines)``.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    window_s: float
    #: end-to-end metrics the window measured, by name
    metrics: dict
    #: counts the per-layer readers take their numbers from
    counters: dict


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def span(name: str):
    """A host span in the profiler's trace, named ``bench.<name>``."""
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def driver(kind: str):
    if kind == "solve":
        from bench.drivers.solve import SolveDriver
        return SolveDriver
    if kind == "waves":
        from bench.drivers.waves import WavesDriver
        return WavesDriver
    raise ValueError(f"unknown traffic kind {kind!r}")
