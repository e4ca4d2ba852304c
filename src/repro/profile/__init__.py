"""Profiling plane: trace capture and the host loops' named constants.

- ``repro.profile.trace`` — versioned trace JSON from real runs; one
  writer for train / sweeps / bench, keyed by the RoundEngine
  structural key + a device fingerprint; host spans on the profiler's
  clock; the interleaved min-of-reps timing protocol.
- ``repro.profile.tuner`` — the constant table behind ``get_knob``
  (prefetch depth, bench rep counts).

Submodules are imported lazily, so this package is importable mid-way
through ``repro.core`` / ``repro.data`` imports without touching them
back.
"""

from __future__ import annotations

import importlib

_SUBMODULES = ("trace", "tuner")


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f"repro.profile.{name}")
    raise AttributeError(f"module 'repro.profile' has no attribute {name!r}")


def __dir__():
    return sorted(list(globals()) + list(_SUBMODULES))
