"""Trace capture: versioned trace JSON from real runs, one writer for
train / sweeps / bench, and the program's host spans on the profiler's
clock.

Every emitter — the train driver's round loop, the sweep runner's
per-point timing, the kernel micro benches — produces the SAME record
shape through :func:`write_trace`
(mirroring how ``repro.core.metrics`` owns one summary-row schema for
train/sweeps/bench):

- ``schema_version`` / ``kind``: one of :data:`TRACE_KINDS`;
- ``device`` + ``device_key``: the fingerprint of the machine that
  took the timings, so records from two machines are told apart;
- ``structural_key``: the RoundEngine jit-cache identity of the traced
  plan (``repro.core.engine.structural_key_str``), so traces join
  against compiled-graph identities, not point names;
- ``sections``: per-stage wall timers ({count, total_s, min_s,
  mean_s}) from a :class:`TraceRecorder` wrapped around the host
  pipeline stages (pack -> round step -> eval);
- ``kernels``: per-kernel us from the micro benches;
- ``counters``: run bookkeeping (rounds, n_params, ...).

Host spans: :func:`span` is a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, and every :class:`TraceRecorder` section opens one,
so a device trace can put an idle gap down to what the host was doing
(``repro.pack``, ``repro.round``, ``repro.prefetch.put`` ...). Inside
the jitted round step the stages are ``jax.named_scope``s instead
(``core/fedavg.py``, ``models/rnnt.py``): compile-time names on each
device operation.

Also home to :func:`measure_interleaved_min` — the order-rotating
min-of-reps protocol of the fed_round bench, shared here so every
bench measures the same way.
"""

from __future__ import annotations

import json
import os
import platform
import threading
import time
from contextlib import contextmanager
from typing import Callable, Optional

import jax

TRACE_SCHEMA_VERSION = 2
TRACE_KINDS = ("round", "sweep", "kernels")
SECTION_STAT_KEYS = ("count", "total_s", "min_s", "mean_s")


# ------------------------------------------------------------ identity


def device_fingerprint() -> dict:
    """What makes timings from this process comparable: accelerator
    kind + count, host arch, and the jax version (Pallas lowering and
    XLA fusion choices move between releases)."""
    devices = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "unknown",
        "device_count": len(devices),
        "host_arch": platform.machine(),
        "jax_version": jax.__version__,
    }


def device_key(fp: Optional[dict] = None) -> str:
    """Stable slug of the fingerprint — the traces' join key (e.g.
    ``cpu_x8_cpu_x86_64_jax0.9.0``)."""
    fp = fp or device_fingerprint()
    raw = (
        f"{fp['backend']}_x{fp['device_count']}_{fp['device_kind']}"
        f"_{fp['host_arch']}_jax{fp['jax_version']}"
    )
    return "".join(c if c.isalnum() or c in "._-" else "_" for c in raw.lower())


# ----------------------------------------------------------- recorder

SPAN_PREFIX = "repro."


def span(name: str):
    """A host span ``repro.<name>`` on the profiler's clock: an
    annotation only, which costs a few microseconds when no trace is
    being taken and keeps no record of its own."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class TraceRecorder:
    """Lightweight per-section wall timers (thread-safe: the data
    plane's prefetch worker packs on a background thread). Each section
    is also a :func:`span` of the same name."""

    def __init__(self):
        self._lock = threading.Lock()
        self._sections: dict[str, list[float]] = {}

    def add(self, name: str, seconds: float) -> None:
        with self._lock:
            self._sections.setdefault(name, []).append(float(seconds))

    @contextmanager
    def section(self, name: str):
        with span(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.add(name, time.perf_counter() - t0)

    def stats(self) -> dict:
        with self._lock:
            out = {}
            for name, samples in self._sections.items():
                out[name] = {
                    "count": len(samples),
                    "total_s": sum(samples),
                    "min_s": min(samples),
                    "mean_s": sum(samples) / len(samples),
                }
            return out


# ------------------------------------------------------------- schema


def trace_record(
    kind: str,
    *,
    structural_key: Optional[str] = None,
    sections: Optional[dict] = None,
    kernels: Optional[dict] = None,
    counters: Optional[dict] = None,
    meta: Optional[dict] = None,
) -> dict:
    """Build a schema-valid trace record (the one writer's payload)."""
    rec = {
        "schema_version": TRACE_SCHEMA_VERSION,
        "kind": kind,
        "created_unix": time.time(),
        "device": device_fingerprint(),
        "device_key": device_key(),
        "structural_key": structural_key,
        "sections": dict(sections or {}),
        "kernels": {k: float(v) for k, v in (kernels or {}).items()},
        "counters": {k: float(v) for k, v in (counters or {}).items()},
        "meta": dict(meta or {}),
    }
    return validate_trace(rec)


def validate_trace(rec: dict) -> dict:
    """Strict schema check — same contract style as
    ``repro.core.metrics.summary_row``: unknown shapes fail loudly at
    the writer, not in a reader three PRs later."""
    if rec.get("schema_version") != TRACE_SCHEMA_VERSION:
        raise ValueError(
            f"trace schema_version {rec.get('schema_version')!r} != {TRACE_SCHEMA_VERSION}"
        )
    if rec.get("kind") not in TRACE_KINDS:
        raise ValueError(f"trace kind {rec.get('kind')!r} not in {TRACE_KINDS}")
    required = (
        "created_unix",
        "device",
        "device_key",
        "structural_key",
        "sections",
        "kernels",
        "counters",
        "meta",
    )
    missing = [k for k in required if k not in rec]
    if missing:
        raise ValueError(f"trace record missing keys: {missing}")
    for name, stats in rec["sections"].items():
        extra = set(stats) - set(SECTION_STAT_KEYS)
        lacking = set(SECTION_STAT_KEYS) - set(stats)
        if extra or lacking:
            raise ValueError(
                f"section {name!r}: stats must be exactly {SECTION_STAT_KEYS} "
                f"(extra={sorted(extra)}, missing={sorted(lacking)})"
            )
    return rec


def write_trace(path: str, kind: str, **kwargs) -> str:
    """THE trace writer — every emitter goes through here. ``kwargs``
    are :func:`trace_record` fields; a TraceRecorder may be passed
    directly as ``sections``."""
    sections = kwargs.get("sections")
    if isinstance(sections, TraceRecorder):
        kwargs["sections"] = sections.stats()
    rec = trace_record(kind, **kwargs)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def load_trace(path: str) -> dict:
    with open(path) as f:
        return validate_trace(json.load(f))


# -------------------------------------------------------- measurement


def measure_interleaved_min(
    fns: dict[str, Callable], reps: Optional[int] = None, warmup: int = 1
) -> dict[str, float]:
    """Order-rotating interleaved min-of-reps wall timing, in seconds.

    The fed_round bench protocol, generalized: warm every candidate
    first (compile excluded), then run ``reps`` cycles, each visiting
    every fn once in an order rotated per cycle (so drift hits each
    candidate equally), and report the per-fn MIN — the lowest
    observed time is the least-noise estimate on a shared machine.
    """
    if reps is None:
        from repro.profile.tuner import get_knob

        reps = int(get_knob("bench.micro_reps"))
    names = list(fns)
    for _ in range(max(warmup, 1)):
        for name in names:
            jax.block_until_ready(fns[name]())
    best = {name: float("inf") for name in names}
    for r in range(reps):
        order = names[r % len(names) :] + names[: r % len(names)]
        for name in order:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[name]())
            best[name] = min(best[name], time.perf_counter() - t0)
    return best
