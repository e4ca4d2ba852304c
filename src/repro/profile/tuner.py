"""The constants the host-side loops read by name: the prefetch queue
depth of the train driver and the chip harness, and the rep counts of
the CPU micro benches (``benchmarks.common.bench_reps`` lets a
``REPRO_BENCH_*_REPS`` environment variable win over them).

Kernel choice is not here: each dispatcher picks its kernel from the
backend alone (``models/lstm.py``, ``kernels/ops.py``,
``kernels/wire_pack.py``, ``models/encdec.py``).

This module is stdlib-only, so any layer may import it.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Knob:
    default: object
    doc: str


KNOBS: dict[str, Knob] = {
    "prefetch.depth": Knob(
        2,
        "Queue depth of the host->device prefetch pipeline "
        "(data/prefetch.PrefetchIterator) used by launch/train.",
    ),
    "bench.fed_reps": Knob(
        5,
        "Interleaved order-rotating cycles for the fed_round bench "
        "(min per variant over this many visits).",
    ),
    "bench.fed_pair_reps": Knob(
        6,
        "Adjacent fp32-vs-variant A/B pairs per fed_round ratio "
        "(median over pairs).",
    ),
    "bench.wire_reps": Knob(
        12,
        "Interleaved min reps for the wire-plane micro benches "
        "(pack/unpack kernels).",
    ),
    "bench.micro_reps": Knob(
        5,
        "Interleaved min reps for the remaining micro benches "
        "(attention, RNN-T joint).",
    ),
    "bench.pack_reps": Knob(
        30,
        "Interleaved min reps for the host round-packing bench "
        "(fed_pack_vectorized; host-side, cheap, so many reps).",
    ),
}


def get_knob(name: str):
    """The constant ``name``; ``KeyError`` for an unknown name."""
    if name not in KNOBS:
        raise KeyError(f"unknown tuning knob {name!r}; known: {sorted(KNOBS)}")
    return KNOBS[name].default
