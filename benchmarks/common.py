"""Shared benchmark scaffolding: the paper's experiment ladder at
container scale, cached per-experiment so tables reuse runs.

The ladder itself is declared in ``repro.launch.sweeps`` (the
multi-sweep runner); this module owns the bench policy — round budget
via REPRO_BENCH_ROUNDS, a process-wide shared SweepRunner (one corpus,
one jit cache for all experiments) and the on-disk result cache.

Scale disclosure: the paper trains a 122M RNN-T on 960h Librispeech
for thousands of rounds on TPU; this harness runs the SAME code paths
(FedAvg engine, FVN, data-limit dial, CFMQ accounting, WER metric) on
the synthetic speaker-split corpus at CPU scale. The deliverable is
the *qualitative ladder* (directions and orderings of E0-E10), not the
absolute WERs.
"""
from __future__ import annotations

import json
import os

from repro.launch.sweeps import LADDER_LIMIT, SweepRunner, ladder_points, ladder_specs

ROUNDS = int(os.environ.get("REPRO_BENCH_ROUNDS", "100"))
CACHE = os.environ.get("REPRO_BENCH_CACHE", "results/bench_cache")

LIMIT = LADDER_LIMIT   # the ladder's E2 data limit (part of the cache key)

_MEM = {}
_RUNNER = None


def shared_runner() -> SweepRunner:
    """One corpus + one jitted-round-fn cache for every experiment."""
    global _RUNNER
    if _RUNNER is None:
        _RUNNER = SweepRunner(seed=0, eval_examples=64)
    return _RUNNER


def ladder_plans() -> dict:
    """The ladder's {eid: {plan, iid, ...}} specs (tables/fig3 use the
    plan objects for CFMQ accounting)."""
    return ladder_specs(ROUNDS)


def run_experiment(eid: str, seed: int = 0) -> dict:
    """Run (or fetch cached) experiment eid. Returns history summary."""
    # v2: summary rows renamed wer -> quality/quality_metric (FederatedTask
    # redesign); the suffix invalidates pre-rename cached rows
    key = f"{eid}_r{ROUNDS}_L{LIMIT}_s{seed}_v2"
    if key in _MEM:
        return _MEM[key]
    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, key + ".json")
    if os.path.exists(path):
        with open(path) as f:
            _MEM[key] = json.load(f)
        return _MEM[key]

    (point,) = ladder_points(ROUNDS, seed=seed, experiments=[eid])
    # the sweep row IS the experiment summary: one schema
    # (repro.core.metrics.SUMMARY_KEYS) across train histories, sweep
    # rows and the bench cache — no hand-picked subset to drift
    row = shared_runner().run_point(point)
    with open(path, "w") as f:
        json.dump(row, f)
    _MEM[key] = row
    return row


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"


def bench_reps(env: str, knob: str) -> int:
    """Rep count for a bench loop: the environment variable wins (CI
    pins budgets), otherwise the named constant of
    ``repro.profile.tuner``."""
    from repro.profile.tuner import get_knob

    raw = os.environ.get(env)
    return max(1, int(raw)) if raw else int(get_knob(knob))


def interleaved_min_us(fns: dict, reps=None) -> dict:
    """Microsecond wrapper over the profiling plane's shared
    interleaved order-rotating min protocol
    (``repro.profile.trace.measure_interleaved_min``) — the fed_round
    bench measurement style, now the default for every micro-bench:
    per-cycle order rotation cancels slow-drift runner load, and the
    per-fn MIN is the noise floor each graph can reach."""
    from repro.profile.trace import measure_interleaved_min

    return {k: v * 1e6 for k, v in measure_interleaved_min(fns, reps=reps).items()}
