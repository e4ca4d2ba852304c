"""Autotuning planner: the registry that owns every kernel dispatch
threshold, with measured-on-this-device overrides, plus the sweep-grid
pruner.

Before this module, dispatch constants (bench interleave rep counts,
the Pallas-vs-ref backend choice) were hard-coded from one machine's
benchmarks. Here every such constant is a named *knob* with
a documented default; call sites read them through :func:`get_knob`,
and per-device measured overrides persist to ``results/tuning.json``
keyed by the trace plane's device fingerprint — so a new backend tunes
itself once and every later run picks the measured value up.

The same JSON document stores the cost predictor's calibrated
per-device coefficients (see ``repro.profile.predict``), which is what
lets ``sweeps.py --prune-budget`` drop grid points whose *predicted*
cost exceeds a budget before anything compiles. ``check_prune`` is the
safety property behind ``--check``: pruning must never drop a row the
measured run marked pareto.

This module is import-light on purpose (stdlib only at module level):
``repro.kernels.wire_pack`` reads knobs from the hot dispatch path, so
nothing here may import jax, the kernels, or the core planes at import
time.

CLI::

    PYTHONPATH=src python -m repro.profile.tuner --show
    PYTHONPATH=src python -m repro.profile.tuner --set lstm.scan_min_seq 32
    PYTHONPATH=src python -m repro.profile.tuner --autotune lstm
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Callable, Optional

TUNING_SCHEMA_VERSION = 1
DEFAULT_PATH = os.path.join("results", "tuning.json")
ENV_PATH = "REPRO_TUNING_JSON"


@dataclasses.dataclass(frozen=True)
class Knob:
    default: object
    doc: str
    choices: Optional[tuple] = None


KNOBS: dict[str, Knob] = {
    "wire_pack.dispatch": Knob(
        "auto",
        "Pallas-vs-ref backend choice for the wire kernels: 'auto' picks "
        "Pallas off-CPU and the jnp oracle on CPU; 'ref' forces the "
        "oracle everywhere (a measured escape hatch for backends where "
        "Pallas lowering regresses); 'pallas' forces Pallas kernels "
        "(interpret mode on CPU — test/debug only).",
        choices=("auto", "pallas", "ref"),
    ),
    "lstm.scan_dispatch": Knob(
        "auto",
        "Backend choice for the full-sequence Pallas LSTM scan kernel "
        "(w_hh VMEM-resident across steps): 'auto' picks the kernel "
        "off-CPU when the shape is eligible, the jnp lax.scan otherwise; "
        "'ref' forces the jnp scan; 'pallas' forces the kernel "
        "(interpret mode on CPU — test/debug only).",
        choices=("auto", "pallas", "ref"),
    ),
    "lstm.scan_min_seq": Knob(
        16,
        "Sequence length at or above which the LSTM layer dispatches "
        "the full-scan Pallas kernel; below it the per-step w_hh "
        "refetch is too small to matter and lax.scan wins "
        "(re-measure with --autotune lstm).",
    ),
    "lstm.scan_max_vmem_mb": Knob(
        8,
        "VMEM budget (MB) for the scan kernel's resident w_hh block; "
        "hidden sizes whose (H x 4H) fp32 weight exceeds it fall back "
        "to lax.scan.",
    ),
    "rnnt.joint_bwd_dispatch": Knob(
        "auto",
        "Backend choice for the fused RNN-T joint backward: 'auto' "
        "picks the Pallas recompute-in-VMEM backward off-CPU and the "
        "U-chunked jnp rematerialization on CPU; 'ref' forces the "
        "chunked jnp backward; 'pallas' forces the kernel (interpret "
        "mode on CPU — test/debug only).",
        choices=("auto", "pallas", "ref"),
    ),
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


def _coerce(name: str, value):
    knob = KNOBS[name]
    if knob.choices is not None:
        if value not in knob.choices:
            raise ValueError(f"{name}: {value!r} not in {knob.choices}")
        return value
    kind = type(knob.default)
    out = kind(value)
    if isinstance(out, (int, float)) and out <= 0:
        raise ValueError(f"{name}: must be positive, got {out}")
    return out


class TuningRegistry:
    """``results/tuning.json`` facade: knob overrides + predictor
    coefficients, both keyed by device fingerprint so one file serves a
    fleet of heterogeneous machines."""

    def __init__(self, path: Optional[str] = None, device_key: Optional[str] = None):
        self.path = path or os.environ.get(ENV_PATH, DEFAULT_PATH)
        self._device_key = device_key
        self._doc = self._load()

    # ------------------------------------------------------------ store

    def _load(self) -> dict:
        doc = {"schema_version": TUNING_SCHEMA_VERSION, "devices": {}}
        try:
            with open(self.path) as f:
                on_disk = json.load(f)
            if on_disk.get("schema_version") == TUNING_SCHEMA_VERSION:
                doc = on_disk
                doc.setdefault("devices", {})
        except FileNotFoundError:
            pass
        except (json.JSONDecodeError, OSError, AttributeError):
            # a corrupt tuning file must never brick the dispatch path;
            # defaults are always safe
            pass
        return doc

    @property
    def device_key(self) -> str:
        if self._device_key is None:
            from repro.profile.trace import device_key

            self._device_key = device_key()
        return self._device_key

    def _device_entry(self, create: bool = False) -> dict:
        devices = self._doc["devices"]
        if create and self.device_key not in devices:
            from repro.profile.trace import device_fingerprint

            devices[self.device_key] = {
                "fingerprint": device_fingerprint(),
                "overrides": {},
                "coefficients": {},
            }
        return devices.get(self.device_key, {})

    def save(self) -> str:
        self._doc["updated_unix"] = time.time()
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        tmp = self.path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self._doc, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)
        return self.path

    # ------------------------------------------------------------ knobs

    def get(self, name: str):
        if name not in KNOBS:
            raise KeyError(f"unknown tuning knob {name!r}; known: {sorted(KNOBS)}")
        overrides = self._device_entry().get("overrides", {})
        if name in overrides:
            return _coerce(name, overrides[name])
        return KNOBS[name].default

    def overrides(self) -> dict:
        return dict(self._device_entry().get("overrides", {}))

    def set_override(self, name: str, value, persist: bool = False):
        if name not in KNOBS:
            raise KeyError(f"unknown tuning knob {name!r}; known: {sorted(KNOBS)}")
        value = _coerce(name, value)
        self._device_entry(create=True)["overrides"][name] = value
        if persist:
            self.save()
        return value

    def clear_override(self, name: str, persist: bool = False):
        self._device_entry().get("overrides", {}).pop(name, None)
        if persist:
            self.save()

    # ----------------------------------------------- predictor coeffs

    def set_coefficients(self, source: str, coeffs: dict, persist: bool = False):
        entry = self._device_entry(create=True)
        entry.setdefault("coefficients", {})[source] = {k: float(v) for k, v in coeffs.items()}
        if persist:
            self.save()

    def get_coefficients(self, source: str) -> Optional[dict]:
        got = self._device_entry().get("coefficients", {}).get(source)
        return dict(got) if got is not None else None


_ACTIVE: Optional[TuningRegistry] = None


def registry() -> TuningRegistry:
    """The process-wide registry (created lazily from $REPRO_TUNING_JSON
    or results/tuning.json)."""
    global _ACTIVE
    if _ACTIVE is None:
        _ACTIVE = TuningRegistry()
    return _ACTIVE


def set_registry(reg: Optional[TuningRegistry]) -> None:
    """Install (or with None: reset) the process-wide registry — tests
    point it at a tmp path."""
    global _ACTIVE
    _ACTIVE = reg


def get_knob(name: str):
    """Hot-path accessor used by kernel dispatchers and the bench
    harness; resolves override-else-default for this device."""
    return registry().get(name)


# ----------------------------------------------------------------------
# Autotune: measure the dispatch candidates on THIS device and persist
# the observed crossover as an override.
# ----------------------------------------------------------------------


def autotune_lstm_scan(
    reg: Optional[TuningRegistry] = None,
    seq_lens=(4, 8, 16, 32, 64, 128),
    batch: int = 8,
    hidden: int = 128,
    reps: int = 5,
    persist: bool = True,
    log=print,
) -> int:
    """Measure the full-scan Pallas LSTM kernel against the jnp
    ``lax.scan`` over ``seq_lens`` (forward + backward, the training
    shape) and persist the first length where the kernel wins as
    ``lstm.scan_min_seq``.

    On CPU the kernel runs in interpret mode, so the crossover
    validates the machinery rather than the production dispatch (CPU
    dispatch always takes lax.scan); on TPU this is the real
    w_hh-residency threshold for the local chip."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.lstm_gates import lstm_scan_fused_vjp
    from repro.models.lstm import lstm_gates
    from repro.profile.trace import measure_interleaved_min

    reg = reg or registry()
    interpret = jax.default_backend() == "cpu"
    key = jax.random.PRNGKey(0)
    w_hh = jax.random.normal(key, (hidden, 4 * hidden), jnp.float32) * 0.1
    crossover = None
    for S in seq_lens:
        xg = jax.random.normal(key, (S, batch, 4 * hidden), jnp.float32)
        h0 = jnp.zeros((batch, hidden), jnp.float32)
        c0 = jnp.zeros((batch, hidden), jnp.float32)

        def scan_loss(xg, w):
            def step(carry, xg_t):
                h, c = carry
                h, c = lstm_gates(xg_t + h @ w, c)
                return (h, c), h

            _, ys = jax.lax.scan(step, (h0, c0), xg)
            return jnp.sum(ys * ys)

        def kernel_loss(xg, w):
            ys, _, _ = lstm_scan_fused_vjp(xg, w, h0, c0, interpret=interpret)
            return jnp.sum(ys * ys)

        scan_g = jax.jit(jax.grad(scan_loss, argnums=(0, 1)))
        kern_g = jax.jit(jax.grad(kernel_loss, argnums=(0, 1)))
        t = measure_interleaved_min(
            {"scan": lambda: scan_g(xg, w_hh), "kernel": lambda: kern_g(xg, w_hh)},
            reps=reps,
        )
        log(
            f"[tuner] lstm_scan S={S}: lax.scan {t['scan'] * 1e6:.1f}us "
            f"kernel {t['kernel'] * 1e6:.1f}us"
        )
        if crossover is None and t["kernel"] < t["scan"]:
            crossover = S
    chosen = crossover if crossover is not None else max(seq_lens) * 2
    reg.set_override("lstm.scan_min_seq", chosen, persist=persist)
    log(f"[tuner] lstm.scan_min_seq <- {chosen} (device {reg.device_key})")
    return chosen


AUTOTUNERS: dict[str, Callable] = {
    "lstm": autotune_lstm_scan,
}


# ----------------------------------------------------------------------
# Sweep-grid pruner: drop points whose predicted cost exceeds a budget
# BEFORE anything compiles; --check proves the frontier survives.
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PruneDecision:
    point_id: str
    axis: str
    predicted: float
    budget: float

    @property
    def keep(self) -> bool:
        return self.predicted <= self.budget

    def as_dict(self) -> dict:
        return {
            "point_id": self.point_id,
            "axis": self.axis,
            "predicted": self.predicted,
            "budget": self.budget,
            "keep": self.keep,
        }


def prune_report(predicted: dict[str, float], budget: float, axis: str) -> dict:
    """{point_id: PruneDecision} over per-point predicted costs."""
    return {
        pid: PruneDecision(point_id=pid, axis=axis, predicted=float(cost), budget=float(budget))
        for pid, cost in predicted.items()
    }


def check_prune(rows: list[dict], report: dict, *, rtol: float = 0.05, log=print) -> int:
    """The pruner-never-drops-pareto property, asserted against a full
    measured run: (a) the budget must actually drop >= 1 point, (b) no
    measured-pareto row may be dropped, (c) where the budget axis is a
    measured row column (cfmq_tb), prediction must agree with the
    measurement within ``rtol``. Returns the dropped count."""
    dropped = [d.point_id for d in report.values() if not d.keep]
    if not dropped:
        raise AssertionError(
            f"--prune-budget dropped nothing: every predicted cost is under "
            f"{next(iter(report.values())).budget if report else float('nan')}"
        )
    for row in rows:
        pid = row.get("id")
        if pid not in report:
            raise AssertionError(f"measured row {pid!r} has no prune decision")
        d = report[pid]
        if row.get("pareto") and not d.keep:
            raise AssertionError(
                f"prune budget {d.budget} would drop PARETO point {pid!r} "
                f"(predicted {d.axis}={d.predicted:.6g}) — raise the budget"
            )
        if d.axis in row:
            measured = float(row[d.axis])
            err = abs(d.predicted - measured) / max(abs(measured), 1e-12)
            if err > rtol:
                raise AssertionError(
                    f"{pid!r}: predicted {d.axis}={d.predicted:.6g} vs measured "
                    f"{measured:.6g} (rel err {err:.3f} > {rtol})"
                )
    log(
        f"[tuner] prune check OK: {len(dropped)}/{len(report)} points over "
        f"budget ({', '.join(sorted(dropped))}), pareto frontier intact"
    )
    return len(dropped)


# ------------------------------------------------------------------ CLI


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--path", default=None, help="tuning JSON (default results/tuning.json)")
    ap.add_argument("--show", action="store_true", help="print knobs + overrides for this device")
    ap.add_argument("--set", nargs=2, metavar=("NAME", "VALUE"), action="append", default=[])
    ap.add_argument("--autotune", choices=sorted(AUTOTUNERS), action="append", default=[])
    args = ap.parse_args(argv)
    reg = TuningRegistry(path=args.path)
    for name, value in args.set:
        reg.set_override(name, value, persist=True)
        print(f"{name} <- {reg.get(name)!r}")
    for target in args.autotune:
        AUTOTUNERS[target](reg)
    if args.show or not (args.set or args.autotune):
        overrides = reg.overrides()
        print(f"# device {reg.device_key} ({reg.path})")
        for name in sorted(KNOBS):
            src = "override" if name in overrides else "default"
            print(f"{name:32s} = {reg.get(name)!r:10} [{src}] {KNOBS[name].doc.split('.')[0]}")


if __name__ == "__main__":
    main()
