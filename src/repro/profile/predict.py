"""Fed-round cost predictor: static FLOP/byte counts x per-device
coefficients calibrated from measured rounds.

Prices any ``FederatedPlan`` WITHOUT running it, on two axes:

- ``cfmq_tb`` — the paper's cost metric is *exactly* predictable from
  the plan + param shapes (wire accounting is arithmetic over leaf
  sizes; see :func:`point_cfmq_tb`, which mirrors the sweep runner's
  accounting term for term). Full-participation plans predict the
  measured row bit-for-bit; partial participation predicts the
  expectation of the sampled cohort size.
- ``seconds`` — wall time needs the device. A round's static cost
  features (FLOPs, HBM bytes, wire bytes, server steps) map to seconds
  through per-device coefficients fit by non-negative least squares
  over measured rounds (:func:`calibrate`), the byteprofile replayer
  idea with the repo's own HLO cost model as the DAG side. Two feature
  sources share one coefficient shape: ``hlo`` (exact counts from
  ``launch/hlo_cost`` over the compiled round step — used when a
  lowering is in hand) and ``analytic`` (closed-form over the plan +
  param count — no compilation, which is what lets the sweep pruner
  run before anything compiles).

``predict_report`` is the calibrate->predict loop behind
``python -m repro.launch.roofline --predict``: measure the five
tiny-RNN-T acceptance plans (fp32 / int8 / int4_packed / top5 /
async), fit both coefficient sources, persist them to tuning.json, and
report per-plan relative error. The documented in-sample tolerance is
:data:`PREDICT_REL_TOL`; CI captures the report and a warn-only drift
step compares runs over time.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import numpy as np

FEATURE_KEYS = ("flops", "hbm_bytes", "wire_bytes", "ici_bytes", "server_steps", "overhead")

# Documented tolerance for predicted-vs-measured round seconds on the
# calibration plans (asserted by tests and the roofline --strict path).
# The five plans share ~identical client compute — only the
# compression plane differs — so the fit's residual is dominated by
# the measured side's scatter across near-equal-cost graphs; on a
# quiet host the in-sample max lands ~0.1-0.3, and 0.5 gives the
# shared-2-core-CI measured side room without letting an
# order-of-magnitude modeling error through.
PREDICT_REL_TOL = 0.5

# Uncalibrated fallback (rough CPU-host magnitudes): lets the pruner
# rank plans before any trace exists on this device. Rankings only —
# absolute seconds from these are fiction until calibrated.
DEFAULT_COEFFS = {
    "flops": 2e-10,
    "hbm_bytes": 5e-11,
    "wire_bytes": 1e-9,
    "ici_bytes": 2e-11,  # ~ICI_BW magnitude; 0 on 1-device layouts
    "server_steps": 1e-3,
    "overhead": 5e-3,
}


def abstract_params(bundle, seed: int = 0):
    """Param tree as ShapeDtypeStructs — byte-exact wire accounting
    with zero allocation (predict plans you could never fit)."""
    return jax.eval_shape(bundle.init, jax.random.PRNGKey(seed))


def _n_params(params) -> int:
    return sum(int(l.size) for l in jax.tree_util.tree_leaves(params))


def expected_server_steps(plan) -> float:
    """Server-optimizer applications per round: the sync barrier takes
    one; the buffered-async engine flushes ~K*participation/B waves."""
    k = plan.clients_per_round
    if plan.engine != "async":
        return 1.0
    buffer = plan.asynchrony.resolve_buffer(k)
    return max(1.0, k * plan.cohort.participation / buffer)


def plan_round_features(plan, params, steps: int, client_shards: int = 1) -> dict:
    """Closed-form static cost features for one round — no compilation.

    ``flops`` uses the 6*N*examples fwd+bwd rule of thumb and
    ``hbm_bytes`` charges param+grad+optimizer traffic per local step;
    both are proportional, not exact — the per-device coefficients
    absorb the constants, the features only need to scale correctly
    across plans. ``wire_bytes`` IS exact (same accounting the CFMQ
    axis uses).

    With ``client_shards`` > 1 (the round's client axis sharded over a
    ``clients`` mesh, see ``core.fedavg.ClientSharding``) the compute
    features become PER-SHARD (the critical path is one shard's
    K/shards clients) and ``ici_bytes`` prices the collectives the
    sharded round adds: a ring all-reduce moves ``2*(S-1)/S`` of the
    payload per device, and the round's reductions (code-sum psum /
    delta gather + scale pmax) are params-tree-sized, so ``4*n_params``
    stands in for the payload. On 1 device the column is exactly 0 —
    unsharded calibration zeroes its NNLS coefficient and every
    unsharded prediction is untouched."""
    from repro.core.cfmq import plan_wire_accounting

    n_params = _n_params(params)
    shards = max(1, int(client_shards))
    k = plan.clients_per_round
    up, down = plan_wire_accounting(plan, params)
    expected_clients = k * plan.cohort.participation
    examples = k * steps * plan.local_batch_size
    ici = 0.0 if shards == 1 else 2.0 * (shards - 1) / shards * 4.0 * n_params
    return {
        "flops": 6.0 * n_params * examples / shards,
        "hbm_bytes": 4.0 * n_params * (3.0 * k * steps + 2.0 * k + 2.0) / shards,
        "wire_bytes": float(down) + float(up) * expected_clients,
        "ici_bytes": ici,
        "server_steps": expected_server_steps(plan),
        "overhead": 1.0,
    }


def hlo_round_features(
    hlo_analysis: dict, plan, params, steps: int, client_shards: int = 1
) -> dict:
    """Same feature shape, with FLOPs/HBM bytes taken from the HLO
    cost model's walk of the compiled round step (``hlo_cost.analyze``
    output) instead of the closed form. The compiled module is already
    per-shard under a sharded lowering, so only the analytic fallback
    divides by ``client_shards``."""
    feats = plan_round_features(plan, params, steps, client_shards)
    feats["flops"] = float(hlo_analysis["flops"])
    feats["hbm_bytes"] = float(hlo_analysis["bytes"])
    return feats


def feature_vector(features: dict) -> np.ndarray:
    # Missing keys read as 0 so feature dicts persisted before a key was
    # added (e.g. pre-sharding traces without ici_bytes) stay loadable.
    return np.array([float(features.get(k, 0.0)) for k in FEATURE_KEYS], dtype=np.float64)


# -------------------------------------------------------- calibration


def nnls(x: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Non-negative least squares by active-set elimination: solve the
    unconstrained problem, drop the most-negative coefficient from the
    support, repeat. Deterministic; exact whenever the unconstrained
    solution is already non-negative (the well-posed calibration case).
    Negative coefficients would let collinear features (all five
    acceptance plans share client compute) flip the pruner's cost
    ranking — a nonsense like "more wire bytes makes rounds faster"
    must round to a zero coefficient instead."""
    x = np.asarray(x, dtype=np.float64)
    y = np.asarray(y, dtype=np.float64)
    coef = np.zeros(x.shape[1])
    support = list(range(x.shape[1]))
    while support:
        sol, *_ = np.linalg.lstsq(x[:, support], y, rcond=None)
        if (sol >= -1e-12).all():
            coef[support] = np.clip(sol, 0.0, None)
            return coef
        support.pop(int(np.argmin(sol)))
    return coef


def calibrate(samples: list[tuple[dict, float]]) -> dict:
    """Fit per-device coefficients from (features, measured_seconds)
    samples — trace records or fresh measurements. Returns a coeffs
    dict over FEATURE_KEYS (>= 0 each)."""
    if not samples:
        raise ValueError("calibrate needs at least one (features, seconds) sample")
    x = np.stack([feature_vector(f) for f, _ in samples])
    y = np.array([float(s) for _, s in samples])
    # column scaling: feature magnitudes span ~12 decades (flops vs
    # overhead); normalize for lstsq conditioning, undo after
    scale = np.maximum(np.abs(x).max(axis=0), 1e-30)
    coef = nnls(x / scale, y) / scale
    return dict(zip(FEATURE_KEYS, (float(c) for c in coef)))


def predict_round_seconds(features: dict, coeffs: Optional[dict] = None) -> float:
    coeffs = coeffs or DEFAULT_COEFFS
    return float(
        sum(float(coeffs.get(k, 0.0)) * float(features.get(k, 0.0)) for k in FEATURE_KEYS)
    )


# ------------------------------------------------------- point pricing


def point_cfmq_tb(plan, params, steps: int, rounds: int) -> float:
    """Predicted CFMQ terabytes for a sweep point — mirrors
    ``SweepRunner.run_point``'s accounting exactly, with the expected
    cohort size standing in for the measured participant mean (equal
    at full participation, the expectation otherwise)."""
    from repro.core.cfmq import cfmq, measured_payload

    n_params = _n_params(params)
    expected_clients = plan.clients_per_round * plan.cohort.participation
    payload = measured_payload(plan, params, expected_clients)
    mu = plan.local_epochs * (plan.data_limit or steps * plan.local_batch_size)
    terms = cfmq(
        rounds=rounds,
        clients_per_round=plan.clients_per_round,
        model_bytes=n_params * plan.param_bytes,
        local_steps=mu / plan.local_batch_size,
        alpha=plan.alpha,
        payload_bytes=payload,
    )
    return terms.total_terabytes


def predict_point(
    plan, params, steps: int, rounds: int, coeffs: Optional[dict] = None
) -> dict:
    """Everything the planner needs about a sweep point, without
    running it: per-round seconds, whole-point seconds, the CFMQ cost
    axis, and the compression scheme's wire profile."""
    from repro.core.compression import wire_cost_profile

    feats = plan_round_features(plan, params, steps)
    round_s = predict_round_seconds(feats, coeffs)
    return {
        "round_s": round_s,
        "point_s": rounds * round_s,
        "cfmq_tb": point_cfmq_tb(plan, params, steps, rounds),
        "features": feats,
        "wire": wire_cost_profile(plan.compression, params),
    }


# ------------------------------------------- calibrate->predict report


def tiny_rnnt_plans() -> dict:
    """The five acceptance plans (the compression smoke schemes plus
    the buffered-async engine) on the tiny-RNN-T bench base."""
    from repro.core import AsyncConfig, CompressionConfig, FederatedPlan, LatencyConfig

    base = dict(
        clients_per_round=8,
        local_batch_size=4,
        data_limit=4,
        local_steps=12,
        client_lr=0.3,
        server_lr=0.05,
        server_warmup_rounds=4,
    )
    return {
        "fp32": FederatedPlan(**base),
        "int8": FederatedPlan(**base, compression=CompressionConfig(kind="int8")),
        "int4_packed": FederatedPlan(
            **base, compression=CompressionConfig(kind="int4", packed=True)
        ),
        "top5": FederatedPlan(
            **base, compression=CompressionConfig(kind="topk", topk_frac=0.05)
        ),
        "async": FederatedPlan(
            **{**base, "server_lr": 0.05 * 5 / 8},
            engine="async",
            asynchrony=AsyncConfig(buffer_size=5),
            latency=LatencyConfig(enabled=True, base_s=60.0, spread=0.35),
        ),
    }


def predict_report(
    reps: int = 3,
    seed: int = 0,
    plans: Optional[dict] = None,
    persist_coeffs: bool = True,
    trace_path: Optional[str] = None,
    log: Callable = print,
) -> dict:
    """Measure the acceptance plans' round time, fit both coefficient
    sources, report per-plan predicted-vs-measured relative error.

    In-sample by design: the report documents how well the feature
    model can explain THIS device (tolerance ``PREDICT_REL_TOL``);
    cross-run drift is what the CI warn-only step watches via the
    persisted report JSON."""
    from repro.core import build_round_engine
    from repro.core.engine import structural_key_str
    from repro.data import FederatedSampler
    from repro.launch import hlo_cost
    from repro.launch.train import tiny_asr_setup
    from repro.models import build_model
    from repro.profile import trace as trace_mod
    from repro.profile import tuner

    plans = plans or tiny_rnnt_plans()
    cfg, corpus = tiny_asr_setup(seed)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(seed))
    base_key = jax.random.PRNGKey(seed + 1)

    prepared = {}
    fns = {}
    for name, plan in plans.items():
        engine = build_round_engine(plan, bundle.loss_fn)
        steps = FederatedSampler.natural_steps(
            corpus,
            plan.local_batch_size,
            data_limit=plan.data_limit,
            local_epochs=plan.local_epochs,
            max_steps=plan.local_steps,
        )
        sampler = FederatedSampler(
            corpus,
            clients_per_round=plan.clients_per_round,
            local_batch_size=plan.local_batch_size,
            data_limit=plan.data_limit,
            local_epochs=plan.local_epochs,
            seed=seed,
            steps=steps,
        )
        batch = jax.tree.map(jax.numpy.asarray, sampler.next_round().engine_batch())
        state = engine.init_state(params)
        hypers = engine.hypers()
        log(f"[predict] compiling {name} ({structural_key_str(engine.structural_key)})")
        compiled = jax.jit(engine.hyper_step).lower(state, batch, hypers, base_key).compile()
        analysis = hlo_cost.analyze(compiled.as_text())
        prepared[name] = {
            "plan": plan,
            "steps": steps,
            "structural_key": structural_key_str(engine.structural_key),
            "analytic": plan_round_features(plan, params, steps),
            "hlo": hlo_round_features(analysis, plan, params, steps),
            "unparsed_ops": analysis["unparsed_ops"],
        }
        fns[name] = (lambda c=compiled, a=(state, batch, hypers, base_key): c(*a))

    measured = trace_mod.measure_interleaved_min(fns, reps=reps)

    coeffs = {
        source: calibrate([(prepared[n][source], measured[n]) for n in plans])
        for source in ("analytic", "hlo")
    }
    rows = []
    for name in plans:
        row = {
            "plan": name,
            "structural_key": prepared[name]["structural_key"],
            "measured_s": measured[name],
            "unparsed_ops": prepared[name]["unparsed_ops"],
        }
        for source in ("analytic", "hlo"):
            pred = predict_round_seconds(prepared[name][source], coeffs[source])
            row[f"predicted_{source}_s"] = pred
            row[f"rel_err_{source}"] = abs(pred - measured[name]) / max(measured[name], 1e-12)
        rows.append(row)
    report = {
        "schema_version": 1,
        "device_key": trace_mod.device_key(),
        "reps": reps,
        "tolerance": PREDICT_REL_TOL,
        "coefficients": coeffs,
        "rows": rows,
        "max_rel_err": {
            source: max(r[f"rel_err_{source}"] for r in rows) for source in ("analytic", "hlo")
        },
    }
    if persist_coeffs:
        reg = tuner.registry()
        for source, c in coeffs.items():
            reg.set_coefficients(source, c)
        reg.save()
        log(f"[predict] coefficients (analytic+hlo) -> {reg.path}")
    if trace_path:
        trace_mod.write_trace(
            trace_path,
            "predict",
            kernels={f"round_{n}": measured[n] * 1e6 for n in plans},
            counters={"reps": reps, "n_plans": len(plans)},
            meta={"rows": rows, "coefficients": coeffs},
        )
        log(f"[predict] trace -> {trace_path}")
    return report
