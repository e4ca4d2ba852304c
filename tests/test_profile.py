"""Profiling plane: trace schema round-trip, cost-predictor
calibration, tuner knob registry, wire_pack dispatch wiring and the
sweep-grid pruner's never-drop-pareto contract.

The slow-marked test at the bottom is the acceptance loop itself:
measure the five tiny-RNN-T plans, calibrate, and assert in-sample
predicted-vs-measured round seconds within the documented tolerance.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.profile import predict, trace, tuner


@pytest.fixture()
def tmp_registry(tmp_path):
    """Process-wide registry pointed at a tmp file; restored after."""
    reg = tuner.TuningRegistry(path=str(tmp_path / "tuning.json"))
    tuner.set_registry(reg)
    yield reg
    tuner.set_registry(None)


# ----------------------------------------------------------------------
# Trace schema
# ----------------------------------------------------------------------

def test_trace_write_load_round_trip(tmp_path):
    rec = trace.TraceRecorder()
    with rec.section("pack"):
        pass
    with rec.section("round"):
        pass
    with rec.section("round"):
        pass
    path = str(tmp_path / "trace_round.json")
    trace.write_trace(path, "round", structural_key="fedavg|adam",
                      sections=rec, counters={"rounds": 2},
                      features={"flops": 1.0}, meta={"id": "t"})
    got = trace.load_trace(path)
    assert got["kind"] == "round"
    assert got["structural_key"] == "fedavg|adam"
    assert got["sections"]["round"]["count"] == 2
    assert set(got["sections"]["pack"]) == set(trace.SECTION_STAT_KEYS)
    assert got["counters"]["rounds"] == 2.0
    assert got["device_key"] == trace.device_key()


def test_trace_validate_rejects_bad_records():
    good = trace.trace_record("kernels", kernels={"k": 1.0})
    with pytest.raises(ValueError, match="kind"):
        trace.validate_trace({**good, "kind": "nonsense"})
    with pytest.raises(ValueError, match="schema_version"):
        trace.validate_trace({**good, "schema_version": 999})
    with pytest.raises(ValueError, match="missing keys"):
        trace.validate_trace({k: v for k, v in good.items() if k != "sections"})
    with pytest.raises(ValueError, match="stats must be exactly"):
        trace.validate_trace({**good, "sections": {"round": {"min_s": 0.1}}})


def test_recorder_stats_and_wrap():
    rec = trace.TraceRecorder()
    for _ in range(2):
        with rec.section("work"):
            pass
    s = rec.stats()["work"]
    assert s["count"] == 2
    assert s["total_s"] >= s["min_s"] >= 0.0
    assert s["mean_s"] == pytest.approx(s["total_s"] / 2)


def test_host_spans_reach_the_profiler(tmp_path):
    """Recorder sections, the prefetch worker's copy and wait, and the
    sampler's packing are spans on the profiler's clock."""
    import glob

    from repro.data import FederatedSampler, PrefetchIterator
    from repro.launch.train import tiny_asr_setup

    _, corpus = tiny_asr_setup(0)
    sampler = FederatedSampler(corpus, 2, 2, data_limit=2, seed=0)
    rec = trace.TraceRecorder()

    def rounds():
        for _ in range(2):
            with rec.section("pack"):
                batch = sampler.next_round().engine_batch()
            yield batch

    jax.profiler.start_trace(str(tmp_path))
    try:
        with PrefetchIterator(rounds()) as it:
            assert len(list(it)) == 2
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    names = {ev.name for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:") for line in plane.lines for ev in line.events}
    want = {"repro.pack", "repro.prefetch.put", "repro.prefetch.wait",
            "repro.sampler.next_round", "repro.sampler.engine_batch"}
    assert want <= names
    assert rec.stats()["pack"]["count"] == 2


def test_measure_interleaved_min_visits_every_fn():
    counts = {"a": 0, "b": 0}

    def mk(name):
        def fn():
            counts[name] += 1
        return fn

    got = trace.measure_interleaved_min({"a": mk("a"), "b": mk("b")},
                                        reps=4, warmup=2)
    assert set(got) == {"a", "b"}
    assert all(v >= 0.0 and np.isfinite(v) for v in got.values())
    assert counts == {"a": 6, "b": 6}       # 2 warmup + 4 timed each


# ----------------------------------------------------------------------
# Calibration
# ----------------------------------------------------------------------

def test_nnls_exact_recovery():
    rng = np.random.default_rng(0)
    x = rng.uniform(0.5, 2.0, size=(12, 5))
    true = np.array([0.3, 0.0, 1.5, 0.2, 0.7])
    got = predict.nnls(x, x @ true)
    np.testing.assert_allclose(got, true, atol=1e-9)


def test_nnls_clamps_negative_directions_to_zero():
    # y decreases with the second column: unconstrained lstsq would go
    # negative, which would flip the pruner's cost ordering
    x = np.array([[1.0, 1.0], [2.0, 2.0], [3.0, 2.9]])
    y = np.array([1.0, 2.0, 3.05])
    got = predict.nnls(x, y)
    assert (got >= 0.0).all()


def test_calibrate_recovers_synthetic_coefficients():
    true = {"flops": 3e-10, "hbm_bytes": 2e-11, "wire_bytes": 1.5e-9,
            "server_steps": 2e-3, "overhead": 4e-3}
    rng = np.random.default_rng(1)
    samples = []
    for _ in range(20):
        f = {"flops": rng.uniform(1e9, 5e9),
             "hbm_bytes": rng.uniform(1e8, 9e8),
             "wire_bytes": rng.uniform(1e6, 4e7),
             "server_steps": rng.uniform(1.0, 3.0),
             "overhead": 1.0}
        samples.append((f, predict.predict_round_seconds(f, true)))
    got = predict.calibrate(samples)
    for k in predict.FEATURE_KEYS:
        # features absent from the samples (ici_bytes: unsharded runs)
        # are a zero column — NNLS must pin their coefficient to 0
        assert got[k] == pytest.approx(true.get(k, 0.0), rel=1e-6), k
    with pytest.raises(ValueError):
        predict.calibrate([])


def test_expected_server_steps():
    from repro.core import AsyncConfig, FederatedPlan

    sync = FederatedPlan(clients_per_round=8, local_batch_size=4)
    assert predict.expected_server_steps(sync) == 1.0
    a = FederatedPlan(clients_per_round=8, local_batch_size=4,
                      engine="async", asynchrony=AsyncConfig(buffer_size=5))
    assert predict.expected_server_steps(a) == pytest.approx(8 / 5)


def _fake_params():
    return {"w": np.zeros((64, 32), np.float32), "b": np.zeros((32,), np.float32)}


def _abstract_fake_params():
    return jax.eval_shape(lambda: jax.tree.map(jnp.asarray, _fake_params()))


def test_features_and_cfmq_identical_on_abstract_params():
    """The predictor's core property: ShapeDtypeStruct trees price
    byte-for-byte like materialized ones — zero-allocation planning."""
    from repro.core import CompressionConfig, FederatedPlan

    plan = FederatedPlan(clients_per_round=8, local_batch_size=4, data_limit=4,
                         compression=CompressionConfig(kind="int4"))
    real, abstract = _fake_params(), _abstract_fake_params()
    f_real = predict.plan_round_features(plan, real, steps=1)
    f_abs = predict.plan_round_features(plan, abstract, steps=1)
    assert f_real == f_abs
    assert (predict.point_cfmq_tb(plan, real, steps=1, rounds=6)
            == predict.point_cfmq_tb(plan, abstract, steps=1, rounds=6))


def test_point_cfmq_matches_sweep_arithmetic():
    """point_cfmq_tb mirrors SweepRunner.run_point term for term."""
    from repro.core import FederatedPlan
    from repro.core.cfmq import cfmq, measured_payload

    plan = FederatedPlan(clients_per_round=8, local_batch_size=4, data_limit=4)
    params = _fake_params()
    n_params = 64 * 32 + 32
    mu = plan.local_epochs * plan.data_limit
    expect = cfmq(rounds=6, clients_per_round=8,
                  model_bytes=n_params * plan.param_bytes,
                  local_steps=mu / plan.local_batch_size, alpha=plan.alpha,
                  payload_bytes=measured_payload(plan, params, 8.0))
    assert predict.point_cfmq_tb(plan, params, steps=1, rounds=6) == \
        expect.total_terabytes


def test_wire_cost_profile():
    from repro.core import CompressionConfig
    from repro.core.compression import client_wire_bytes, wire_cost_profile

    params = _fake_params()
    dense = wire_cost_profile(CompressionConfig(), params)
    assert dense["ratio"] == 1.0
    assert dense["uplink_bytes"] == dense["dense_bytes"] == 4 * (64 * 32 + 32)
    int4 = wire_cost_profile(CompressionConfig(kind="int4"), params)
    assert int4["uplink_bytes"] == client_wire_bytes(
        CompressionConfig(kind="int4"), params)
    assert int4["ratio"] > 6.0        # ~8x minus per-leaf scale overhead
    # abstract trees price identically
    assert wire_cost_profile(CompressionConfig(kind="int4"),
                             _abstract_fake_params()) == int4


# ----------------------------------------------------------------------
# Tuner registry
# ----------------------------------------------------------------------

def test_tuner_defaults_and_unknown_knob(tmp_registry):
    assert tuner.get_knob("lstm.scan_min_seq") == 16
    assert tuner.get_knob("wire_pack.dispatch") == "auto"
    with pytest.raises(KeyError, match="unknown tuning knob"):
        tuner.get_knob("nope.missing")
    with pytest.raises(KeyError):
        tmp_registry.set_override("nope.missing", 1)


def test_tuner_override_persist_round_trip(tmp_registry):
    tmp_registry.set_override("lstm.scan_min_seq", 1024, persist=True)
    tmp_registry.set_coefficients("analytic", {"flops": 1e-10}, persist=True)
    # a fresh registry over the same file sees both, keyed per device
    reloaded = tuner.TuningRegistry(path=tmp_registry.path)
    assert reloaded.get("lstm.scan_min_seq") == 1024
    assert reloaded.get_coefficients("analytic") == {"flops": 1e-10}
    assert reloaded.get_coefficients("hlo") is None
    reloaded.clear_override("lstm.scan_min_seq")
    assert reloaded.get("lstm.scan_min_seq") == 16
    doc = json.load(open(tmp_registry.path))
    assert trace.device_key() in doc["devices"]


def test_tuner_validation(tmp_registry):
    with pytest.raises(ValueError, match="not in"):
        tmp_registry.set_override("wire_pack.dispatch", "cuda")
    with pytest.raises(ValueError, match="positive"):
        tmp_registry.set_override("bench.fed_reps", 0)
    # numeric strings coerce (CLI path)
    assert tmp_registry.set_override("bench.fed_reps", "7") == 7


def test_tuner_corrupt_file_falls_back_to_defaults(tmp_path):
    path = tmp_path / "tuning.json"
    path.write_text("{broken")
    reg = tuner.TuningRegistry(path=str(path))
    assert reg.get("lstm.scan_min_seq") == 16


def test_tuner_env_path(tmp_path, monkeypatch):
    monkeypatch.setenv(tuner.ENV_PATH, str(tmp_path / "env_tuning.json"))
    reg = tuner.TuningRegistry()
    assert reg.path == str(tmp_path / "env_tuning.json")


def test_bench_reps_env_wins_over_knob(tmp_registry, monkeypatch):
    from benchmarks.common import bench_reps

    tmp_registry.set_override("bench.fed_reps", 9)
    assert bench_reps("REPRO_BENCH_FED_REPS", "bench.fed_reps") == 9
    monkeypatch.setenv("REPRO_BENCH_FED_REPS", "2")
    assert bench_reps("REPRO_BENCH_FED_REPS", "bench.fed_reps") == 2


# ----------------------------------------------------------------------
# wire_pack dispatch goes through the tuner
# ----------------------------------------------------------------------

def test_wire_pack_dispatch_modes(tmp_registry):
    from repro.kernels import wire_pack

    codes = jnp.asarray(np.arange(32) % 16 - 8, jnp.int32)  # signed nibbles
    for mode in ("auto", "ref", "pallas"):
        tmp_registry.set_override("wire_pack.dispatch", mode)
        packed = wire_pack.nibble_pack(codes)
        out = wire_pack.nibble_unpack(packed, 32)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))


# ----------------------------------------------------------------------
# Pruner
# ----------------------------------------------------------------------

def _rows(pareto_ids, all_ids, cfmq):
    return [{"id": i, "pareto": i in pareto_ids, "cfmq_tb": cfmq[i]}
            for i in all_ids]


def test_prune_report_and_check_pass():
    cfmq = {"a": 1.0, "b": 2.0, "c": 5.0}
    report = tuner.prune_report(cfmq, budget=3.0, axis="cfmq_tb")
    assert [report[i].keep for i in ("a", "b", "c")] == [True, True, False]
    assert report["c"].as_dict()["keep"] is False
    rows = _rows({"a"}, ("a", "b", "c"), cfmq)
    assert tuner.check_prune(rows, report, log=lambda *_: None) == 1


def test_check_prune_rejects_empty_drop_and_pareto_drop():
    cfmq = {"a": 1.0, "b": 2.0}
    nothing = tuner.prune_report(cfmq, budget=10.0, axis="cfmq_tb")
    with pytest.raises(AssertionError, match="dropped nothing"):
        tuner.check_prune(_rows({"a"}, ("a", "b"), cfmq), nothing,
                          log=lambda *_: None)
    report = tuner.prune_report(cfmq, budget=1.5, axis="cfmq_tb")
    with pytest.raises(AssertionError, match="PARETO"):
        tuner.check_prune(_rows({"a", "b"}, ("a", "b"), cfmq), report,
                          log=lambda *_: None)


def test_check_prune_rejects_prediction_drift():
    predicted = {"a": 1.0, "b": 3.0}
    measured = {"a": 1.0, "b": 2.0}        # b predicted 50% high
    report = tuner.prune_report(predicted, budget=2.5, axis="cfmq_tb")
    with pytest.raises(AssertionError, match="rel err"):
        tuner.check_prune(_rows({"a"}, ("a", "b"), measured), report,
                          log=lambda *_: None)


def test_check_prune_flags_missing_decision():
    report = tuner.prune_report({"a": 1.0}, budget=0.5, axis="cfmq_tb")
    with pytest.raises(AssertionError, match="no prune decision"):
        tuner.check_prune([{"id": "ghost", "pareto": False, "cfmq_tb": 1.0}],
                          report, log=lambda *_: None)


def test_compression_grid_prune_budget_drops_only_fp32(tmp_registry):
    """The CI configuration, verified without running anything: at
    budget 1e-4 TB the smoke compression grid loses exactly fp32, and
    every predicted cfmq_tb is exact arithmetic (machine-independent,
    so this asserts the values the sweep would measure)."""
    from repro.launch.sweeps import (SweepRunner, compression_points,
                                     predict_grid_costs)

    runner = SweepRunner(seed=0, eval_examples=24, pad_steps=True)
    points = compression_points(smoke=True)
    predicted = predict_grid_costs(runner, points, axis="cfmq_tb")
    report = tuner.prune_report(predicted, budget=1e-4, axis="cfmq_tb")
    assert {pid for pid, d in report.items() if not d.keep} == {"fp32"}
    assert predicted["fp32"] == pytest.approx(1.1043102720e-4)
    assert predicted["top5"] < predicted["int4"] < predicted["int8"]


# ----------------------------------------------------------------------
# hlo_cost robustness (satellite: malformed HLO degrades, not raises)
# ----------------------------------------------------------------------

def test_hlo_cost_counts_unparsed_ops():
    from repro.launch import hlo_cost

    text = """
HloModule m

ENTRY %main (p: f32[<=128,8]) -> f32[<=128,8] {
  %p = f32[<=128,8] parameter(0)
  %a = f32[<=128,8] add(%p, %p)
  ROOT %t = f32[<=128,8] tanh(%a)
}
"""
    got = hlo_cost.analyze(text)
    assert got["unparsed_ops"] == 3.0


def test_hlo_cost_garbage_degrades_not_raises():
    from repro.launch import hlo_cost

    assert hlo_cost.analyze("complete nonsense, no HLO here")["flops"] == 0.0
    text = """
HloModule m

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8] parameter(0)
  %bad = f32[8] dot(%p), lhs_contracting_dims=
  ROOT %a = f32[8] add(%p, %p)
}
"""
    got = hlo_cost.analyze(text)
    # the well-formed add is still priced: 8 flops
    assert got["flops"] >= 8.0


def test_hlo_cost_clean_module_has_zero_unparsed():
    from repro.launch import hlo_cost

    text = """
HloModule m

ENTRY %main (p: f32[64,32]) -> f32[64,32] {
  %p = f32[64,32] parameter(0)
  ROOT %a = f32[64,32] add(%p, %p)
}
"""
    got = hlo_cost.analyze(text)
    assert got["unparsed_ops"] == 0.0
    assert got["flops"] == 64 * 32


# ----------------------------------------------------------------------
# Structural key slug
# ----------------------------------------------------------------------

def test_structural_key_str_is_flat_and_deterministic():
    from repro.core import FederatedPlan, build_round_engine
    from repro.core.engine import structural_key_str

    plan = FederatedPlan(clients_per_round=8, local_batch_size=4)
    eng = build_round_engine(plan, lambda p, b, k: (jnp.float32(0.0), {}))
    slug = structural_key_str(eng.structural_key)
    assert slug == structural_key_str(eng.structural_key)
    assert "\n" not in slug and slug.startswith("fedavg|")
    assert "CompressionConfig(kind=none" in slug


# ----------------------------------------------------------------------
# The acceptance loop (slow): measure, calibrate, predict within tol
# ----------------------------------------------------------------------

@pytest.mark.slow
def test_predict_report_within_tolerance(tmp_registry, tmp_path):
    report = predict.predict_report(
        reps=3, trace_path=str(tmp_path / "trace_predict.json"),
        log=lambda *_: None)
    assert set(r["plan"] for r in report["rows"]) == {
        "fp32", "int8", "int4_packed", "top5", "async"}
    for source in ("analytic", "hlo"):
        assert report["max_rel_err"][source] <= report["tolerance"], source
    # compiled-graph pricing parsed every op of every acceptance plan
    assert all(r["unparsed_ops"] == 0.0 for r in report["rows"])
    # coefficients persisted to the (tmp) registry for the pruner
    assert tmp_registry.get_coefficients("analytic") is not None
    assert tmp_registry.get_coefficients("hlo") is not None
    got = trace.load_trace(str(tmp_path / "trace_predict.json"))
    assert got["kind"] == "predict"
