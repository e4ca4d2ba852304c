"""Profiling plane: trace schema round-trip, host spans, the
constant table behind ``get_knob``, the wire_pack backend dispatch and
the hlo_cost parser's robustness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.profile import trace, tuner


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
                      meta={"id": "t"})
    got = trace.load_trace(path)
    assert got["kind"] == "round"
    assert got["structural_key"] == "fedavg|adam"
    assert got["sections"]["round"]["count"] == 2
    assert set(got["sections"]["pack"]) == set(trace.SECTION_STAT_KEYS)
    assert got["counters"]["rounds"] == 2.0
    assert got["device_key"] == trace.device_key()
    assert got["schema_version"] == trace.TRACE_SCHEMA_VERSION
    assert "features" not in got


def test_trace_validate_rejects_bad_records():
    good = trace.trace_record("kernels", kernels={"k": 1.0})
    for kind in ("nonsense", "predict"):
        with pytest.raises(ValueError, match="kind"):
            trace.validate_trace({**good, "kind": kind})
    with pytest.raises(TypeError):
        trace.trace_record("round", features={"flops": 1.0})
    with pytest.raises(ValueError, match="schema_version"):
        trace.validate_trace({**good, "schema_version": 999})
    with pytest.raises(ValueError, match="missing keys"):
        trace.validate_trace({k: v for k, v in good.items() if k != "sections"})
    with pytest.raises(ValueError, match="stats must be exactly"):
        trace.validate_trace({**good, "sections": {"round": {"min_s": 0.1}}})


def test_load_trace_rejects_previous_schema(tmp_path):
    """A record of the schema before ``features`` was dropped does not
    load: its version is not this one."""
    import json

    old = {**trace.trace_record("round"), "schema_version": 1, "features": {"flops": 1.0}}
    path = tmp_path / "trace_round.json"
    path.write_text(json.dumps(old))
    with pytest.raises(ValueError, match="schema_version 1"):
        trace.load_trace(str(path))


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
# Named constants
# ----------------------------------------------------------------------

def test_tuner_defaults_and_unknown_knob():
    assert tuner.get_knob("prefetch.depth") == 2
    assert {name: tuner.get_knob(name) for name in tuner.KNOBS
            if name.startswith("bench.")} == {
        "bench.fed_reps": 5, "bench.fed_pair_reps": 6, "bench.wire_reps": 12,
        "bench.micro_reps": 5, "bench.pack_reps": 30}
    for gone in ("nope.missing", "lstm.scan_min_seq"):
        with pytest.raises(KeyError, match="unknown tuning knob"):
            tuner.get_knob(gone)


def test_bench_reps_env_wins_over_knob(monkeypatch):
    from benchmarks.common import bench_reps

    monkeypatch.delenv("REPRO_BENCH_FED_REPS", raising=False)
    assert bench_reps("REPRO_BENCH_FED_REPS", "bench.fed_reps") == 5
    monkeypatch.setenv("REPRO_BENCH_FED_REPS", "2")
    assert bench_reps("REPRO_BENCH_FED_REPS", "bench.fed_reps") == 2
    monkeypatch.setenv("REPRO_BENCH_FED_REPS", "0")
    assert bench_reps("REPRO_BENCH_FED_REPS", "bench.fed_reps") == 1


# ----------------------------------------------------------------------
# wire_pack kernels: chosen by backend, or forced in interpret mode
# ----------------------------------------------------------------------

@pytest.mark.parametrize("forced", [False, True], ids=["backend", "kernel"])
def test_wire_pack_dispatch_modes(monkeypatch, forced):
    from repro.kernels import wire_pack

    assert wire_pack._dispatch() == (True, False)     # the CPU takes the oracle
    if forced:
        monkeypatch.setattr(wire_pack, "_dispatch", lambda: (False, True))
    pallas, calls = wire_pack.nibble_pack_pallas, []

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return pallas(*args, **kwargs)

    monkeypatch.setattr(wire_pack, "nibble_pack_pallas", spy)
    codes = jnp.asarray(np.arange(32) % 16 - 8, jnp.int32)  # signed nibbles
    packed = wire_pack.nibble_pack(codes)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(wire_pack.ref.nibble_pack_ref(codes)))
    out = wire_pack.nibble_unpack(packed, 32)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))
    assert calls == ([{"interpret": True}] if forced else [])


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
