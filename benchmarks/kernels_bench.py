"""Micro-benchmarks of the Pallas kernel wrappers (interpret mode on
CPU — relative timings only; the jnp fallback is the CPU production
path) and the jnp blockwise implementations they target.

Measurement protocol (all benches): interleaved order-rotating reps
with per-variant MIN, via ``benchmarks.common.interleaved_min_us``
(the fed_round protocol, shared through ``repro.profile.trace``).
Rep counts come from the ``bench.*`` constants of
``repro.profile.tuner`` unless the ``REPRO_BENCH_*_REPS`` environment
pins them.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_reps, csv_row, interleaved_min_us
from repro.kernels import ref
from repro.models.attention import blockwise_attention


def bench_attention():
    rng = np.random.default_rng(0)
    B, S, H, Kv, D = 1, 1024, 8, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, Kv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, Kv, D)), jnp.float32)
    blockwise = jax.jit(lambda q, k, v: blockwise_attention(q, k, v, causal=True, block_kv=256))
    naive = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v, causal=True))
    t = interleaved_min_us({"block": lambda: blockwise(q, k, v),
                            "naive": lambda: naive(q, k, v)},
                           reps=bench_reps("REPRO_BENCH_MICRO_REPS",
                                           "bench.micro_reps"))
    print(csv_row("attention_blockwise_1k", t["block"],
                  f"naive_us={t['naive']:.1f}"))
    return t["block"], t["naive"]


def _joint_bench_case(seed):
    rng = np.random.default_rng(seed)
    B, T, U1, J, V = 4, 128, 24, 64, 512
    e = jnp.asarray(rng.normal(size=(B, T, J)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(B, U1, J)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(J, V)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.normal(size=(V,)) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.integers(0, V, (B, U1)), jnp.int32)
    return (B, T, U1, J, V), (e, g, w, b, lbl)


def bench_rnnt_joint():
    """The paper-model hot-spot: the chunked jnp joint (the CPU path) vs
    the naive materialized joint."""
    from repro.models.rnnt import joint_chunked

    (B, T, U1, J, V), (e, g, w, b, lbl) = _joint_bench_case(1)
    chunked = jax.jit(lambda *a: joint_chunked(*a))
    naive = jax.jit(lambda e, g, w, b, l: ref.rnnt_joint_ref(e, g, w, b, l))
    t = interleaved_min_us({"chunked": lambda: chunked(e, g, w, b, lbl),
                            "naive": lambda: naive(e, g, w, b, lbl)},
                           reps=bench_reps("REPRO_BENCH_MICRO_REPS",
                                           "bench.micro_reps"))
    # memory derived: naive materializes B*T*U1*V f32
    naive_bytes = B * T * U1 * V * 4
    chunk_bytes = B * T * 8 * V * 4
    print(csv_row("rnnt_joint_chunked", t["chunked"],
                  f"naive_us={t['naive']:.1f};mem_ratio={naive_bytes/chunk_bytes:.0f}x"))
    return t["chunked"], t["naive"]


def bench_rnnt_joint_bwd():
    """The joint's backward, with the forward it needs, at the same bench
    shapes: autodiff through the chunked jnp joint (the CPU path) vs the
    fused kernels (``kernels/rnnt_joint.py``; interpret mode here, so
    their time is only a relative number in the derived column). Every
    frame is held."""
    from repro.kernels.rnnt_joint import rnnt_joint
    from repro.models.rnnt import joint_chunked

    (B, T, U1, J, V), (e, g, w, b, lbl) = _joint_bench_case(3)
    rng = np.random.default_rng(3)
    cts = (jnp.asarray(rng.normal(size=(B, T, U1)), jnp.float32),
           jnp.asarray(rng.normal(size=(B, T, U1)), jnp.float32))
    frames = jnp.full((B,), T, jnp.int32)

    def backward(joint):
        def f(e, g, w, b, cts):
            _, vjp = jax.vjp(joint, e, g, w, b)
            return vjp(cts)
        return jax.jit(f)

    chunked = backward(lambda *a: joint_chunked(*a, lbl))
    pallas = backward(lambda *a: rnnt_joint(*a, lbl, frames, interpret=True))
    t = interleaved_min_us(
        {"chunked": lambda: chunked(e, g, w, b, cts),
         "pallas": lambda: pallas(e, g, w, b, cts)},
        reps=bench_reps("REPRO_BENCH_MICRO_REPS", "bench.micro_reps"))
    print(csv_row("rnnt_joint_bwd_chunked", t["chunked"],
                  f"pallas_us={t['pallas']:.1f};"
                  f"interp_ratio={t['pallas'] / max(t['chunked'], 1e-9):.2f}"))
    return t["chunked"], t["pallas"]


def _fed_round_setup():
    from repro.launch.train import tiny_asr_setup
    from repro.data import FederatedSampler
    from repro.models import build_model

    cfg, corpus = tiny_asr_setup(0)
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    s = FederatedSampler(corpus, 8, 4, seed=0)
    rb = s.next_round()
    batch = {"features": jnp.asarray(rb.features), "labels": jnp.asarray(rb.labels),
             "frame_len": jnp.asarray(rb.frame_len), "label_len": jnp.asarray(rb.label_len),
             "weight": jnp.asarray(rb.mask)}
    return bundle, params, batch


def _round_variants(base):
    from repro.core import AsyncConfig, CompressionConfig, FederatedPlan

    variants = [
        ("fed_round_tiny_rnnt", FederatedPlan(**base)),
        # buffered-async engine: same client compute, plus the arrival
        # scan + staleness-discounted buffer flushes (B=5 of K=8, the
        # async_vs_sync sweep's configuration)
        ("fed_round_tiny_rnnt_async",
         FederatedPlan(**base, engine="async",
                       asynchrony=AsyncConfig(buffer_size=5,
                                              staleness_beta=0.5))),
        # compression-only variants (weighted_mean) so the timings are
        # attributable to the quantize/sparsify plane alone. int8/int4
        # take the code-domain fast path (shared-scale codes, int32
        # code-sum reduction, one server dequant).
        ("fed_round_tiny_rnnt_int8",
         FederatedPlan(**base, compression=CompressionConfig(kind="int8"))),
        # top5 is PINNED to the generic per-client dense plane (the
        # pre-code-path graph, via _FORCE_GENERIC_PLANE below) so the
        # metric keeps measuring what it always measured; _top5_code is
        # the same plan on the code-domain fast path (segment-bucketed
        # scatter-add of packed {values, idx} wires). Their adjacent
        # pairing is the topk_code_le_topk never-flip flag.
        ("fed_round_tiny_rnnt_top5",
         FederatedPlan(**base, compression=CompressionConfig(kind="topk",
                                                             topk_frac=0.05))),
        ("fed_round_tiny_rnnt_top5_code",
         FederatedPlan(**base, compression=CompressionConfig(kind="topk",
                                                             topk_frac=0.05))),
        # packed-wire variants: materialize + round-trip the real
        # payload buffers (wire_pack kernels; bit-identical numerics)
        ("fed_round_tiny_rnnt_int8_packed",
         FederatedPlan(**base, compression=CompressionConfig(kind="int8",
                                                             packed=True))),
        ("fed_round_tiny_rnnt_int4_packed",
         FederatedPlan(**base, compression=CompressionConfig(kind="int4",
                                                             packed=True))),
        # EF21 error feedback: same wire bytes, per-client residual state
        ("fed_round_tiny_rnnt_top5_ef",
         FederatedPlan(**base, compression=CompressionConfig(
             kind="topk", topk_frac=0.05, error_feedback=True))),
    ]
    # uniform triples: (name, plan, client_sharding). The sharded
    # variants run the SAME plans through the shard_map body on a
    # 1-device `clients` mesh — the pure dispatch/partitioner overhead
    # of the sharded lowering, gated by the sharded_le_fp32 flag.
    from repro.core.fedavg import ClientSharding
    from repro.launch.mesh import make_federated_mesh

    sh = ClientSharding(make_federated_mesh(1))
    return [(n, p, None) for n, p in variants] + [
        ("fed_round_tiny_rnnt_sharded", FederatedPlan(**base), sh),
        ("fed_round_tiny_rnnt_sharded_int8",
         FederatedPlan(**base, compression=CompressionConfig(kind="int8")), sh),
    ]


# Variants whose round step is traced with the code-domain fast path
# DISABLED (repro.core.fedavg._code_fast_path pinned False during the
# compile call): the pre-fast-path generic graph, kept as the slow side
# of the topk_code_le_topk pairing.
_FORCE_GENERIC_PLANE = frozenset({"fed_round_tiny_rnnt_top5"})


def bench_fed_round():
    """Wall time of one jitted federated round at bench scale, plus the
    compressed/robust server-plane variants (bytes/round from the exact
    per-client accounting, clients=8).

    Measurement protocol: every variant is compiled first, then timed
    over ``REPRO_BENCH_FED_REPS`` (default 5) *interleaved* cycles
    whose per-cycle order rotates. The per-variant MINIMUM is reported
    as us_per_call (the noise floor each graph can reach), and the
    fp32-vs-compressed ordering flags use *paired within-cycle ratios*:
    each cycle divides a variant's time by the fp32 time of the SAME
    cycle — temporally adjacent, so shared-runner load drift cancels —
    and the flag takes the median over cycles against a documented
    ``_NOISE_MARGIN``. Sequential per-variant loops (the pre-PR 5
    protocol) made this ordering a coin flip: cross-variant load drift
    dwarfs the sub-percent differential that is actually left now that
    the code fast path removed the compression plane's compute tax
    (the PR 4 baseline had int4_packed at 1.4x fp32).

    Returns (times, flags): flags are the never-flip bench-gate claims
    that a quantized round costs at-or-under the fp32 round (within
    the paired-measurement noise floor; the raw median ratios are
    printed in the derived column and persisted next to the flags).
    """
    import statistics

    from repro.core import client_wire_bytes, init_server_state, make_round_step

    bundle, params, batch = _fed_round_setup()
    base = dict(clients_per_round=8, local_batch_size=4, client_lr=0.3)
    variants = _round_variants(base)
    import repro.core.fedavg as _fedavg_mod

    steps, states = {}, {}
    for name, plan, sharding in variants:
        states[name] = init_server_state(plan, params)
        steps[name] = jax.jit(make_round_step(bundle.loss_fn, plan,
                                              jax.random.PRNGKey(1),
                                              client_sharding=sharding))
        # the code-fast-path branch is taken at TRACE time, so pinning
        # a variant to the generic plane only needs the patch while the
        # first (compiling) call traces; later calls replay the graph
        orig_fast = _fedavg_mod._code_fast_path
        if name in _FORCE_GENERIC_PLANE:
            _fedavg_mod._code_fast_path = lambda plane: False
        try:
            states[name], m = steps[name](states[name], batch)   # compile
            jax.block_until_ready(m["loss"])
        finally:
            _fedavg_mod._code_fast_path = orig_fast
    reps = bench_reps("REPRO_BENCH_FED_REPS", "bench.fed_reps")
    cycle_times = {name: [] for name, _, _ in variants}

    def step_once(name):
        t0 = time.perf_counter()
        states[name], m = steps[name](states[name], batch)
        jax.block_until_ready(m["loss"])
        us = (time.perf_counter() - t0) * 1e6
        cycle_times[name].append(us)
        return us

    for rep in range(reps):
        order = variants[rep % len(variants):] + variants[:rep % len(variants)]
        for name, _, _ in order:
            step_once(name)
    # The ordering flags: ADJACENT fp32<->variant pairs (back-to-back
    # steps, so host-steal drift has ~one round step to move instead of
    # a whole cycle), median of the pair ratios.
    flags = {}
    pair_reps = max(3, bench_reps("REPRO_BENCH_FED_PAIR_REPS",
                                  "bench.fed_pair_reps"))
    # sharded_le_fp32 is the never-flip floor on the shard_map lowering
    # itself: a 1-device `clients` mesh must stay within the noise
    # margin (<= 1.1x) of the plain vmap round — the sharded body adds
    # dispatch/partitioning, never a second copy of the compute.
    for tag, name in [("int8", "fed_round_tiny_rnnt_int8"),
                      ("int4_packed", "fed_round_tiny_rnnt_int4_packed"),
                      ("sharded", "fed_round_tiny_rnnt_sharded"),
                      ("sharded_int8", "fed_round_tiny_rnnt_sharded_int8")]:
        ratios = []
        for _ in range(pair_reps):
            f = step_once("fed_round_tiny_rnnt")
            v = step_once(name)
            ratios.append(v / f)
        r = statistics.median(ratios)
        flags[f"{tag}_le_fp32"] = {
            "pass": r <= 1.0 + _NOISE_MARGIN,
            "vs_fp32_ratio": round(r, 4),
        }
    # topk_code_le_topk: the code-domain top-k round (packed
    # {values, idx} wires + segment-bucketed scatter-add) must stay
    # at-or-under the generic dense top-k plane it replaced — adjacent
    # slow<->code pairs, same protocol as the fp32 flags but with the
    # pinned-generic top5 graph as the denominator.
    ratios = []
    for _ in range(pair_reps):
        s = step_once("fed_round_tiny_rnnt_top5")
        c = step_once("fed_round_tiny_rnnt_top5_code")
        ratios.append(c / s)
    r = statistics.median(ratios)
    flags["topk_code_le_topk"] = {
        "pass": r <= 1.0 + _NOISE_MARGIN,
        "vs_topk_ratio": round(r, 4),
    }
    times = {name: min(ts) for name, ts in cycle_times.items()}
    ratio = {name: flags[f"{tag}_le_fp32"]["vs_fp32_ratio"]
             for tag, name in [("int8", "fed_round_tiny_rnnt_int8"),
                               ("int4_packed", "fed_round_tiny_rnnt_int4_packed"),
                               ("sharded", "fed_round_tiny_rnnt_sharded"),
                               ("sharded_int8", "fed_round_tiny_rnnt_sharded_int8")]}
    for name, plan, sharding in variants:
        up = 8 * client_wire_bytes(plan.compression, params)
        if name == "fed_round_tiny_rnnt_top5_code":
            derived = (f"vs_topk_ratio="
                       f"{flags['topk_code_le_topk']['vs_topk_ratio']};"
                       f"uplink_B_round={up}")
        elif name in ratio:
            derived = f"vs_fp32_ratio={ratio[name]};uplink_B_round={up}"
        elif plan.compression.kind == "none":
            derived = "clients=8"
        else:
            derived = f"uplink_B_round={up}"
        print(csv_row(name, times[name], derived))
    return times, flags


# The discrimination floor of shared 2-core runners: the int8 and
# int8_packed fast paths compile to the SAME HLO (the static packed
# bit only changes which wrapper builds the graph) yet their median
# adjacent-pair ratios vs fp32 still land up to ~8% apart under host
# CPU steal — no estimator at this wall-time budget can certify a
# sub-percent ordering. The flag therefore gates the claim that
# actually regressed before PR 5 and is measurable: a quantized round
# costs AT MOST fp32 + this band (the PR 4 baseline had int4_packed at
# 1.40x fp32 — a regression back to a real compute tax trips this
# immediately), while the strict sub-1.0 orderings show up in quiet-
# window runs (persisted as vs_fp32_ratio next to each flag) and in
# the stable plane-only wire_plane_*_speedup metrics.
_NOISE_MARGIN = 0.10


def bench_wire_plane():
    """The compression plane in isolation at bench-model shapes: the
    slow path (per-client quantize->dequantize, K fp32 trees reduced by
    the aggregator) vs the code-domain fast path (shared-scale fused
    quantize(+pack), int32 code-sum, ONE dequant). The full-round bench
    above buries this differential under local training; here it is the
    whole measurement — timed as mins over interleaved slow/fast reps
    (same rationale as ``bench_fed_round``) so the ``*_speedup`` ratios
    are stable enough for the bench gate's speedup-floor class."""
    from repro.core.aggregation import get_aggregator
    from repro.core.compression import (
        CompressionConfig, code_domain_aggregate, make_compressor)

    rng = np.random.default_rng(7)
    K = 8
    tree = {f"l{i}": jnp.asarray(rng.normal(size=(K, 256, 91)), jnp.float32)
            for i in range(8)}
    n_k = jnp.full((K,), 16.0)
    pmask = jnp.ones((K,))
    key = jax.random.PRNGKey(0)
    ckeys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(K))
    wm = get_aggregator("weighted_mean")

    times, speedups = {}, {}
    for tag, cfg in [("int8", CompressionConfig(kind="int8")),
                     ("int4_packed", CompressionConfig(kind="int4",
                                                       packed=True))]:
        comp = make_compressor(cfg)
        slow = jax.jit(lambda tr, c=comp: wm(jax.vmap(c)(tr, ckeys),
                                             n_k, pmask, {}, key))
        fast = jax.jit(lambda tr, c=cfg: code_domain_aggregate(
            c, tr, n_k, pmask, ckeys))
        t = interleaved_min_us({"slow": lambda: slow(tree),
                                "fast": lambda: fast(tree)},
                               reps=bench_reps("REPRO_BENCH_WIRE_REPS",
                                               "bench.wire_reps"))
        t_slow, t_fast = t["slow"], t["fast"]
        speedup = t_slow / max(t_fast, 1e-9)
        times[f"wire_plane_{tag}"] = t_fast
        speedups[f"{tag}_speedup"] = round(speedup, 2)
        print(csv_row(f"wire_plane_{tag}", t_fast,
                      f"slow_us={t_slow:.1f};fast_speedup={speedup:.2f}"))
    return times, speedups


def main(trace_path: str = "results/trace_kernels.json") -> tuple[dict, dict]:
    """Runs every micro-bench; returns (times, extra): {bench_name:
    us_per_call} plus the extra gated sections — the never-flip
    code-fast-path pass flags and the wire-plane fast-vs-slow speedups
    — so the harness can persist all of it for the CI regression
    gate. Per-kernel timings also land in a profiling-plane trace
    (``trace_path``; empty string disables)."""
    times = {}
    times["attention_blockwise_1k"], _ = bench_attention()
    times["rnnt_joint_chunked"], _ = bench_rnnt_joint()
    times["rnnt_joint_bwd_chunked"], _ = bench_rnnt_joint_bwd()
    plane_times, plane_speedups = bench_wire_plane()
    times.update(plane_times)
    round_times, flags = bench_fed_round()
    times.update(round_times)
    if trace_path:
        from repro.profile.trace import write_trace

        write_trace(trace_path, "kernels", kernels=times,
                    meta={"wire_plane": plane_speedups})
        print(f"[trace] {trace_path}")
    return times, {"code_fast_path": flags, "wire_plane": plane_speedups}


if __name__ == "__main__":
    main()
