#!/usr/bin/env python3
"""Bring-up smoke: the paper's federated RNN-T round on a TPU chip.

Drives ``rnnt-librispeech`` at its published widths (8x1152 LSTM
encoder, 2x1152 prediction network, joint 640, 4096 word-pieces, 128
log-mel bins, bf16 compute; 105,333,760 parameters from a seeded random
init) through the same entry points ``launch.train --preset full``
uses, in one process:

  (a) kernel parity on the chip: the fused LSTM gate kernel (forward and
      custom-VJP backward) against the jnp cell at H=1152, the fused RNN-T
      joint (forward and gradient) against the float32 dense joint at J=640,
      V=4096, and the keyed int8 / int4 quantize kernels against the
      ``kernels.ref`` hash, bit-exact at n = 1152*4608 (one ``w_hh``);
  (b) three fp32 FedAvg rounds through ``run_federated`` (K=8 clients,
      local batch 4): finite losses, exact wire bytes;
  (c) one int8 round, which takes the code-domain fast path, so the
      keyed quantize kernel runs inside the round.

``--chips 4`` runs only the ``clients``-mesh comparison instead: one
K=8 round sharded over 4 chips against the unsharded round on one chip
of the same process (int8 server state bitwise equal; fp32 within the
sharded-round tests' tolerance).

Earlier lines print compile seconds, seconds per steady round,
``n_params``, losses and peak device memory: a bring-up record, not
metrics. The last line is the JSON result; any failed phase exits
non-zero before printing it. Without a TPU it exits non-zero at once.

Usage:
    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: sharded vs unsharded
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.core import (  # noqa: E402  (src/ joins sys.path first)
    CompressionConfig,
    FederatedPlan,
    build_round_engine,
    plan_wire_accounting,
    round_wire_bytes,
)
from repro.core.fedavg import ClientSharding  # noqa: E402
from repro.core.task import arch_task  # noqa: E402
from repro.data import FederatedSampler  # noqa: E402
from repro.kernels import ref, wire_pack  # noqa: E402
from repro.kernels.lstm_gates import lstm_gates_fused_vjp  # noqa: E402
from repro.kernels.rnnt_joint import rnnt_joint  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_federated_mesh  # noqa: E402
from repro.launch.train import run_federated  # noqa: E402
from repro.models.lstm import _fused_tile, lstm_gates  # noqa: E402

ARCH = "rnnt-librispeech"
N_PARAMS = 105_333_760
H = 1152
CLIENTS, LOCAL_BATCH, DATA_LIMIT, MAX_LABEL_LEN = 8, 4, 8, 40
OUT = ROOT / "results" / "chip_smoke"  # round traces (git-ignored)
FP32_REL_L2 = 1e-4  # sharded vs 1-chip fp32 round, per state leaf (see phase_mesh)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def _max_diff(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))


def _close(name: str, got, want, atol: float, rtol: float) -> None:
    g, w = np.asarray(got, np.float32), np.asarray(want, np.float32)
    bad = int(np.sum(~np.isclose(g, w, atol=atol, rtol=rtol)))
    log(f"  {name}: max |diff| {_max_diff(g, w):.3e}, {bad} outside atol={atol} rtol={rtol}")
    check(bad == 0, f"{name}: {bad} elements differ from the reference")


def _rel_change_diff(a, b, before) -> float:
    """||a - b|| / ||b - before||: how far two end states of one round
    differ, relative to how far the round moved the state."""
    a, b, z = (np.asarray(v, np.float64) for v in (a, b, before))
    moved = np.linalg.norm(b - z)
    gap = np.linalg.norm(a - b)
    return float(gap / moved) if moved > 0 else (0.0 if gap == 0 else math.inf)


def _runs_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


# ----------------------------------------------------------- phase (a)


def phase_kernels() -> None:
    log(f"(a) kernel parity on the chip, H={H}")
    th = _fused_tile(H)
    for B in (1, 4, 8):
        kg, kc, kh, kd = jax.random.split(jax.random.PRNGKey(B), 4)
        gates = (2.0 * jax.random.normal(kg, (B, 4 * H))).astype(jnp.bfloat16)
        c = jax.random.normal(kc, (B, H), jnp.float32)
        dh = jax.random.normal(kh, (B, H)).astype(jnp.bfloat16)
        dc = jax.random.normal(kd, (B, H), jnp.float32)
        kern = lambda g, c_: lstm_gates_fused_vjp(g, c_, th=th)  # noqa: E731

        def grad_of(fn):
            return lambda g, c_: jax.vjp(fn, g, c_)[1]((dh, dc))

        check(_runs_kernel(kern, gates, c) and _runs_kernel(grad_of(kern), gates, c),
              "lstm_gates_fused_vjp compiled without its kernel")
        (hk, ck), (hr, cr) = jax.jit(kern)(gates, c), jax.jit(lstm_gates)(gates, c)
        _close(f"B={B} h (bf16)", hk, hr, atol=2**-7, rtol=2**-7)
        _close(f"B={B} c (f32)", ck, cr, atol=1e-4, rtol=1e-4)
        (dgk, dck) = jax.jit(grad_of(kern))(gates, c)
        (dgr, dcr) = jax.jit(grad_of(lstm_gates))(gates, c)
        _close(f"B={B} dgates (bf16)", dgk, dgr, atol=2**-6, rtol=2**-7)
        _close(f"B={B} dc_prev (f32)", dck, dcr, atol=1e-4, rtol=1e-4)

    _joint_parity()

    n = H * 4 * H
    x = jax.random.normal(jax.random.PRNGKey(7), (n,), jnp.float32)
    key = jax.random.PRNGKey(1234)
    u = ref.threefry_uniform_ref(key, n)
    # power-of-two scales (grid edge near 4 sigma) make x / scale exact on
    # every backend, so the comparison isolates the in-kernel hash
    for bits, scale, kernel in (
        (8, jnp.float32(2.0**-5),
         lambda x_, s, k: wire_pack.quantize_with_scale_keyed_pallas(x_, s, k, 8)),
        (4, jnp.float32(2.0**-1), wire_pack.quantize_pack4_keyed_pallas),
    ):
        check(_runs_kernel(kernel, x, scale, key), f"int{bits} keyed quantize has no kernel")
        got = np.asarray(jax.jit(kernel)(x, scale, key))
        want = np.asarray(jax.jit(ref.quantize_pack_ref, static_argnums=3)(x, scale, u, bits))
        bad = int(np.sum(got != want))
        log(f"  keyed int{bits} quantize, n={n}: {bad} of {got.size} bytes differ from ref")
        check(got.shape == want.shape and bad == 0, f"keyed int{bits} kernel != ref hash")


def _joint_parity() -> None:
    """The fused joint at the config's widths, bf16 operands, on frame
    counts that leave whole tiles past two utterances: log-probs at every
    frame held, and the gradient of a loss that reads only those, against
    the dense joint in float32 on the same rounded inputs."""
    B, T, U1, J, V = 3, 64, 61, 640, 4096
    ke, kg, kw, kb, kl, kc = jax.random.split(jax.random.PRNGKey(18), 6)
    e = (0.5 * jax.random.normal(ke, (B, T, J))).astype(jnp.bfloat16)
    g = (0.5 * jax.random.normal(kg, (B, U1, J))).astype(jnp.bfloat16)
    w = jax.random.normal(kw, (J, V), jnp.float32) * J ** -0.5
    b = 0.1 * jax.random.normal(kb, (V,), jnp.float32)
    lbl = jax.random.randint(kl, (B, U1), 1, V)
    frames = jnp.array([T, 29, 1])
    live = (jnp.arange(T)[None, :, None] < frames[:, None, None]).astype(jnp.float32)
    cts = tuple(jax.random.normal(kc, (2, B, T, U1)) * live)

    def kern(e, g, w, b):
        return rnnt_joint(e, g, w, b, lbl, frames)

    def dense(e, g, w, b):
        return ref.rnnt_joint_ref(e.astype(jnp.float32), g.astype(jnp.float32),
                                  w.astype(jnp.bfloat16).astype(jnp.float32), b, lbl)

    def run(fn):
        out, vjp = jax.vjp(fn, e, g, w, b)
        return out, vjp(cts)

    check(_runs_kernel(lambda *a: run(kern), e), "rnnt_joint compiled without its kernel")
    got = jax.jit(lambda: run(kern))()
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda: run(dense))()
    for name, k, r in zip(("blank_lp", "label_lp"), got[0], want[0]):
        _close(f"joint {name} (f32)", k * live, r * live, atol=2e-2, rtol=0)
    for name, k, r in zip(("de", "dg", "dW", "db"), got[1], want[1]):
        scale = float(jnp.max(jnp.abs(r)))
        _close(f"joint {name} / max |{name}|", k.astype(jnp.float32) / scale, r / scale,
               atol=2e-2, rtol=0)


# ------------------------------------------------------- phases (b), (c)


def _full_task():
    task = arch_task(ARCH, full=True)
    t0 = time.perf_counter()
    corpus = task.make_corpus(0, max_label_len=MAX_LABEL_LEN)
    cfg = task.bundle.config
    log(f"corpus: {corpus.num_speakers} speakers, feat_dim={corpus.cfg.feat_dim}, "
        f"vocab={corpus.cfg.vocab_size}, <= {corpus.u_max} labels / {corpus.t_max} frames "
        f"({time.perf_counter() - t0:.1f}s to build)")
    check(corpus.cfg.feat_dim == cfg.feat_dim and corpus.cfg.vocab_size == cfg.vocab,
          "corpus is not at the config's widths")
    return task, corpus


def _plan(kind: str):
    return FederatedPlan(clients_per_round=CLIENTS, local_batch_size=LOCAL_BATCH,
                         data_limit=DATA_LIMIT, compression=CompressionConfig(kind=kind))


def _round_lowering_has(task, corpus, plan, marker: str) -> bool:
    """Lower the plan's round step at this task's shapes and look for
    ``marker`` (a kernel's name) in the program."""
    engine = build_round_engine(plan, task, base_key=jax.random.PRNGKey(1))
    params = jax.eval_shape(task.bundle.init, jax.random.PRNGKey(0))
    state = jax.eval_shape(engine.init_state, params)
    sampler = FederatedSampler(corpus, CLIENTS, LOCAL_BATCH, data_limit=DATA_LIMIT, seed=0)
    batch = jax.tree.map(jnp.asarray, sampler.next_round().engine_batch())
    return marker in jax.jit(engine.step).lower(state, batch).as_text()


def phase_rounds(task, corpus, kind: str, rounds: int) -> None:
    plan = _plan(kind)
    trace = OUT / f"trace_{kind}.json"
    t0 = time.perf_counter()
    _, hist = run_federated(task, corpus, plan, rounds, eval_every=0, eval_examples=8,
                            trace_path=str(trace), log=log)
    wall = time.perf_counter() - t0
    losses = hist["loss"]
    log(f"  n_params={hist['n_params']} losses={losses} {task.quality_metric}="
        f"{hist['quality']:.3f} wall={wall:.1f}s (incl. compile and eval)")
    sec = json.loads(trace.read_text())["sections"]["round"]
    steady = sec["min_s"] if rounds > 1 else float("nan")
    first = sec["total_s"] - (rounds - 1) * steady if rounds > 1 else sec["total_s"]
    log(f"  first round (compile + run) {first:.2f}s"
        + (f"; steady round {steady:.3f}s (min of {rounds - 1})" if rounds > 1 else ""))
    check(hist["n_params"] == N_PARAMS, f"n_params {hist['n_params']} != {N_PARAMS}")
    check(len(losses) == rounds and all(math.isfinite(v) for v in losses),
          f"non-finite losses {losses}")
    params = jax.eval_shape(task.bundle.init, jax.random.PRNGKey(0))
    up, down = plan_wire_accounting(plan, params)
    want = rounds * round_wire_bytes(up, down, CLIENTS)
    log(f"  wire_bytes_total={hist['wire_bytes_total']} (accounting x rounds: {want})")
    check(hist["wire_bytes_total"] == want, "wire bytes differ from the plan accounting")


# ----------------------------------------------------------- --chips 4


def phase_mesh(task, corpus, chips: int) -> None:
    log(f"clients mesh: K={CLIENTS} over {chips} chips vs the unsharded round on one chip")
    sharding = ClientSharding(make_federated_mesh(chips))
    params = task.bundle.init(jax.random.PRNGKey(0))
    sampler = FederatedSampler(corpus, CLIENTS, LOCAL_BATCH, data_limit=DATA_LIMIT, seed=0)
    batch = jax.tree.map(jnp.asarray, sampler.next_round().engine_batch())
    for kind in ("fp32", "int8"):
        plan = _plan("none" if kind == "fp32" else kind)
        base = build_round_engine(plan, task, base_key=jax.random.PRNGKey(1))
        shard = build_round_engine(plan, task, base_key=jax.random.PRNGKey(1),
                                   client_sharding=sharding)
        s0 = base.init_state(params)
        out = {}
        for name, engine in (("1 chip", base), (f"{chips} chips", shard)):
            step = jax.jit(engine.step)
            t0 = time.perf_counter()
            jax.block_until_ready(step(s0, batch))
            t1 = time.perf_counter()
            state, metrics = jax.block_until_ready(step(s0, batch))
            t2 = time.perf_counter()
            out[name] = (state, metrics)
            log(f"  {kind} {name}: first call (compile + run) {t1 - t0:.1f}s, "
                f"round {t2 - t1:.3f}s, loss {float(metrics['loss'])!r}")
        (sa, ma), (sb, mb) = out.values()
        la, lb, l0 = jax.tree.leaves(sa), jax.tree.leaves(sb), jax.tree.leaves(s0)
        diff = max(_max_diff(a, b) for a, b in zip(la, lb))
        check(math.isfinite(float(ma["loss"])) and math.isfinite(float(mb["loss"])),
              f"{kind}: non-finite loss")
        if kind == "int8":
            equal = all(np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(la, lb))
            log(f"  int8 server state bitwise equal: {equal} (max |diff| {diff:.3e})")
            check(equal, "int8 sharded server state differs from the 1-chip round")
            continue
        # fp32 sums the K deltas in another order (per-shard partial sums +
        # psum), and the server's Adam step can magnify that f32 rounding
        # where the aggregate is near zero: compare each leaf's change over
        # the round in relative L2 instead of element by element
        rel = max(_rel_change_diff(a, b, z) for a, b, z in zip(la, lb, l0))
        log(f"  fp32 server state: max |diff| {diff:.3e}, worst leaf relative L2 "
            f"of the round's change {rel:.3e} (tolerance {FP32_REL_L2})")
        check(rel <= FP32_REL_L2, "fp32 sharded server state differs beyond tolerance")


# ---------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the clients-mesh comparison over four chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        log(f"chip_smoke: no TPU — JAX found {len(devices)} {platform} device(s)")
        return 2
    if len(devices) < args.chips:
        log(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU chips, found {len(devices)}")
        return 2

    log(f"device: {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {use_compile_cache()}")
    OUT.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    task, corpus = _full_task()
    if args.chips == 4:
        phase_mesh(task, corpus, args.chips)
    else:
        phase_kernels()
        log(f"(b) 3 fp32 rounds, K={CLIENTS}, b={LOCAL_BATCH}, data limit {DATA_LIMIT}")
        phase_rounds(task, corpus, "none", rounds=3)
        log("(c) one int8 round (code-domain fast path)")
        check(_round_lowering_has(task, corpus, _plan("int8"), "_quantize_keyed_kernel"),
              "the int8 round does not run the keyed quantize kernel")
        phase_rounds(task, corpus, "int8", rounds=1)
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {"platform": platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
