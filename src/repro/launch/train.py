"""End-to-end federated training driver (the paper's experiment loop).

Runs federated rounds of any registered ``FederatedTask`` — the
paper's RNN-T, the enc-dec/LM/MoE/RWKV zoo tasks, or the
keyword-spotting tiny model — on the synthetic speaker-split corpus,
with the paper's knobs (data limit, FVN, server LR schedule), CFMQ
accounting per round, and the optional per-client evaluation plane.
Each round is one jitted step on the default device; ``--mesh-clients
N`` shards the round's client axis over N devices of a 1-D ``clients``
mesh (``core.fedavg.ClientSharding``). Nothing else is partitioned.

Usage:
    PYTHONPATH=src python -m repro.launch.train --task asr-rnnt --rounds 40
    PYTHONPATH=src python -m repro.launch.train --task keyword \
        --population 1000000 --clients 32
    PYTHONPATH=src python -m repro.launch.train --preset arch --arch rnnt-librispeech ...
    PYTHONPATH=src python -m repro.launch.train --preset full --arch rnnt-librispeech ...

``--preset arch`` runs the ``--arch`` entry's smoke config on the
container-scale corpus; ``--preset full`` runs its published widths on
a corpus built at the config's own feature dim and vocab.

The task carries the model AND its eval contract, so this module has
no model-specific code: quality is WER, perplexity or error rate
depending on the task (the ``quality_metric`` summary field says
which).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.core import (
    FederatedPlan,
    FederatedTask,
    FVNConfig,
    available_tasks,
    build_round_engine,
    cfmq,
    get_task,
    measured_payload,
    plan_wire_accounting,
    round_wire_bytes,
    summary_row,
    task_for_config,
)
from repro.core.clienteval import ClientEvalPlane, empty_spread
from repro.core.task import arch_task, default_corpus
from repro.data import (
    FederatedSampler,
    PrefetchIterator,
    available_strategies,
    pack_round,
)
from repro.launch.cli import (
    add_client_eval_args,
    add_plan_args,
    add_scale_args,
    plan_kwargs,
)
from repro.launch.compile_cache import use_compile_cache


def tiny_asr_setup(seed: int = 0):
    """Container-scale RNN-T config + corpus (the benchmarks'
    workhorse) — the 'asr-rnnt' task's pieces, kept as a tuple for the
    callers that predate FederatedTask."""
    return get_task("asr-rnnt").bundle.config, default_corpus(seed)


def _check_iid_corruption(plan: FederatedPlan, iid: bool) -> None:
    if iid and plan.corruption.kind == "label_shuffle":
        raise ValueError(
            "label_shuffle corrupts labels inside the FederatedSampler, but "
            "--iid packs rounds from the global pool and bypasses the "
            "sampler — the adversary would silently never fire. Use a "
            "non-IID run (or a delta corruption kind, which is engine-side "
            "and composes with --iid)")


def _scaled_task(task: FederatedTask, specaug_scale: float) -> FederatedTask:
    """Rebuild the task around a specaug-scaled config (E10-style
    regularization sweeps); only defined for models that carry a
    ``specaug`` policy."""
    cfg = task.bundle.config
    if getattr(cfg, "specaug", None) is None:
        raise ValueError(
            f"specaug_scale={specaug_scale} but task {task.name!r} "
            f"({type(cfg).__name__}) has no specaug policy")
    sa = cfg.specaug
    cfg = dataclasses.replace(
        cfg, specaug=dataclasses.replace(
            sa, freq_masks=max(1, int(round(sa.freq_masks * specaug_scale))),
            time_masks=max(1, int(round(sa.time_masks * specaug_scale)))))
    return task_for_config(cfg, name=task.name)


def run_federated(
    task: FederatedTask,
    corpus,
    plan: FederatedPlan,
    rounds: int,
    seed: int = 0,
    iid: bool = False,
    eval_every: int = 0,
    eval_examples: int = 64,
    specaug_scale: float = 1.0,
    log=print,
    ckpt_dir: str | None = None,
    prefetch: bool = True,
    trace_path: str | None = None,
    mesh_clients: int = 0,
    client_eval: int = 0,
    client_eval_examples: int = 4,
):
    """Returns (state, history): per-round losses + the task's final
    quality + CFMQ, in the shared ``SUMMARY_KEYS`` schema.

    ``trace_path`` routes pack/round/eval section timers through the
    profiling plane's single writer (``repro.profile.trace``), keyed by
    the engine's structural key.
    ``mesh_clients`` > 0 shards the round's client axis over a
    ``clients`` mesh (bit-for-bit the vmap round on 1 device).
    ``client_eval`` > 0 tracks that many clients' per-round
    loss/quality (``repro.core.clienteval``): the fairness spread
    joins the summary fields and the full curves ride in
    ``extras["client_eval"]``."""
    _check_iid_corruption(plan, iid)
    if specaug_scale != 1.0:
        task = _scaled_task(task, specaug_scale)
    bundle = task.bundle
    key = jax.random.PRNGKey(seed)
    params = bundle.init(key)
    n_params = bundle.param_count(params)
    client_sharding = None
    if mesh_clients:
        from repro.core.fedavg import ClientSharding
        from repro.launch.mesh import make_federated_mesh

        client_sharding = ClientSharding(make_federated_mesh(mesh_clients))
    engine = build_round_engine(plan, task,
                                base_key=jax.random.PRNGKey(seed + 1),
                                client_sharding=client_sharding)
    state = engine.init_state(params)
    round_step = jax.jit(engine.step)

    sampler = FederatedSampler(
        corpus, clients_per_round=plan.clients_per_round,
        local_batch_size=plan.local_batch_size, data_limit=plan.data_limit,
        local_epochs=plan.local_epochs, seed=seed,
        max_steps=plan.local_steps, strategy=plan.client_sampling,
        label_shuffle_rate=(plan.corruption.rate
                            if plan.corruption.kind == "label_shuffle"
                            else 0.0))
    rng = np.random.default_rng(seed)
    ckpt = Checkpointer(ckpt_dir) if ckpt_dir else None
    eval_plane = (ClientEvalPlane(task, corpus, clients=client_eval,
                                  n=client_eval_examples)
                  if client_eval > 0 else None)

    from repro.profile.trace import TraceRecorder

    rec = TraceRecorder()

    def host_batches():
        """Host packing stream — runs on the prefetch worker thread so
        round r+1 packs (and transfers) while the device runs round r."""
        for _ in range(rounds):
            with rec.section("pack"):
                if iid:
                    # fresh IID shuffle each round
                    pool = corpus.iid_pool()
                    idx = rng.permutation(pool["labels"].shape[0])
                    pool = {k: v[idx] for k, v in pool.items()}
                    rb = pack_round(pool, plan.clients_per_round, sampler.steps,
                                    plan.local_batch_size)
                else:
                    rb = sampler.next_round()
                batch = rb.engine_batch()
            yield batch

    # wire accounting: exact per-client byte counts over the param
    # shapes, accumulated as host-side Python ints — the in-graph f32
    # byte metrics round above ~16 MB/round, exact ints never do
    up_per_client, down_per_round = plan_wire_accounting(plan, params)

    t0 = time.time()
    wire_total = 0
    losses = []
    participants = []
    corrupted = []
    sim_times = []
    server_steps = []
    staleness = []
    # per-shard prefetch: with a client mesh the worker thread puts each
    # round batch pre-split over the ``clients`` axis, so the sharded
    # round step never stalls on a consumer-thread reshard; depth is
    # the ``prefetch.depth`` constant
    batch_sharding = None
    if client_sharding is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        batch_sharding = NamedSharding(client_sharding.mesh,
                                       PartitionSpec(client_sharding.axis))
    if prefetch:
        from repro.profile.tuner import get_knob

        batches = PrefetchIterator(host_batches(),
                                   depth=int(get_knob("prefetch.depth")),
                                   sharding=batch_sharding)
    else:
        batches = map(lambda b: jax.tree.map(jnp.asarray, b), host_batches())
    try:
        for r, batch in enumerate(batches):
            # float() blocks, so the section covers dispatch + device
            # compute; round 1 includes compile — min_s is steady-state.
            # The step annotation marks the round in a profiler trace.
            with jax.profiler.StepTraceAnnotation("round", step_num=r), rec.section("round"):
                state, metrics = round_step(state, batch)
                losses.append(float(metrics["loss"]))
            participants.append(float(metrics["participants"]))
            corrupted.append(float(metrics["corrupted"]))
            sim_times.append(float(metrics["sim_time_s"]))
            server_steps.append(float(metrics["server_steps"]))
            staleness.append(float(metrics["staleness_mean"]))
            wire_total += round_wire_bytes(up_per_client, down_per_round,
                                           participants[-1])
            if eval_plane is not None:
                eval_plane.measure(state.params)
            if eval_every and (r + 1) % eval_every == 0:
                q = task.evaluate(state.params, corpus, eval_examples)
                log(f"round {r+1}: loss={losses[-1]:.4f} "
                    f"{task.quality_metric}={q['quality']:.3f} "
                    f"{task.quality_metric}_hard={q['quality_hard']:.3f}")
            if ckpt and (r + 1) % max(1, rounds // 3) == 0:
                ckpt.save(r + 1, state.params,
                          extra={"wire_bytes": wire_total,
                                 "participants_mean": float(np.mean(participants))})
    finally:
        if prefetch:
            batches.close()

    train_time_s = time.time() - t0
    with rec.section("eval"):
        quality = task.evaluate(state.params, corpus, eval_examples)
    mu = plan.local_epochs * (plan.data_limit or sampler.steps * plan.local_batch_size)
    payload = measured_payload(plan, params, float(np.mean(participants)))
    terms = cfmq(
        rounds=rounds, clients_per_round=plan.clients_per_round,
        model_bytes=n_params * plan.param_bytes,
        local_steps=mu / plan.local_batch_size, alpha=plan.alpha,
        payload_bytes=payload)
    if plan.corruption.kind == "label_shuffle":
        # data-plane adversary: realized counts live on the sampler
        corrupted = [float(c) for c in sampler.corrupted_counts]
    steps_total = sum(server_steps)
    extras = {
        "loss": losses,
        "wire_bytes": wire_total,
        "train_time_s": train_time_s,
    }
    if eval_plane is not None:
        extras["client_eval"] = eval_plane.curves()
    spread = eval_plane.spread() if eval_plane is not None else empty_spread()
    # same round-metrics schema as the sweep rows and bench summaries
    # (repro.core.metrics.SUMMARY_KEYS); the loss curve and the legacy
    # "wire_bytes"/"train_time_s" aliases ride along as extras
    history = summary_row(
        rounds=rounds,
        final_loss=float(np.mean(losses[-5:])),
        quality=quality["quality"], quality_hard=quality["quality_hard"],
        quality_metric=task.quality_metric,
        **spread,
        cfmq_tb=terms.total_terabytes, cfmq_bytes=terms.total_bytes,
        payload_bytes=terms.payload_bytes,
        uplink_bytes_client=up_per_client,
        uplink_bytes_total=wire_total - down_per_round * rounds,
        wire_bytes_total=wire_total,
        downlink_bytes_round=down_per_round,
        participants_mean=float(np.mean(participants)),
        corrupted_mean=float(np.mean(corrupted)) if corrupted else 0.0,
        corrupted_total=int(round(sum(corrupted))),
        n_params=n_params,
        sim_time_s=sum(sim_times),
        server_steps_total=steps_total,
        staleness_mean=(sum(s * w for s, w in zip(staleness, server_steps))
                        / steps_total if steps_total else 0.0),
        wall_s=train_time_s,
        extras=extras,
    )
    if trace_path:
        from repro.core.engine import structural_key_str
        from repro.profile.trace import write_trace

        write_trace(
            trace_path, "round",
            structural_key=structural_key_str(engine.structural_key),
            sections=rec,
            counters={"rounds": rounds, "n_params": n_params,
                      "local_steps": sampler.steps},
            meta={"wall_s": train_time_s, "final_loss": history["final_loss"]},
        )
        log(f"[trace] {trace_path}")
    return state, history


def run_federated_asr(cfg, corpus, plan: FederatedPlan, rounds: int, **kwargs):
    """Config-first compatibility wrapper: the pre-FederatedTask entry
    point. Builds the task from the model config and delegates to
    ``run_federated`` (new code should construct the task directly)."""
    _check_iid_corruption(plan, kwargs.get("iid", False))
    return run_federated(task_for_config(cfg), corpus, plan, rounds, **kwargs)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--task", default=None, choices=available_tasks(),
                    help="a registered FederatedTask (model + eval metric); "
                         "overrides --preset/--arch")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "arch", "full"],
                    help="tiny: the container-scale asr-rnnt task; arch: the "
                         "--arch smoke config; full: the --arch published widths")
    ap.add_argument("--arch", default="rnnt-librispeech")
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--data-limit", type=int, default=None)
    ap.add_argument("--fvn-std", type=float, default=0.0)
    ap.add_argument("--fvn-ramp", type=int, default=0)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--server-lr", type=float, default=0.01)
    ap.add_argument("--client-lr", type=float, default=0.05)
    ap.add_argument("--client-sampling", default="uniform",
                    choices=available_strategies())
    add_scale_args(ap)
    add_plan_args(ap)
    add_client_eval_args(ap)
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the async host->device prefetch")
    ap.add_argument("--eval-every", type=int, default=10)
    ap.add_argument("--out", default=None)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a profiling-plane trace JSON (pack/round/"
                         "eval section timers, keyed by the engine's "
                         "structural key + device fingerprint)")
    args = ap.parse_args()
    use_compile_cache()

    if args.task is not None:
        task = get_task(args.task)
    elif args.preset == "tiny":
        task = get_task("asr-rnnt")
    else:
        task = arch_task(args.arch, full=args.preset == "full")
    corpus = task.make_corpus(0)
    if args.population:
        from repro.data import VirtualPopulation

        corpus = VirtualPopulation(corpus, args.population)

    plan = FederatedPlan(
        clients_per_round=args.clients, local_batch_size=args.batch,
        data_limit=args.data_limit, client_lr=args.client_lr,
        client_sampling=args.client_sampling,
        server_lr=args.server_lr, server_warmup_rounds=max(2, args.rounds // 8),
        fvn=FVNConfig(enabled=args.fvn_std > 0, std=args.fvn_std,
                      ramp_rounds=args.fvn_ramp),
        **plan_kwargs(args),
    )
    _, hist = run_federated(task, corpus, plan, args.rounds, iid=args.iid,
                            eval_every=args.eval_every,
                            prefetch=not args.no_prefetch,
                            trace_path=args.trace,
                            mesh_clients=args.mesh_clients,
                            client_eval=args.client_eval,
                            client_eval_examples=args.client_eval_examples)
    print(json.dumps({k: v for k, v in hist.items()
                      if k not in ("loss", "client_eval")}, indent=1))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(hist, f)


if __name__ == "__main__":
    main()
