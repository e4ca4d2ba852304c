"""Multi-sweep experiment runner — the paper's grids as declarative specs.

The paper's central artifact is a *sweep*: vary the non-IID dial (the
per-client data limit, §4.2.1) and/or FVN (§4.2.2) and measure quality
vs CFMQ cost (Fig. 3). This module expresses those grids as lists of
``SweepPoint``s and runs them on shared infrastructure:

- ONE corpus + model bundle built per runner, reused by every point;
- ONE jitted round function per (engine, server-optimizer, batch
  shape): every scalar knob a sweep varies — client/server lr, warmup,
  decay, FVN std/ramp — enters the compiled function as a *traced*
  hyper input (see ``repro.core.fedavg.make_hyper_round_step``), and
  all points are padded to a common local-step count, so the whole grid
  shares one compilation;
- async host->device prefetch (``repro.data.prefetch``) per point;
- optional point-level mesh parallelism (``--mesh-clients N``): grid
  points that share a compiled round fn stack along a leading axis
  sharded over the ``clients`` mesh, so N whole points advance per
  round step — one jit(vmap(hyper_step)) per grid, rows identical to
  the sequential path (each point keeps its own host sampler/RNG);
- optional ``--population N``: the corpus wrapped in a
  ``VirtualPopulation`` of N clients (see ``repro.data.corpus``), so
  sampling draws from millions of virtual clients in O(K log P).

Grids:
- ``noniid_fvn``: data-limit x FVN cross — the Fig. 3 quality/cost
  frontier (engine behind ``examples/noniid_tradeoff.py``);
- ``ladder``: the paper's E0-E10 experiment ladder at container scale
  (engine behind ``benchmarks/tables.py``);
- ``compression``: uplink compression (fp32/int8/int4/top-k) x cohort /
  robust-aggregation variants — moves the CFMQ *cost* axis with
  measured wire bytes instead of the paper's flat 4 B/param;
- ``ef_compression``: plain vs EF21 error-feedback at identical wire
  bytes (top-k 5%/1%, int4, + the materialized packed-wire path) —
  the quality EF recovers at aggressive sparsity;
- ``sampling``: the client-sampling strategy registry (uniform /
  weighted-by-examples / stratified) x data limit;
- ``robustness``: aggregator x adversary x corruption-rate (see
  ``repro.core.corruption``) — where weighted_mean collapses under
  sign-flip/stale attacks and the robust rules hold, at *identical*
  wire cost (corrupted clients still pay uplink bytes). Rates and
  magnitudes are traced, so one compilation serves each
  (aggregator, adversary-kind) cell across every rate in the grid;
- ``async_vs_sync``: buffered-async (FedBuff-style, see
  ``repro.core.async_engine``) vs the sync barrier at matched CFMQ
  across the non-IID ladder — moves the *wall-clock* cost axis
  (``sim_time_s`` under a shared device-tier latency model) while the
  byte axes stay pair-identical;
- ``client_eval``: the non-IID ladder with the per-client evaluation
  plane on (``repro.core.clienteval``) — per-round per-client
  loss/quality curves in each row's extras and the p10/p90 fairness
  spread in the schema columns, so the frontier shows WHO pays for a
  cheap round, not just the fleet mean.

The runner is task-generic: it drives any ``FederatedTask`` (the
paper's RNN-T by default — quality = WER; LM/keyword tasks report
perplexity/error through the same ``quality`` columns).

Every row follows ``repro.core.metrics.SUMMARY_KEYS`` (the schema the
train history and bench summaries share), plus per-grid extras like
``loss_curve`` / ``sim_time_curve``.

CLI::

    PYTHONPATH=src python -m repro.launch.sweeps --grid noniid_fvn --smoke
    PYTHONPATH=src python -m repro.launch.sweeps --grid compression --smoke
    PYTHONPATH=src python -m repro.launch.sweeps --grid robustness --smoke --check
    PYTHONPATH=src python -m repro.launch.sweeps --grid async_vs_sync --smoke --check
    PYTHONPATH=src python -m repro.launch.sweeps --grid ladder --rounds 100

emits one frontier JSON (WER + final loss vs ``cfmq_tb`` per point,
pareto-marked) under ``results/``. CFMQ payload uses the measured
per-round wire bytes whenever a plan compresses or drops clients; the
paper's 2x-model-bytes formula remains the default/parity path.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.core import (
    AggregatorConfig,
    AsyncConfig,
    CohortConfig,
    CompressionConfig,
    CorruptionConfig,
    FederatedPlan,
    FVNConfig,
    LatencyConfig,
    accumulate_wire_bytes,
    build_round_engine,
    cfmq,
    get_task,
    measured_payload,
    plan_wire_accounting,
    seconds_to_target,
    summary_row,
    task_for_config,
)
from repro.core.clienteval import ClientEvalPlane, empty_spread
from repro.data import FederatedSampler, PrefetchIterator, pack_round


@dataclasses.dataclass(frozen=True)
class SweepPoint:
    """One experiment of a sweep: a plan plus its run budget."""
    id: str
    plan: FederatedPlan
    rounds: int
    iid: bool = False                    # feed IID-shuffled pools (E0 style)
    specaug_scale: float = 1.0
    seed: int = 0
    meta: dict = dataclasses.field(default_factory=dict)


class SweepRunner:
    """Runs SweepPoints against one shared corpus + jit cache.

    ``pad_steps=True`` forces every point of a grid to the grid's max
    local-step count S; padded steps carry weight-0 batches, which the
    engine's n_k weighting makes exact no-ops, so all points share one
    compiled round fn (verified in tests/test_data_plane.py). Default
    False: at full round budgets the no-op steps cost more than the
    per-shape retraces they avoid — ``run_grid`` flips it on for smoke
    runs, where compile time dominates.
    """

    def __init__(self, cfg=None, corpus=None, seed: int = 0,
                 eval_examples: int = 64, prefetch: bool = True,
                 pad_steps: bool = False, trace_dir: Optional[str] = None,
                 mesh_clients: int = 0, task=None, client_eval: int = 0,
                 client_eval_examples: int = 4):
        if task is None:
            task = (task_for_config(cfg) if cfg is not None
                    else get_task("asr-rnnt", seed=seed))
        if corpus is None:
            from repro.core.task import default_corpus

            corpus = default_corpus(seed)
        self.task = task
        self.cfg = task.bundle.config
        self.corpus = corpus
        self.eval_examples = eval_examples
        self.prefetch = prefetch
        self.pad_steps = pad_steps
        # client_eval > 0: every point tracks this many clients'
        # per-round loss/quality (repro.core.clienteval) — the
        # fairness spread joins the row schema, the full curves ride
        # in extras["client_eval"]
        self.client_eval = client_eval
        self.client_eval_examples = client_eval_examples
        # when set, run_point emits one trace JSON per point through
        # the profiling plane's single writer (repro.profile.trace):
        # host pack / round-step / eval section timers
        self.trace_dir = trace_dir
        # mesh_clients > 1: run() shards embarrassingly-parallel grid
        # points over the `clients` mesh (see _run_sharded) — grids are
        # the one driver where whole independent rounds, not a round's
        # client axis, are the natural unit of data parallelism
        self.mesh_clients = mesh_clients
        self._mesh_obj = None
        self._bundles: Dict[float, object] = {}
        self._jit_cache: Dict[tuple, Callable] = {}

    # -------------------------------------------------------- internals

    def _task(self, specaug_scale: float):
        """The runner's task, rebuilt around a specaug-scaled config
        when a point asks for one (one task per scale, cached — the
        task's cached loss_fn is what keys the jit caches)."""
        if specaug_scale not in self._bundles:
            if specaug_scale == 1.0:
                task = self.task
            else:
                from repro.launch.train import _scaled_task

                task = _scaled_task(self.task, specaug_scale)
            self._bundles[specaug_scale] = task
        return self._bundles[specaug_scale]

    def _bundle(self, specaug_scale: float):
        task = self._task(specaug_scale)
        return task.bundle.config, task.bundle

    def _engine(self, plan: FederatedPlan, specaug_scale: float):
        """The point's RoundEngine (validated at construction). Cheap —
        no tracing happens until the jitted hyper_step is called."""
        return build_round_engine(plan, self._task(specaug_scale))

    def _round_fn(self, engine, specaug_scale: float):
        # The engine's structural_key IS the compile identity: engine
        # name + server optimizer + aggregator + compression +
        # corruption kind (+ latency tiers / async buffer when they
        # shape the graph). Every cohort/trim/DP/corruption-rate/
        # latency/staleness knob is traced, so e.g. a participation or
        # adversary-rate grid still shares one entry here; the
        # data-plane label_shuffle adversary keys as the honest plane.
        key = engine.structural_key + (float(specaug_scale),)
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(engine.hyper_step)
        return self._jit_cache[key]

    def _mesh(self):
        if self._mesh_obj is None:
            from repro.launch.mesh import make_federated_mesh

            self._mesh_obj = make_federated_mesh(self.mesh_clients)
        return self._mesh_obj

    def _point_sharding(self):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self._mesh(), PartitionSpec("clients"))

    def _stacked_fn(self, engine, specaug_scale: float):
        """jit(vmap(hyper_step)) — one compiled fn per structural key,
        exactly like _round_fn but with a leading grid-point axis that
        the caller shards over the `clients` mesh."""
        key = (("stacked", self.mesh_clients) + engine.structural_key
               + (float(specaug_scale),))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(jax.vmap(engine.hyper_step))
        return self._jit_cache[key]

    def _stack_key(self, point: SweepPoint, steps: Optional[int]) -> tuple:
        """Points stack into one vmapped round fn only when they share
        compile structure (structural_key + specaug), round batch shape
        (K, S, b) and round budget; everything else is traced."""
        plan = point.plan
        engine = self._engine(plan, point.specaug_scale)
        S = steps if steps is not None else self.native_steps(plan)
        return (engine.structural_key, float(point.specaug_scale),
                point.rounds, plan.clients_per_round,
                plan.local_batch_size, S)

    def native_steps(self, plan: FederatedPlan) -> int:
        """The local-step count the plan would get on its own (the
        FederatedSampler formula) — CFMQ accounting always uses this,
        never the padded shape."""
        return FederatedSampler.natural_steps(
            self.corpus, plan.local_batch_size, data_limit=plan.data_limit,
            local_epochs=plan.local_epochs, max_steps=plan.local_steps)

    def common_steps(self, points) -> Optional[int]:
        if not self.pad_steps:
            return None
        return max(self.native_steps(p.plan) for p in points)

    # ------------------------------------------------------------- runs

    def run_point(self, point: SweepPoint, steps: Optional[int] = None,
                  log=print) -> dict:
        plan = point.plan
        if point.iid and plan.corruption.kind == "label_shuffle":
            raise ValueError(
                f"{point.id}: label_shuffle corrupts inside the "
                "FederatedSampler, which IID points bypass — the adversary "
                "would silently never fire")
        task = self._task(point.specaug_scale)
        bundle = task.bundle
        params = bundle.init(jax.random.PRNGKey(point.seed))
        n_params = bundle.param_count(params)
        engine = self._engine(plan, point.specaug_scale)
        state = engine.init_state(params)
        round_fn = self._round_fn(engine, point.specaug_scale)
        hypers = engine.hypers()
        base_key = jax.random.PRNGKey(point.seed + 1)
        eval_plane = (ClientEvalPlane(task, self.corpus,
                                      clients=self.client_eval,
                                      n=self.client_eval_examples)
                      if self.client_eval > 0 else None)

        native = self.native_steps(plan)
        S = steps if steps is not None else native
        sampler = FederatedSampler(
            self.corpus, clients_per_round=plan.clients_per_round,
            local_batch_size=plan.local_batch_size, data_limit=plan.data_limit,
            local_epochs=plan.local_epochs, seed=point.seed, steps=S,
            strategy=plan.client_sampling,
            label_shuffle_rate=(plan.corruption.rate
                                if plan.corruption.kind == "label_shuffle"
                                else 0.0))
        rng = np.random.default_rng(point.seed)

        from repro.profile.trace import TraceRecorder

        rec = TraceRecorder()

        def host_batches():
            for _ in range(point.rounds):
                with rec.section("pack"):
                    if point.iid:
                        pool = self.corpus.iid_pool()
                        idx = rng.permutation(pool["labels"].shape[0])
                        pool = {k: v[idx] for k, v in pool.items()}
                        # pack at the plan's native steps, then zero-pad
                        # to the grid shape — pad_steps must stay a
                        # no-op, not extra weight-1 recycled examples
                        rb = pack_round(pool, plan.clients_per_round, native,
                                        plan.local_batch_size).pad_steps(S)
                    else:
                        rb = sampler.next_round()
                    batch = rb.engine_batch()
                yield batch

        t0 = time.time()
        losses = []
        participants = []
        corrupted = []
        sim_times = []
        server_steps = []
        staleness = []
        batches = (PrefetchIterator(host_batches(), depth=2) if self.prefetch
                   else map(lambda b: jax.tree.map(jax.numpy.asarray, b),
                            host_batches()))
        try:
            for r, batch in enumerate(batches):
                # the float() pulls synchronize, so the section times
                # dispatch + device compute (round 1 includes compile;
                # min_s is the steady-state round); the step annotation
                # marks the round in a profiler trace
                with jax.profiler.StepTraceAnnotation("round", step_num=r), \
                        rec.section("round"):
                    state, metrics = round_fn(state, batch, hypers, base_key)
                    losses.append(float(metrics["loss"]))
                participants.append(float(metrics["participants"]))
                corrupted.append(float(metrics["corrupted"]))
                sim_times.append(float(metrics["sim_time_s"]))
                server_steps.append(float(metrics["server_steps"]))
                staleness.append(float(metrics["staleness_mean"]))
                if eval_plane is not None:
                    eval_plane.measure(state.params)
        finally:
            if self.prefetch:
                batches.close()
        if plan.corruption.kind == "label_shuffle":
            # the data-plane adversary corrupts host-side; the realized
            # counts live on the sampler, not in the round metrics
            corrupted = [float(c) for c in sampler.corrupted_counts]

        with rec.section("eval"):
            quality = task.evaluate(state.params, self.corpus,
                                    self.eval_examples)
        row = self._finish_row(point, params, n_params, native, losses,
                               participants, corrupted, sim_times,
                               server_steps, staleness, quality,
                               time.time() - t0, eval_plane=eval_plane,
                               log=log)
        if self.trace_dir:
            from repro.core.engine import structural_key_str
            from repro.profile.trace import write_trace

            path = os.path.join(self.trace_dir,
                                f"trace_sweep_{point.id}.json")
            write_trace(
                path, "sweep",
                structural_key=structural_key_str(engine.structural_key),
                sections=rec,
                counters={"rounds": point.rounds, "n_params": n_params,
                          "local_steps": native, "padded_steps": S},
                meta={"id": point.id, "wall_s": row["wall_s"]},
            )
            log(f"  [trace] {path}")
        return row

    def _finish_row(self, point: SweepPoint, params, n_params: int,
                    native: int, losses, participants, corrupted, sim_times,
                    server_steps, staleness, quality, wall_s: float,
                    eval_plane=None, log=print) -> dict:
        """Per-point metric lists -> one frontier row. Shared by the
        sequential and mesh-stacked paths, so both emit identical
        schemas with identical accounting."""
        plan = point.plan
        # wire-accurate payload: per-client byte counts are exact ints
        # over the param shapes; participants come from the round
        # metrics, so partial participation shrinks measured uplink.
        # Totals stay host-side Python ints — byte-exact at any scale.
        up_per_client, down_per_round = plan_wire_accounting(plan, params)
        wire_total = accumulate_wire_bytes(up_per_client, down_per_round,
                                           participants)
        uplink_total = wire_total - down_per_round * point.rounds
        payload = measured_payload(plan, params, float(np.mean(participants)))
        mu = plan.local_epochs * (plan.data_limit or native * plan.local_batch_size)
        terms = cfmq(rounds=point.rounds, clients_per_round=plan.clients_per_round,
                     model_bytes=n_params * plan.param_bytes,
                     local_steps=mu / plan.local_batch_size, alpha=plan.alpha,
                     payload_bytes=payload)
        steps_total = sum(server_steps)
        # per-round staleness_mean averages over that round's applied
        # deltas, so the run-level mean weights each round by its step
        # count (sync rounds: 1 step, staleness 0)
        stale_mean = (sum(s * w for s, w in zip(staleness, server_steps))
                      / steps_total if steps_total else 0.0)
        curve_stride = max(1, point.rounds // 50)
        spread = (eval_plane.spread() if eval_plane is not None
                  else empty_spread())
        extras = {
            "id": point.id,
            "loss_curve": losses[::curve_stride],
            "sim_time_curve": sim_times[::curve_stride],
            **point.meta,
        }
        if eval_plane is not None:
            extras["client_eval"] = eval_plane.curves()
        row = summary_row(
            rounds=point.rounds,
            final_loss=float(np.mean(losses[-5:])),
            quality=quality["quality"], quality_hard=quality["quality_hard"],
            quality_metric=self.task.quality_metric,
            **spread,
            cfmq_tb=terms.total_terabytes, cfmq_bytes=terms.total_bytes,
            payload_bytes=terms.payload_bytes,
            uplink_bytes_client=up_per_client,
            uplink_bytes_total=uplink_total,
            wire_bytes_total=wire_total,
            downlink_bytes_round=down_per_round,
            participants_mean=float(np.mean(participants)),
            corrupted_mean=float(np.mean(corrupted)) if corrupted else 0.0,
            corrupted_total=int(round(sum(corrupted))),
            n_params=n_params,
            sim_time_s=sum(sim_times),
            server_steps_total=steps_total,
            staleness_mean=stale_mean,
            wall_s=wall_s,
            extras=extras,
        )
        log(f"  {point.id:>10s}: loss={row['final_loss']:.3f} "
            f"{row['quality_metric']}={row['quality']:.3f} "
            f"cfmq={row['cfmq_tb']:.5f}TB ({row['wall_s']:.0f}s)")
        return row

    def _run_chunk(self, chunk, steps: Optional[int], n_real: Optional[int] = None,
                   log=print) -> list[dict]:
        """Run len(chunk) == mesh_clients points in lockstep: states,
        hypers and round batches gain a leading point axis sharded over
        the `clients` mesh, and ONE jit(vmap(hyper_step)) advances every
        point per round — whole grid points are embarrassingly parallel,
        so each lives on its own device. Host-side sampling stays one
        independent sampler/RNG per point: rounds are bit-identical to
        the sequential path's draws."""
        import jax.numpy as jnp

        m = len(chunk)
        first = chunk[0]
        task = self._task(first.specaug_scale)
        bundle = task.bundle
        engines = [self._engine(p.plan, p.specaug_scale) for p in chunk]
        natives = [self.native_steps(p.plan) for p in chunk]
        S = steps if steps is not None else natives[0]
        params0 = [bundle.init(jax.random.PRNGKey(p.seed)) for p in chunk]
        n_params = bundle.param_count(params0[0])

        def stack(trees):
            return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)

        shard = self._point_sharding()
        state = jax.device_put(
            stack([e.init_state(pr) for e, pr in zip(engines, params0)]), shard)
        hypers = jax.device_put(stack([e.hypers() for e in engines]), shard)
        keys = jax.device_put(
            jnp.stack([jax.random.PRNGKey(p.seed + 1) for p in chunk]), shard)
        round_fn = self._stacked_fn(engines[0], first.specaug_scale)
        samplers = [
            FederatedSampler(
                self.corpus, clients_per_round=p.plan.clients_per_round,
                local_batch_size=p.plan.local_batch_size,
                data_limit=p.plan.data_limit, local_epochs=p.plan.local_epochs,
                seed=p.seed, steps=S, strategy=p.plan.client_sampling,
                label_shuffle_rate=(p.plan.corruption.rate
                                    if p.plan.corruption.kind == "label_shuffle"
                                    else 0.0))
            for p in chunk
        ]

        def host_batches():
            for _ in range(first.rounds):
                rbs = [s.next_round().engine_batch() for s in samplers]
                yield jax.tree.map(lambda *xs: np.stack(xs), *rbs)

        t0 = time.time()
        series = {k: [[] for _ in range(m)]
                  for k in ("loss", "participants", "corrupted",
                            "sim_time_s", "server_steps", "staleness_mean")}
        batches = (PrefetchIterator(host_batches(), depth=2, device_put=False,
                                    transform=lambda b: jax.device_put(b, shard))
                   if self.prefetch
                   else map(lambda b: jax.device_put(b, shard), host_batches()))
        try:
            for r, batch in enumerate(batches):
                with jax.profiler.StepTraceAnnotation("round", step_num=r):
                    state, metrics = round_fn(state, batch, hypers, keys)
                    for k, per_point in series.items():
                        vals = np.asarray(metrics[k])
                        for i in range(m):
                            per_point[i].append(float(vals[i]))
        finally:
            if self.prefetch:
                batches.close()

        wall = time.time() - t0
        rows = []
        for i, p in enumerate(chunk[:n_real]):
            corrupted = series["corrupted"][i]
            if p.plan.corruption.kind == "label_shuffle":
                corrupted = [float(c) for c in samplers[i].corrupted_counts]
            params_i = jax.tree.map(lambda x: np.asarray(x[i]), state.params)
            quality = task.evaluate(params_i, self.corpus, self.eval_examples)
            rows.append(self._finish_row(
                p, params_i, n_params, natives[i], series["loss"][i],
                series["participants"][i], corrupted, series["sim_time_s"][i],
                series["server_steps"][i], series["staleness_mean"][i],
                quality, wall, log=log))
        return rows

    def _run_sharded(self, points, steps: Optional[int], log=print) -> list[dict]:
        """Group stackable points, run them in mesh-sized chunks (the
        last chunk pads by repeating its final point — duplicate rows
        are dropped), and fall back to run_point for singletons and IID
        points (whose host pipeline bypasses the sampler)."""
        m = self.mesh_clients
        groups: Dict[tuple, list] = {}
        for i, p in enumerate(points):
            if not p.iid:
                groups.setdefault(self._stack_key(p, steps), []).append(i)
        rows: Dict[int, dict] = {}
        for key, idxs in groups.items():
            if len(idxs) < 2:
                continue
            log(f"[sweeps] mesh: {len(idxs)} points sharded over "
                f"{m} devices ({[points[i].id for i in idxs]})")
            for lo in range(0, len(idxs), m):
                chunk_idx = idxs[lo:lo + m]
                pad = m - len(chunk_idx)
                chunk = [points[i] for i in chunk_idx] + \
                        [points[chunk_idx[-1]]] * pad
                chunk_rows = self._run_chunk(chunk, steps,
                                             n_real=len(chunk_idx), log=log)
                for i, row in zip(chunk_idx, chunk_rows):
                    rows[i] = row
        return [rows[i] if i in rows else self.run_point(p, steps=steps, log=log)
                for i, p in enumerate(points)]

    def run(self, points, log=print) -> list[dict]:
        steps = self.common_steps(points)
        if steps is not None:
            log(f"[sweeps] {len(points)} points padded to S={steps} local "
                f"steps -> one compiled round fn per engine/optimizer")
        if self.mesh_clients > 1 and not self.trace_dir and not self.client_eval:
            # traces need per-point section timers, and the
            # per-client plane measures after every round — neither fits
            # the lockstep path, so both force sequential
            return self._run_sharded(points, steps, log=log)
        return [self.run_point(p, steps=steps, log=log) for p in points]


# ----------------------------------------------------------------------
# Grids
# ----------------------------------------------------------------------

def noniid_fvn_points(rounds: int = 60, smoke: bool = False, seed: int = 0,
                      limits=(1, 2, 4, 8, None), fvn_opts=(False, True),
                      client_sampling: str = "uniform") -> list[SweepPoint]:
    """Data-limit x FVN cross — the paper's Fig. 3 frontier grid."""
    if smoke:
        rounds = min(rounds, 6)
        limits = (1, 4, None)
    points = []
    for fvn_on in fvn_opts:
        for limit in limits:
            plan = FederatedPlan(
                clients_per_round=8, local_batch_size=4, data_limit=limit,
                local_steps=12, client_lr=0.3, server_lr=0.05,
                server_warmup_rounds=4, client_sampling=client_sampling,
                fvn=FVNConfig(enabled=fvn_on, std=0.03,
                              ramp_rounds=max(1, rounds // 2)))
            points.append(SweepPoint(
                id=f"L{limit if limit is not None else 'inf'}_fvn{int(fvn_on)}",
                plan=plan, rounds=rounds, seed=seed,
                meta={"limit": limit, "fvn": fvn_on}))
    return points


def compression_points(rounds: int = 40, smoke: bool = False,
                       seed: int = 0) -> list[SweepPoint]:
    """Uplink-compression frontier — the new CFMQ cost axis.

    fp32 (the paper's wire model) vs int8/int4 stochastic quantization
    and top-k sparsification, plus partial-participation and
    straggler+trimmed-mean variants at the cheapest quantized point.
    Every point's ``cfmq_tb`` uses *measured* wire bytes (fp32 keeps
    the paper formula, which the measured path reproduces exactly).
    """
    base = dict(clients_per_round=8, local_batch_size=4, data_limit=4,
                local_steps=12, client_lr=0.3, server_lr=0.05,
                server_warmup_rounds=4)
    if smoke:
        rounds = min(rounds, 6)
    schemes = [
        ("fp32", CompressionConfig()),
        ("int8", CompressionConfig(kind="int8")),
        ("int4", CompressionConfig(kind="int4")),
        ("top5", CompressionConfig(kind="topk", topk_frac=0.05)),
    ]
    points = [
        SweepPoint(id=name, rounds=rounds, seed=seed,
                   plan=FederatedPlan(**base, compression=comp),
                   meta={"compression": name, "aggregator": "weighted_mean"})
        for name, comp in schemes
    ]
    if not smoke:
        int8 = CompressionConfig(kind="int8")
        points += [
            SweepPoint(id="int8_p75", rounds=rounds, seed=seed,
                       plan=FederatedPlan(**base, compression=int8,
                                          cohort=CohortConfig(participation=0.75)),
                       meta={"compression": "int8", "aggregator": "weighted_mean",
                             "participation": 0.75}),
            # trim_frac 0.2 so floor(0.2 * 8) trims one client per side
            # (the plan default 0.1 would trim nobody at K=8)
            SweepPoint(id="int8_trim", rounds=rounds, seed=seed,
                       plan=FederatedPlan(**base, compression=int8,
                                          aggregation=AggregatorConfig(
                                              name="trimmed_mean",
                                              trim_frac=0.2),
                                          cohort=CohortConfig(straggler_frac=0.25)),
                       meta={"compression": "int8", "aggregator": "trimmed_mean",
                             "straggler_frac": 0.25}),
        ]
    return points


def ef_compression_points(rounds: int = 40, smoke: bool = False,
                          seed: int = 0) -> list[SweepPoint]:
    """Error-feedback frontier: plain vs EF21 at *identical* wire bytes.

    EF changes what travels in the payload, not its size, so each
    plain/EF pair sits at the same cfmq_tb — the grid isolates the
    quality EF recovers at aggressive sparsity (top-k 5%/1%) and int4.
    ``int4_packed_ef`` additionally routes through the materialized
    packed-wire path (bit-identical numerics, exercises the wire_pack
    kernels in the sweep harness).

    The server is plain SGD at lr 1.0 (the canonical FedAvg server,
    w += wbar): EF21's convergence story assumes the aggregated
    update is applied as-is, and an adaptive server (Adam) renormalizes
    the delayed residual bursts into oscillation — measured here too,
    which is exactly the kind of interaction the grid exists to show.
    """
    base = dict(clients_per_round=8, local_batch_size=4, data_limit=4,
                local_steps=12, client_lr=0.3, server_lr=1.0,
                server_optimizer="sgd", server_warmup_rounds=4)
    if smoke:
        rounds = min(rounds, 8)
    topk = lambda **kw: CompressionConfig(kind="topk", topk_frac=0.05, **kw)
    schemes = [
        ("top5", topk()),
        ("top5_ef", topk(error_feedback=True)),
        ("int4", CompressionConfig(kind="int4")),
        ("int4_ef", CompressionConfig(kind="int4", error_feedback=True)),
        ("int4_packed_ef", CompressionConfig(kind="int4", packed=True,
                                             error_feedback=True)),
    ]
    if not smoke:
        schemes += [
            ("top1", CompressionConfig(kind="topk", topk_frac=0.01)),
            ("top1_ef", CompressionConfig(kind="topk", topk_frac=0.01,
                                          error_feedback=True)),
        ]
    return [
        SweepPoint(id=name, rounds=rounds, seed=seed,
                   plan=FederatedPlan(**base, compression=comp),
                   meta={"compression": comp.kind,
                         "topk_frac": comp.topk_frac,
                         "error_feedback": comp.error_feedback,
                         "packed": comp.packed})
        for name, comp in schemes
    ]


def sampling_points(rounds: int = 40, smoke: bool = False, seed: int = 0,
                    limits=(2, None)) -> list[SweepPoint]:
    """Client-sampling-strategy x data-limit grid (registry sweep).

    Sampling is host-side, so the whole grid shares one compiled round
    fn; the strategies open a second non-IID axis beyond the data
    limit (round example-mass variance vs per-speaker coverage).
    """
    from repro.data import available_strategies

    if smoke:
        rounds = min(rounds, 6)
        limits = (2,)
    points = []
    for strat in available_strategies():
        for limit in limits:
            plan = FederatedPlan(
                clients_per_round=8, local_batch_size=4, data_limit=limit,
                local_steps=12, client_lr=0.3, server_lr=0.05,
                server_warmup_rounds=4, client_sampling=strat)
            points.append(SweepPoint(
                id=f"{strat}_L{limit if limit is not None else 'inf'}",
                plan=plan, rounds=rounds, seed=seed,
                meta={"strategy": strat, "limit": limit}))
    return points


def robustness_points(rounds: int = 40, smoke: bool = False,
                      seed: int = 0) -> list[SweepPoint]:
    """Aggregator x adversary x corruption-rate grid — the Byzantine
    axis of the quality/cost frontier.

    Each aggregator gets one clean baseline (kind "none") plus every
    adversary at each nonzero rate. Kind is compile-time structure but
    rate/scale are traced, so the whole grid compiles once per
    (aggregator, kind) cell — label_shuffle (a host-side data-plane
    adversary) shares the honest compilation. Wire bytes are identical
    down every column: corrupted clients still pay full uplink, so the
    grid isolates pure quality damage at fixed CFMQ cost.

    trim_frac 0.3 so trimmed_mean drops floor(0.3 * 8) = 2 clients per
    side — enough to shed the ~2.4 corrupted clients a 0.3 rate draws
    at K=8 (the plan default 0.1 would trim nobody).
    """
    base = dict(clients_per_round=8, local_batch_size=4, data_limit=4,
                local_steps=12, client_lr=0.3, server_lr=0.05,
                server_warmup_rounds=4)
    aggregators = ["weighted_mean", "trimmed_mean", "coordinate_median"]
    adversaries = [("sign_flip", 3.0), ("gaussian", 5.0), ("zero", 1.0),
                   ("stale", 1.0), ("label_shuffle", 1.0)]
    rates = (0.1, 0.3)
    if smoke:
        rounds = min(rounds, 8)
        aggregators = ["weighted_mean", "trimmed_mean"]
        adversaries = [("sign_flip", 3.0), ("label_shuffle", 1.0)]
        rates = (0.3,)
    points = []
    for agg in aggregators:
        for kind, scale, rate in ([("none", 1.0, 0.0)] +
                                  [(k, s, r) for k, s in adversaries
                                   for r in rates]):
            plan = FederatedPlan(
                **base, aggregation=AggregatorConfig(name=agg, trim_frac=0.3),
                corruption=CorruptionConfig(kind=kind, rate=rate, scale=scale))
            points.append(SweepPoint(
                id=f"{agg}_{kind}_r{int(round(rate * 100))}",
                plan=plan, rounds=rounds, seed=seed,
                meta={"aggregator": agg, "adversary": kind,
                      "corrupt_rate": rate, "corrupt_scale": scale}))
    return points


def async_vs_sync_points(rounds: int = 40, smoke: bool = False, seed: int = 0,
                         limits=(1, 4, None)) -> list[SweepPoint]:
    """Buffered-async vs barrier-sync at matched CFMQ across the
    non-IID ladder — the wall-clock axis of the frontier.

    Both engines share one device-tier latency model, K, round budget
    and (un)compressed payload, so every pair sits at byte-identical
    CFMQ; the pair isolates what the async engine buys on the
    ``sim_time_s`` axis and what (if anything) staleness costs on the
    quality axis. ``seconds_to_target`` over each row's
    loss/sim-time curves is the headline readout.

    buffer_size 5 deliberately does NOT divide K = 8: leftover buffered
    updates carry across waves, so a wave's last flush generally lands
    BEFORE its slowest arrival — that gap is the async wall-clock win.
    A divisor buffer at full participation flushes exactly on the last
    arrival and silently re-creates the sync barrier.

    The async arm's server lr is scaled by B/K (FedBuff's practice): a
    wave applies ~K/B server steps, so the unscaled lr moves the params
    ~K/B times further per wave than the barrier engine and overshoots
    where sync is stable — scaling matches per-wave displacement, which
    is what "same server lr" actually means across the two engines.
    """
    if smoke:
        rounds = min(rounds, 10)
        limits = (1, 4)
    base = dict(clients_per_round=8, local_batch_size=4, local_steps=12,
                client_lr=0.3, server_warmup_rounds=4,
                latency=LatencyConfig(enabled=True, base_s=60.0, spread=0.35))
    server_lr, B = 0.05, 5
    points = []
    for limit in limits:
        lname = f"L{limit if limit is not None else 'inf'}"
        for engine, acfg in (("fedavg", AsyncConfig()),
                             ("async", AsyncConfig(buffer_size=B,
                                                   staleness_beta=0.5))):
            tag = "sync" if engine == "fedavg" else "async"
            lr = server_lr * (B / base["clients_per_round"]
                              if engine == "async" else 1.0)
            plan = FederatedPlan(**base, data_limit=limit, engine=engine,
                                 server_lr=lr, asynchrony=acfg)
            points.append(SweepPoint(
                id=f"{tag}_{lname}", plan=plan, rounds=rounds, seed=seed,
                meta={"pair": lname, "engine": engine, "limit": limit}))
    return points


# Container-scale ladder constants (shared with benchmarks/common.py).
LADDER_BASE = dict(clients_per_round=8, local_batch_size=4, client_lr=0.3,
                   server_lr=0.05, local_steps=12)
LADDER_LIMIT = 8
LADDER_FVN_STD = 0.02
MEAN_CLIENT_EXAMPLES = 24.0          # tiny corpus mean_utterances


def ladder_rounds(plan: FederatedPlan, rounds: int) -> int:
    """Equal-examples budgeting: the paper trains every config to
    convergence; data-limited rounds see fewer examples, so they get
    proportionally more rounds ("the entire per-speaker dataset was
    still seen over the course of multiple rounds", §4.2.1)."""
    if plan.data_limit is None:
        return rounds
    mult = MEAN_CLIENT_EXAMPLES / plan.data_limit
    return int(rounds * max(1.0, min(mult, 5.0)))


def ladder_specs(rounds: int = 100) -> dict:
    """The paper's E0-E10 ladder (Tables 1-5) as plan specs."""
    fvn = lambda std, ramp=0: FVNConfig(enabled=True, std=std, ramp_rounds=ramp)
    base = dict(LADDER_BASE, server_warmup_rounds=max(2, rounds // 15))
    ramp = rounds // 2
    decay = dict(server_warmup_rounds=max(2, rounds // 30),
                 server_decay_rounds=max(5, rounds // 4), server_decay_rate=0.85)
    L, STD = LADDER_LIMIT, LADDER_FVN_STD
    return {
        "E0": dict(plan=FederatedPlan(**base, fvn=fvn(STD, ramp)), iid=True),
        "E1": dict(plan=FederatedPlan(**base), iid=False),
        "E2": dict(plan=FederatedPlan(**base, data_limit=L), iid=False),
        "E3": dict(plan=FederatedPlan(**base, data_limit=2 * L), iid=False),
        "E4": dict(plan=FederatedPlan(**base, data_limit=4 * L), iid=False),
        "E5": dict(plan=FederatedPlan(**base, data_limit=L, fvn=fvn(STD / 2)), iid=False),
        "E6": dict(plan=FederatedPlan(**base, data_limit=L, fvn=fvn(STD)), iid=False),
        "E7": dict(plan=FederatedPlan(**base, data_limit=L,
                                      fvn=fvn(1.5 * STD, ramp)), iid=False),
        "E8": dict(plan=FederatedPlan(**base, fvn=fvn(1.5 * STD, ramp)), iid=False),
        "E9": dict(plan=FederatedPlan(**{**base, **decay}, data_limit=L,
                                      fvn=fvn(1.5 * STD, ramp)), iid=False),
        "E10": dict(plan=FederatedPlan(**{**base, **decay}, data_limit=L,
                                       fvn=fvn(1.5 * STD, ramp)), iid=False,
                    specaug_scale=2.0),
    }


def ladder_points(rounds: int = 100, smoke: bool = False, seed: int = 0,
                  experiments=None) -> list[SweepPoint]:
    """E0-E10 as SweepPoints with per-point equal-examples budgets and
    budget-scaled FVN ramps / LR decay (matching the bench harness)."""
    if smoke:
        rounds = min(rounds, 6)
    specs = ladder_specs(rounds)
    if experiments is not None:
        specs = {e: specs[e] for e in experiments}
    points = []
    for eid, spec in specs.items():
        plan = spec["plan"]
        n_rounds = ladder_rounds(plan, rounds)
        if plan.fvn.enabled and plan.fvn.ramp_rounds:
            plan = dataclasses.replace(
                plan, fvn=dataclasses.replace(plan.fvn, ramp_rounds=n_rounds // 2))
        if plan.server_decay_rounds:
            plan = dataclasses.replace(plan,
                                       server_decay_rounds=max(5, n_rounds // 4))
        points.append(SweepPoint(
            id=eid, plan=plan, rounds=n_rounds, iid=spec["iid"],
            specaug_scale=spec.get("specaug_scale", 1.0), seed=seed,
            meta={"experiment": eid}))
    return points


def client_eval_points(rounds: int = 30, smoke: bool = False, seed: int = 0,
                       limits=(1, 4, None)) -> list[SweepPoint]:
    """The non-IID ladder with the per-client evaluation plane on —
    the fairness axis of the frontier.

    Same dial as ``noniid_fvn`` (the per-client data limit), but the
    readout is WHO pays: each row carries the p10/p90 client-quality
    spread and the full per-round per-client curves. ``run_grid``
    turns the plane on automatically for this grid (panel of 6
    clients, 4 eval examples each).
    """
    if smoke:
        rounds = min(rounds, 6)
    points = []
    for limit in limits:
        plan = FederatedPlan(
            clients_per_round=8, local_batch_size=4, data_limit=limit,
            local_steps=12, client_lr=0.3, server_lr=0.05,
            server_warmup_rounds=4)
        points.append(SweepPoint(
            id=f"L{limit if limit is not None else 'inf'}",
            plan=plan, rounds=rounds, seed=seed, meta={"limit": limit}))
    return points


GRIDS: Dict[str, Callable[..., list]] = {
    "noniid_fvn": noniid_fvn_points,
    "ladder": ladder_points,
    "compression": compression_points,
    "ef_compression": ef_compression_points,
    "sampling": sampling_points,
    "robustness": robustness_points,
    "async_vs_sync": async_vs_sync_points,
    "client_eval": client_eval_points,
}


def check_robustness(frontier: dict, log=print) -> None:
    """The robustness grid's qualitative claim, asserted (the CI smoke
    gate): under sign_flip at rate 0.3 the robust trimmed_mean must
    end at a lower loss than the paper's weighted_mean, and every row
    must carry its realized corrupted-client count and exact wire
    bytes."""
    rows = {r["id"]: r for r in frontier["points"]}
    wm = rows["weighted_mean_sign_flip_r30"]
    tm = rows["trimmed_mean_sign_flip_r30"]
    log(f"[check] sign_flip@0.3: trimmed_mean loss={tm['final_loss']:.3f} "
        f"vs weighted_mean loss={wm['final_loss']:.3f}")
    assert tm["final_loss"] < wm["final_loss"], (
        "robustness claim failed: trimmed_mean should beat weighted_mean "
        f"under sign_flip at rate 0.3 ({tm['final_loss']:.3f} vs "
        f"{wm['final_loss']:.3f})")
    for r in frontier["points"]:
        assert "corrupted_mean" in r and "wire_bytes_total" in r, r["id"]
        if r["corrupt_rate"] >= 0.3:
            assert r["corrupted_mean"] > 0, (
                f"{r['id']}: adversary at rate {r['corrupt_rate']} never "
                "corrupted anyone")
    # identical wire cost down every column: the adversary moves
    # quality, never bytes
    totals = {r["wire_bytes_total"] for r in frontier["points"]}
    assert len(totals) == 1, f"wire bytes must not vary with the adversary: {totals}"
    log("[check] robustness grid invariants hold")


# Async must land within this factor of the sync final loss at matched
# CFMQ. At smoke budgets (10 rounds, 5-client flushes, traced staleness
# discounts) the async arm lands ~1.1-1.25x the sync loss across seeds
# and beta choices; 1.3 flags the real regressions — an unscaled server
# lr diverges to ~1.75x here — without flaking on smoke-scale noise.
ASYNC_LOSS_TOL = 1.3


def check_async_vs_sync(frontier: dict, log=print) -> None:
    """The async engine's claim, asserted (the CI smoke gate): at
    byte-identical CFMQ, buffered-async finishes its server steps in
    less simulated wall-clock than the sync barrier while landing at a
    sync-comparable loss, on every rung of the non-IID ladder."""
    rows = {r["id"]: r for r in frontier["points"]}
    for pair in sorted({r["pair"] for r in frontier["points"]}):
        s, a = rows[f"sync_{pair}"], rows[f"async_{pair}"]
        assert s["sim_time_s"] > 0 and a["sim_time_s"] > 0, (
            f"{pair}: wall-clock axis missing — latency model never priced "
            "a round")
        # matched cost: same K/rounds/payload and full participation, so
        # both byte axes must agree exactly
        assert a["cfmq_bytes"] == s["cfmq_bytes"], (
            f"{pair}: CFMQ bytes diverged ({a['cfmq_bytes']} vs "
            f"{s['cfmq_bytes']}) — the pair no longer isolates wall-clock")
        assert a["wire_bytes_total"] == s["wire_bytes_total"], (
            f"{pair}: wire bytes diverged ({a['wire_bytes_total']} vs "
            f"{s['wire_bytes_total']})")
        assert a["sim_time_s"] < s["sim_time_s"], (
            f"{pair}: async should beat the barrier on simulated seconds "
            f"({a['sim_time_s']:.0f}s vs {s['sim_time_s']:.0f}s) — did the "
            "buffer size become a divisor of K?")
        assert a["final_loss"] <= s["final_loss"] * ASYNC_LOSS_TOL, (
            f"{pair}: async loss {a['final_loss']:.3f} not comparable to "
            f"sync {s['final_loss']:.3f} (tol x{ASYNC_LOSS_TOL})")
        target = s["final_loss"] * 1.05
        t_a = seconds_to_target(a["loss_curve"], a["sim_time_curve"], target)
        t_s = seconds_to_target(s["loss_curve"], s["sim_time_curve"], target)
        log(f"[check] {pair}: async {a['sim_time_s']:.0f}s/"
            f"{a['server_steps_total']:.0f} steps/loss {a['final_loss']:.3f} "
            f"(stale {a['staleness_mean']:.2f}) vs sync "
            f"{s['sim_time_s']:.0f}s/loss {s['final_loss']:.3f}; "
            f"seconds-to-target({target:.3f}): async={t_a} sync={t_s}")
    log("[check] async_vs_sync grid invariants hold")


def check_client_eval(frontier: dict, log=print) -> None:
    """The per-client plane's contract, asserted (the CI smoke gate):
    every row carries a live fairness spread (clients tracked, finite
    p10 <= p90 columns) and full per-round per-client curves; and the
    non-IID ladder orders both axes — more per-round data trains
    further (final_loss falls monotonically with the limit), and the
    trained non-IID model serves its clients UNEVENLY: the panel's
    quality gap at the unlimited rung must exceed the limit-1 rung,
    where barely-trained clients are uniformly bad (gap ~0). I.e.
    heterogeneity is what the plane measures, not noise."""
    from repro.core.clienteval import SPREAD_KEYS

    rows = {r["limit"]: r for r in frontier["points"]}
    for r in frontier["points"]:
        assert r["clients_tracked"] > 0, f"{r['id']}: plane never measured"
        for k in SPREAD_KEYS:
            assert np.isfinite(r[k]), f"{r['id']}: {k} not finite"
        assert r["client_loss_p10"] <= r["client_loss_p90"], r["id"]
        assert r["client_quality_p10"] <= r["client_quality_p90"], r["id"]
        curves = r["client_eval"]
        C = r["clients_tracked"]
        assert len(curves["client_ids"]) == C, r["id"]
        assert len(curves["client_loss"]) == r["rounds"], r["id"]
        assert all(len(c) == C for c in curves["client_loss"]), r["id"]
        assert all(len(c) == C for c in curves["client_quality"]), r["id"]
        log(f"[check] {r['id']}: gap(loss)={r['client_loss_gap']:.3f} "
            f"gap({r['quality_metric']})={r['client_quality_gap']:.3f} "
            f"({C} clients x {r['rounds']} rounds)")
    near_iid, non_iid = rows[1], rows[None]
    # endpoints only: adjacent rungs can swap inside smoke budgets,
    # the ladder's ends never do
    assert near_iid["final_loss"] > non_iid["final_loss"], (
        "ladder ordering failed: the limit-1 rung sees 1/24th the data "
        "per round and must end at a higher loss than the unlimited rung "
        f"({near_iid['final_loss']:.3f} vs {non_iid['final_loss']:.3f})")
    assert non_iid["client_quality_gap"] > near_iid["client_quality_gap"], (
        "ladder ordering failed: the unlimited (most non-IID) rung should "
        "spread the panel's quality wider than the barely-trained limit-1 "
        f"rung ({non_iid['client_quality_gap']:.4f} vs "
        f"{near_iid['client_quality_gap']:.4f})")
    log("[check] client_eval grid invariants hold")


GRID_CHECKS: Dict[str, Callable[..., None]] = {
    "robustness": check_robustness,
    "async_vs_sync": check_async_vs_sync,
    "client_eval": check_client_eval,
}


# ----------------------------------------------------------------------
# Frontier assembly + CLI
# ----------------------------------------------------------------------

def mark_pareto(rows: list[dict], cost="cfmq_tb", quality="quality") -> list[dict]:
    """Flag points on the quality/cost pareto front (min both)."""
    for r in rows:
        r["pareto"] = not any(
            (o[cost] <= r[cost] and o[quality] <= r[quality]) and
            (o[cost] < r[cost] or o[quality] < r[quality])
            for o in rows if o is not r)
    return rows


def run_grid(grid: str, rounds: Optional[int] = None, smoke: bool = False,
             seed: int = 0, out: Optional[str] = None, runner: Optional[SweepRunner] = None,
             pad_steps: Optional[bool] = None, check: bool = False,
             trace_dir: Optional[str] = None, mesh_clients: int = 0,
             population: int = 0, client_eval: int = 0,
             plan_overrides: Optional[dict] = None,
             log=print, **grid_kwargs) -> dict:
    """Run a named grid and write one quality/cost frontier JSON.

    ``pad_steps`` defaults to the smoke flag: with tiny round budgets
    compile time dominates, so padding every point to one shape (one
    compilation for the whole grid) wins; at full budgets the padded
    no-op steps cost more than the extra per-shape retraces save.
    """
    make_points = GRIDS[grid]
    kwargs = dict(grid_kwargs, smoke=smoke, seed=seed)
    if rounds is not None:
        kwargs["rounds"] = rounds
    points = make_points(**kwargs)
    if plan_overrides:
        # grid-wide plan overrides (launch.cli.plan_overrides): every
        # point keeps its own plan except the groups the CLI moved
        log(f"[sweeps] plan overrides: {sorted(plan_overrides)}")
        points = [dataclasses.replace(
            p, plan=dataclasses.replace(p.plan, **plan_overrides))
            for p in points]
    if client_eval == 0 and grid == "client_eval":
        # the grid exists to exercise the per-client plane — default
        # the panel on rather than silently emitting empty spreads
        client_eval = 6
    if runner is None:
        corpus = None
        if population:
            from repro.core.task import default_corpus
            from repro.data import VirtualPopulation

            corpus = VirtualPopulation(default_corpus(seed), population)
        runner = SweepRunner(corpus=corpus, seed=seed,
                             eval_examples=24 if smoke else 64,
                             pad_steps=smoke if pad_steps is None else pad_steps,
                             trace_dir=trace_dir, mesh_clients=mesh_clients,
                             client_eval=client_eval)
    t0 = time.time()
    log(f"[sweeps] grid={grid} points={len(points)} "
        f"rounds={[p.rounds for p in points]}")
    rows = mark_pareto(runner.run(points, log=log))
    frontier = {
        "grid": grid, "smoke": smoke, "seed": seed,
        "n_points": len(rows), "wall_s": time.time() - t0,
        "points": rows,
    }
    out = out or f"results/sweep_{grid}.json"
    os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
    with open(out, "w") as f:
        json.dump(frontier, f, indent=1)
    log(f"[sweeps] frontier ({sum(r['pareto'] for r in rows)} pareto points) "
        f"-> {out} [{frontier['wall_s']:.0f}s]")
    if check:
        checker = GRID_CHECKS.get(grid)
        if checker is None:
            log(f"[sweeps] no --check defined for grid {grid!r}; skipping")
        else:
            checker(frontier, log=log)
    return frontier


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--grid", default="noniid_fvn", choices=sorted(GRIDS))
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny budget (<2min): fewer points, few rounds")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--pad-steps", dest="pad_steps", action="store_true",
                    default=None, help="pad all points to one batch shape "
                    "(one compiled round fn for the whole grid)")
    ap.add_argument("--no-pad-steps", dest="pad_steps", action="store_false")
    ap.add_argument("--check", action="store_true",
                    help="assert the grid's qualitative claim after the "
                         "run (e.g. robustness: trimmed_mean beats "
                         "weighted_mean under sign_flip@0.3)")
    ap.add_argument("--trace-dir", default=None,
                    help="emit one trace JSON per point (pack/round/eval "
                         "section timers) into this directory")
    from repro.launch.cli import (
        add_client_eval_args,
        add_plan_args,
        add_scale_args,
        plan_overrides,
    )

    add_scale_args(ap)
    add_plan_args(ap)
    add_client_eval_args(ap)
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    run_grid(args.grid, rounds=args.rounds, smoke=args.smoke, seed=args.seed,
             out=args.out, pad_steps=args.pad_steps, check=args.check,
             trace_dir=args.trace_dir, mesh_clients=args.mesh_clients,
             population=args.population, client_eval=args.client_eval,
             plan_overrides=plan_overrides(args))


if __name__ == "__main__":
    main()
