"""FedAvg round engines (paper Alg. 1) as single pjit-able functions.

A federated round is ONE pure function of (server state, round batch):
clients are a leading array axis — ``jax.vmap`` over clients wrapping a
``jax.lax.scan`` over local steps — so under pjit with the client axis
sharded over the mesh's ("pod","data") axes, client-parallel local
training and the delta-aggregation all-reduce lower exactly like the
production system's communication pattern.

Three engines (see DESIGN.md §3 and ``repro.core.engine`` for the
unified ``RoundEngine`` facade):

- ``fedavg``: general case. Per-client weight replicas live on the
  client's model-parallel group; supports local_steps >= 1 and
  per-client FVN. Weights must fit one model-parallel group.
- ``fedsgd``: the paper's §2.2 IID-limit (one local step). No
  per-client weight state exists, so weights can be FSDP-sharded; the
  round is one example-weighted forward/backward over all clients'
  data. FVN degrades to one shared draw per round (documented).
- ``async``: buffered-asynchronous (FedBuff-style) streaming server —
  see ``repro.core.async_engine``. Shares this module's client update,
  cohort stage and payload pipeline; replaces the barrier aggregate
  with a staleness-discounted buffer.

The server update treats the aggregated delta ``wbar`` as a
pseudo-gradient for the server optimizer (Adam in the paper), i.e.
adaptive federated optimization (Reddi et al.).

The round step is a composed server-side pipeline (one jitted graph):

    client deltas -> cohort mask -> uplink compression -> corruption
                  -> aggregator -> server optimizer

Each stage is pluggable (see ``repro.core.cohort`` / ``compression`` /
``aggregation`` / ``corruption``); the defaults — full participation,
no compression, no adversary, example-weighted mean — reproduce the
paper's Alg. 1 exactly and are the parity baseline for tests. The
round metrics report the *exact* wire bytes of the configured
compression so CFMQ can account measured (not approximated)
communication cost, and carry exactly the keys of
``repro.core.metrics.ROUND_METRIC_KEYS`` — including the simulated
wall-clock axis (``sim_time_s``), which for a barrier round is the
slowest participant's arrival under the plan's ``LatencyConfig``
device-tier model (0.0 when disabled: the paper prices bytes only).

When the plane quantizes (int8/int4) under the paper's weighted mean
with no EF and no delta adversary, the engine statically swaps the
compress->aggregate stages for the *code-domain fast path*
(``compression.code_domain_aggregate``): per-leaf scales are
negotiated by a max-reduce over the client axis, each client runs ONE
fused quantize(+nibble-pack) kernel, the reduction is an exact int32
code sum (``sum_packed_codes``) and the server dequantizes once —
per-client fp32 deltas are never rematerialized, wire bytes are
untouched, and every other configuration (including the fp32 parity
plane) keeps its previous graph byte for byte.

With ``compression.error_feedback`` the pipeline carries EF21-style
per-client residuals in ``ServerState.ef``: client k uploads
C(delta_k + ef_k) and keeps ef_k' = (delta_k + ef_k) - C(...), so the
error of aggressive compression (top-k at small fractions, int4) is
compensated over rounds instead of lost. Wire bytes are unchanged.
With ``compression.packed`` the uplink payloads are materialized
(int8 / int4-nibble / top-k (value, index) buffers via
``repro.kernels.wire_pack``) and round-tripped bit-exactly.

The corruption stage (``repro.core.corruption``) models Byzantine /
faulty clients on what the server *receives* (the post-compression
deltas): its rate and magnitude are traced ``HYPER_KEYS`` scalars, so
an adversary grid shares one compilation per (aggregator, kind), and a
corrupted client still pays its full uplink bytes — the wire metrics
count participants, not honesty.

With a ``ClientSharding`` (a mesh with a named ``clients`` axis,
threaded through ``build_round_engine``), the per-client stage runs
under ``shard_map``: each shard owns K/shards clients of the round
batch and the local-steps scan runs unchanged per client, so the
sharded round is bit-for-bit the vmap round on a 1-device mesh. The
code-domain fast path additionally keeps its whole aggregate inside
the shard_map — the shared-scale negotiation becomes a ``lax.pmax``
over 4-byte scalars and ``sum_packed_codes`` becomes a literal
``lax.psum`` of int32 partial code sums (exact integer arithmetic, so
the single-server-dequant semantics and the int32 overflow bound carry
over unchanged). The slow path (EF / robust aggregators / delta
adversaries) shards the client compute only and aggregates on the
gathered global axis.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import fvn as fvn_lib
from repro.core.aggregation import AGG_HYPER_DEFAULTS, get_aggregator
from repro.core.cohort import LatencyConfig, identity_cohort, make_cohort_fn, make_latency_fn
from repro.core.compression import (
    CompressionConfig,
    client_wire_bytes,
    code_domain_aggregate,
    code_domain_aggregate_ef,
    make_compressor,
    tree_param_bytes,
)
from repro.core.corruption import DELTA_KINDS, identity_corruption, make_corruption_fn
from repro.core.plan import FederatedPlan, make_server_optimizer
from repro.optim import Optimizer, apply_updates, sgd

PyTree = Any


class ClientSharding(NamedTuple):
    """Construction-time capability: run a round's per-client stage
    under ``shard_map`` over a named mesh axis, each shard owning
    K/num_shards clients of the round batch.

    The mesh axis name and size are compile-time structure (they shape
    the lowered collectives), so engines fold ``structural()`` into
    their jit-cache identity; the concrete device assignment is not —
    the same program lowers on any mesh of the same shape."""

    mesh: Any  # jax.sharding.Mesh with ``axis`` in its axis names
    axis: str = "clients"

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    def structural(self) -> tuple:
        return ("clients_sharded", self.axis, self.num_shards)

    def check_clients(self, clients: int) -> None:
        if self.axis not in self.mesh.shape:
            raise ValueError(
                f"client sharding axis {self.axis!r} is not on the mesh "
                f"(axes: {tuple(self.mesh.shape)}) — build it with "
                "launch.mesh.make_federated_mesh"
            )
        if clients % self.num_shards:
            raise ValueError(
                f"clients_per_round={clients} does not divide over the "
                f"{self.num_shards}-way {self.axis!r} mesh axis — each "
                "shard owns an equal slice of the round batch"
            )


class ServerState(NamedTuple):
    params: PyTree
    opt_state: PyTree
    round_idx: jnp.ndarray
    # EF21 per-client compression residuals: a params-shaped tree with a
    # leading K axis when plan.compression.error_feedback, else None.
    # Client k compresses (delta_k + ef_k) and keeps the compression
    # error as next round's residual, so top-k/int4 error is
    # compensated across rounds instead of lost.
    ef: Optional[PyTree] = None
    # Stale-replay cache (plan.corruption.kind == "stale", else None):
    # each participant's last honestly-computed (post-compression)
    # delta, leading K axis — what a stale adversary re-sends next
    # round (honest even for corrupted clients: staleness stays one
    # round deep, never a replay-of-replay).
    stale: Optional[PyTree] = None
    # Buffered-async engine state (plan.engine == "async", else None):
    # an ``async_engine.AsyncBuffer`` of pending staleness-tagged
    # deltas that persists ACROSS waves — a straggler's update lands in
    # a later wave's flush instead of being dropped.
    abuf: Optional[Any] = None


class ServerPlane(NamedTuple):
    """The composed server side of one round: cohort -> compression ->
    corruption -> aggregation. Built once per (static) configuration;
    every traced knob rides in via the closures (plan constants or
    hyper inputs). ``aggregator_name`` / ``corruption_kind`` mirror the
    closures as static strings so the engine can select the code-domain
    fast path at trace time (see ``_code_fast_path``)."""

    cohort: Callable  # (key, weight) -> (weight', pmask)
    compress: Callable  # (delta_tree, key) -> delta_tree
    compression: CompressionConfig  # static: wire-byte accounting
    aggregate: Callable  # (deltas, n_k, pmask, key) -> wbar
    corrupt: Callable = identity_corruption
    # (key, deltas, pmask, stale) -> (deltas', cmask, stale')
    aggregator_name: str = "weighted_mean"
    corruption_kind: str = "none"


def _code_fast_path(plane: ServerPlane) -> bool:
    """Static selector for the code-domain aggregation fast path: the
    plane compresses (int8/int4/topk), aggregates with the paper's
    weighted mean, and no delta-domain adversary needs the per-client
    fp32 deltas the fast path never materializes (corruption transforms
    what the server receives; in the fast path the server receives code
    sums / payload scatters). EF planes are eligible since PR 10:
    ``code_domain_aggregate_ef`` computes the residual straight from
    the transmitted codes' dequant (intN) or the selected-coordinate
    zeroing (topk), so no separately compressed fp32 tree is needed.
    Everything here is compile-time structure, so the fp32 parity graph
    is byte-for-byte untouched and each configuration keeps one
    compilation."""
    return (
        plane.compression.kind in ("int8", "int4", "topk")
        and plane.aggregator_name == "weighted_mean"
        and plane.corruption_kind not in DELTA_KINDS
    )


# Distinct fold_in tags keep the plane's RNG streams away from the FVN
# stream (which folds small client/step indices).
_COHORT_TAG, _COMPRESS_TAG, _AGG_TAG, _CORRUPT_TAG = (0x636F68, 0x636D70, 0x616767, 0x626164)
# Arrival-latency stream: its own tag so enabling the latency model
# never perturbs the cohort/compression/aggregation/corruption draws.
_LATENCY_TAG = 0x6C6174


def _plane_keys(base_key, round_idx):
    rk = jax.random.fold_in(base_key, round_idx)
    return (
        jax.random.fold_in(rk, _COHORT_TAG),
        jax.random.fold_in(rk, _COMPRESS_TAG),
        jax.random.fold_in(rk, _AGG_TAG),
        jax.random.fold_in(rk, _CORRUPT_TAG),
    )


def _latency_key(base_key, round_idx):
    return jax.random.fold_in(jax.random.fold_in(base_key, round_idx), _LATENCY_TAG)


def _make_server_plane(
    aggregator: str = "weighted_mean",
    compression: Optional[CompressionConfig] = None,
    cohort_knobs: Optional[tuple] = None,  # (participation, frac, keep) or None
    agg_hypers: Optional[dict] = None,
    corruption_kind: str = "none",
    corruption_knobs: Optional[tuple] = None,  # (rate, scale) or None
) -> ServerPlane:
    """Compose a server plane. ``cohort_knobs=None`` means the paper's
    full-participation assumption (no cohort RNG enters the graph);
    knob values may be Python floats or traced scalars. Likewise
    ``corruption_kind="none"`` (and the data-plane "label_shuffle")
    keeps the identity corruption stage with no adversary RNG."""
    compression = compression or CompressionConfig()
    cohort = identity_cohort if cohort_knobs is None else make_cohort_fn(*cohort_knobs)
    agg_fn = get_aggregator(aggregator)
    hyp = dict(AGG_HYPER_DEFAULTS, **(agg_hypers or {}))
    rate, scale = corruption_knobs if corruption_knobs is not None else (0.0, 1.0)
    return ServerPlane(
        cohort=cohort,
        compress=make_compressor(compression),
        compression=compression,
        aggregate=lambda deltas, n_k, pmask, key: agg_fn(deltas, n_k, pmask, hyp, key),
        corrupt=make_corruption_fn(corruption_kind, rate, scale),
        aggregator_name=aggregator,
        corruption_kind=corruption_kind,
    )


def _plan_server_plane(plan: FederatedPlan) -> ServerPlane:
    """The plan's server plane with all knobs as Python constants."""
    knobs = None
    if not plan.cohort.full:
        knobs = (plan.cohort.participation, plan.cohort.straggler_frac, plan.cohort.straggler_keep)
    return _make_server_plane(
        plan.aggregation.name,
        plan.compression,
        knobs,
        plan.aggregation.hypers,
        corruption_kind=plan.corruption.kind,
        corruption_knobs=(plan.corruption.rate, plan.corruption.scale),
    )


_PARITY_PLANE = _make_server_plane()


def _apply_cohort(plane: ServerPlane, ckey, round_batch: PyTree):
    """Mask the round batch's example weights by the drawn cohort."""
    weight = round_batch.get("weight") if hasattr(round_batch, "get") else None
    K = jax.tree.leaves(round_batch)[0].shape[0]
    if weight is None:
        # legacy weight-less layout: nothing to mask. Only the paper's
        # full-participation plane may proceed — silently reporting
        # participants=K for a plan that asked to drop clients would
        # corrupt both training and the CFMQ accounting.
        if plane.cohort is not identity_cohort:
            raise ValueError(
                "cohort dynamics (partial participation / stragglers) mask "
                "the round batch's example weights, but this batch has no "
                "'weight' leaf — pack rounds through the data plane (which "
                "always emits one). Plan-path alternative: a full-"
                "participation plan. The hyper round step always draws a "
                "cohort (its knobs are traced, so participation=1.0 cannot "
                "be detected at trace time) and therefore requires the "
                "weight leaf unconditionally"
            )
        return round_batch, jnp.ones((K,), jnp.float32)
    weight, pmask = plane.cohort(ckey, weight)
    return dict(round_batch, weight=weight), pmask


def _wire_metrics(plane: ServerPlane, params: PyTree, pmask, K: int) -> dict:
    """Wire bytes for this round. Uplink counts only reporting clients
    (compressed deltas); downlink counts every sampled client (the
    server broadcasts the full model before it knows who reports).

    ``participants`` is the exact reporting count (a small integer,
    lossless in f32); the byte totals are f32 conveniences that round
    above ~16 MB/round. Byte-exact accounting multiplies
    ``participants`` by the Python-int per-client counts host-side —
    ``cfmq.plan_wire_accounting`` — which is what train/sweeps feed
    into CFMQ."""
    up = client_wire_bytes(plane.compression, params)
    down = tree_param_bytes(params)
    return {
        "participants": pmask.sum(),
        "uplink_bytes": pmask.sum() * jnp.float32(up),
        "downlink_bytes": jnp.float32(K * down),
    }


def _sim_time_metrics(latency_fn, base_key, round_idx, pmask, K: int) -> dict:
    """The barrier engines' wall-clock/staleness metric trio: a sync
    round's simulated duration is its slowest reporting participant's
    arrival (the barrier waits for everyone who reports), it applies
    exactly one server step, and nothing is ever stale. With no latency
    model the duration is 0.0 — the paper's CFMQ axis prices bytes, not
    seconds, and a disabled model keeps that parity path RNG-free."""
    if latency_fn is None:
        sim_time = jnp.float32(0.0)
    else:
        times = latency_fn(_latency_key(base_key, round_idx), K)
        sim_time = (times * pmask).max()
    return {
        "sim_time_s": sim_time,
        "server_steps": jnp.float32(1.0),
        "staleness_mean": jnp.float32(0.0),
    }


def _client_axis_zeros(params: PyTree, K: int) -> PyTree:
    return jax.tree.map(lambda p: jnp.zeros((K,) + jnp.shape(p), jnp.float32), params)


def init_server_state(plan: FederatedPlan, params: PyTree) -> ServerState:
    opt = make_server_optimizer(plan)
    K = plan.clients_per_round
    ef = _client_axis_zeros(params, K) if plan.compression.error_feedback else None
    stale = _client_axis_zeros(params, K) if plan.corruption.kind == "stale" else None
    abuf = None
    if plan.engine == "async":
        from repro.core.async_engine import init_async_buffer

        abuf = init_async_buffer(params, plan.asynchrony.resolve_buffer(K))
    return ServerState(
        params=params,
        opt_state=opt.init(params),
        round_idx=jnp.zeros((), jnp.int32),
        ef=ef,
        stale=stale,
        abuf=abuf,
    )


def _client_update(
    loss_fn: Callable,
    client_opt: Optimizer,
    sigma_fn: Optional[Callable],
    base_key,
    params: PyTree,
    client_batch: PyTree,
    client_idx,
    round_idx,
):
    """Local optimization for one client (vmapped over the K axis).

    client_batch leaves have shape (S_local, b, ...). ``sigma_fn``
    maps round_idx -> FVN noise std (None disables the perturbation
    entirely; a sigma of 0.0 is numerically identical but keeps the
    draw in the graph so one compilation covers FVN on AND off).
    Returns (delta = w^r - w_hat, mean loss, examples seen).
    """
    n_steps = jax.tree.leaves(client_batch)[0].shape[0]

    def local_step(carry, inp):
        p, opt_state = carry
        step_batch, step_idx = inp
        key = fvn_lib.fvn_key(base_key, round_idx, client_idx, step_idx)
        p_eval = p if sigma_fn is None else fvn_lib.perturb(p, key, sigma_fn(round_idx))
        data_key = jax.random.fold_in(key, 1)
        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p_eval, step_batch, data_key
        )
        updates, opt_state = client_opt.update(grads, opt_state, p)
        p = apply_updates(p, updates)
        w = step_batch.get("weight")
        n = w.sum() if w is not None else jnp.asarray(
            jax.tree.leaves(step_batch)[0].shape[0], jnp.float32
        )
        return (p, opt_state), (loss, n)

    init = (params, client_opt.init(params))
    (p_final, _), (losses, ns) = jax.lax.scan(local_step, init, (client_batch, jnp.arange(n_steps)))
    delta = jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), params, p_final
    )
    n_k = ns.sum()
    step_mask = (ns > 0).astype(jnp.float32)
    mean_loss = (losses * step_mask).sum() / jnp.maximum(step_mask.sum(), 1.0)
    return delta, mean_loss, n_k


def _client_key_fanout(plane: ServerPlane, qkey, K: int):
    """The round's client-key fan-out, built ONCE and threaded through
    every consumer (EF, plain compression, the code fast path) — the
    fold_in vmap used to be rebuilt per compress call site."""
    if plane.compression.kind == "none":
        return None
    return jax.vmap(lambda i: jax.random.fold_in(qkey, i))(jnp.arange(K))


def _client_update_stage(
    loss_fn, client_opt, sigma_fn, base_key, params, round_batch, round_idx,
    sharding: Optional[ClientSharding] = None,
):
    """The round's per-client compute — vmap over the K axis wrapping
    the local-steps scan — optionally shard_mapped over ``sharding``'s
    mesh axis. Each shard runs the identical per-client arithmetic on
    its K/num_shards slice (client indices stay global through the
    sharded arange, so the FVN/RNG streams are untouched), which is
    what makes the sharded round bit-for-bit the vmap round on a
    1-device mesh. Returns (deltas, losses, n_k) with a global leading
    K axis either way."""
    K = jax.tree.leaves(round_batch)[0].shape[0]

    def stage(p, batch, cidx, bkey, ridx):
        return jax.vmap(
            lambda cb, ci: _client_update(loss_fn, client_opt, sigma_fn, bkey, p, cb, ci, ridx)
        )(batch, cidx)

    args = (params, round_batch, jnp.arange(K), base_key, round_idx)
    if sharding is None:
        return stage(*args)
    from jax.sharding import PartitionSpec as P

    sharding.check_clients(K)
    ax = sharding.axis
    return jax.shard_map(
        stage,
        mesh=sharding.mesh,
        in_specs=(P(), P(ax), P(ax), P(), P()),
        out_specs=(P(ax), P(ax), P(ax)),
        check_vma=False,
    )(*args)


def _sharded_code_fastpath(
    plane: ServerPlane,
    loss_fn,
    client_opt,
    sigma_fn,
    base_key,
    params,
    round_batch,
    round_idx,
    pmask,
    ckeys,
    sharding: ClientSharding,
    ef=None,
):
    """Client compute AND the code-domain aggregate in ONE shard_map:
    local deltas never leave their shard — the scale negotiation is a
    ``lax.pmax`` over 4-byte scalars and the code reduction a literal
    ``lax.psum`` of int32 partial sums (exact, order-independent), so
    ``wbar`` replicates bit-for-bit what the unsharded fast path
    computes. With ``ef`` (EF planes) the per-client residual tree
    rides the same client-axis sharding in and out — its update is
    purely local to each shard's clients, so no extra collectives
    appear. Returns (wbar replicated, losses (K,), n_k (K,), ef')."""
    from jax.sharding import PartitionSpec as P

    K = jax.tree.leaves(round_batch)[0].shape[0]
    sharding.check_clients(K)
    ax = sharding.axis

    def client_stage(p, batch, cidx, bkey, ridx):
        with jax.named_scope("client"):
            return jax.vmap(
                lambda cb, ci: _client_update(loss_fn, client_opt, sigma_fn, bkey, p, cb, ci, ridx)
            )(batch, cidx)

    if ef is None:

        def stage(p, batch, cidx, pm, cks, bkey, ridx):
            deltas, losses, n_k = client_stage(p, batch, cidx, bkey, ridx)
            with jax.named_scope("server"), jax.named_scope("aggregate"):
                wbar = code_domain_aggregate(plane.compression, deltas, n_k, pm, cks, axis=ax)
            return wbar, losses, n_k

        wbar, losses, n_k = jax.shard_map(
            stage,
            mesh=sharding.mesh,
            in_specs=(P(), P(ax), P(ax), P(ax), P(ax), P(), P()),
            out_specs=(P(), P(ax), P(ax)),
            check_vma=False,
        )(params, round_batch, jnp.arange(K), pmask, ckeys, base_key, round_idx)
        return wbar, losses, n_k, None

    def stage_ef(p, batch, cidx, pm, cks, bkey, ridx, e):
        deltas, losses, n_k = client_stage(p, batch, cidx, bkey, ridx)
        with jax.named_scope("server"), jax.named_scope("aggregate"):
            wbar, e2 = code_domain_aggregate_ef(
                plane.compression, deltas, n_k, pm, cks, e, axis=ax
            )
        return wbar, losses, n_k, e2

    return jax.shard_map(
        stage_ef,
        mesh=sharding.mesh,
        in_specs=(P(), P(ax), P(ax), P(ax), P(ax), P(), P(), P(ax)),
        out_specs=(P(), P(ax), P(ax), P(ax)),
        check_vma=False,
    )(params, round_batch, jnp.arange(K), pmask, ckeys, base_key, round_idx, ef)


def _delta_payload_stage(plane: ServerPlane, deltas, ef, pmask, ckeys, xkey, stale):
    """The generic per-client payload pipeline — (EF-)compression then
    the delta-domain adversary — shared by the sync slow path and the
    async engine (which buffers per-client deltas the code-domain fast
    path never materializes, so it always routes here). Returns
    (deltas', ef', cmask, stale')."""
    if plane.compression.error_feedback:
        # EF21: each client compresses delta + residual and keeps
        # the compression error. Non-participants send nothing and
        # keep their residual untouched — the pmask select matters
        # because, unlike the plain path (where a dropped client's
        # delta is exactly 0), C(0 + e_k) is generally nonzero.
        target = jax.tree.map(lambda d, e: d + e, deltas, ef)
        sent = jax.vmap(plane.compress)(target, ckeys)
        sel = lambda a, b: jnp.where(pmask.reshape((-1,) + (1,) * (a.ndim - 1)) > 0, a, b)
        deltas = jax.tree.map(lambda s: sel(s, jnp.zeros_like(s)), sent)
        ef = jax.tree.map(lambda t, s, e: sel(t - s, e), target, sent, ef)
    elif plane.compression.kind != "none":
        # each client quantizes its own delta with its own RNG stream
        deltas = jax.vmap(plane.compress)(deltas, ckeys)

    # Adversary stage: corrupts what the server receives (the
    # post-compression deltas). cmask is already pmask-masked — a
    # corrupted non-participant contributes neither delta nor EF
    # residual update; wire bytes are untouched (corrupted
    # participants pay full uplink).
    deltas, cmask, stale = plane.corrupt(xkey, deltas, pmask, stale)
    return deltas, ef, cmask, stale


def _fedavg_round_body(
    loss_fn,
    client_opt,
    server_opt,
    sigma_fn,
    base_key,
    state: ServerState,
    round_batch: PyTree,
    plane: Optional[ServerPlane] = None,
    latency_fn=None,
    sharding: Optional[ClientSharding] = None,
):
    """One FedAvg round: client deltas -> cohort -> compression ->
    corruption -> aggregator -> server optimizer (one jitted graph).

    The stages run under ``jax.named_scope``s — ``client``, and
    ``server`` with its children ``cohort``, ``uplink``, ``aggregate``,
    ``server_step`` and ``round_metrics`` — that every compiled
    instruction carries in its ``op_name``, so a device trace splits the
    round's time by stage. XLA fuses across them (the aggregate's mean
    lands in the server step's fusion), so only ``client`` against
    ``server`` is a hard boundary."""
    plane = plane or _PARITY_PLANE
    K = jax.tree.leaves(round_batch)[0].shape[0]
    ckey, qkey, akey, xkey = _plane_keys(base_key, state.round_idx)

    with jax.named_scope("server"), jax.named_scope("cohort"):
        round_batch, pmask = _apply_cohort(plane, ckey, round_batch)
    with jax.named_scope("server"), jax.named_scope("uplink"):
        ckeys = _client_key_fanout(plane, qkey, K)

    ef = state.ef
    if _code_fast_path(plane) and sharding is not None:
        # Sharded code-domain fast path: client compute and the int32
        # code-sum psum live in one shard_map — per-client deltas never
        # leave their shard (see _sharded_code_fastpath). EF residuals
        # ride the same client-axis sharding in and out.
        wbar, losses, n_k, ef2 = _sharded_code_fastpath(
            plane, loss_fn, client_opt, sigma_fn, base_key, state.params,
            round_batch, state.round_idx, pmask, ckeys, sharding,
            ef=ef if plane.compression.error_feedback else None,
        )
        if plane.compression.error_feedback:
            ef = ef2
        cmask = jnp.zeros((K,), jnp.float32)
        stale = state.stale
    elif _code_fast_path(plane):
        # Code-domain fast path: shared-scale negotiation + in-graph
        # int32 code-sum (or payload scatter-add) reduction, ONE server
        # dequant — per-client fp32 deltas are never rematerialized.
        # Statically selected, so every other configuration keeps its
        # existing graph. The corruption stage here is the honest
        # identity (delta adversaries force the slow path), matching
        # its cmask = 0. EF planes route through the _ef twin, whose
        # residual update reads the transmitted codes directly.
        with jax.named_scope("client"):
            deltas, losses, n_k = _client_update_stage(
                loss_fn, client_opt, sigma_fn, base_key, state.params, round_batch,
                state.round_idx,
            )
        with jax.named_scope("server"), jax.named_scope("aggregate"):
            if plane.compression.error_feedback:
                wbar, ef = code_domain_aggregate_ef(
                    plane.compression, deltas, n_k, pmask, ckeys, ef
                )
            else:
                wbar = code_domain_aggregate(plane.compression, deltas, n_k, pmask, ckeys)
        cmask = jnp.zeros((K,), jnp.float32)
        stale = state.stale
    else:
        with jax.named_scope("client"):
            deltas, losses, n_k = _client_update_stage(
                loss_fn, client_opt, sigma_fn, base_key, state.params, round_batch,
                state.round_idx, sharding,
            )
        with jax.named_scope("server"):
            with jax.named_scope("uplink"):
                deltas, ef, cmask, stale = _delta_payload_stage(
                    plane, deltas, ef, pmask, ckeys, xkey, state.stale
                )
            with jax.named_scope("aggregate"):
                wbar = plane.aggregate(deltas, n_k, pmask, akey)

    with jax.named_scope("server"):
        with jax.named_scope("server_step"):
            updates, opt_state = server_opt.update(wbar, state.opt_state, state.params)
            params = apply_updates(state.params, updates)
        with jax.named_scope("round_metrics"):
            n = jnp.maximum(n_k.sum(), 1.0)
            metrics = {
                "loss": (losses * n_k).sum() / n,
                "examples": n_k.sum(),
                "delta_norm": jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(wbar))),
                "corrupted": cmask.sum(),
                **_wire_metrics(plane, state.params, pmask, K),
                **_sim_time_metrics(latency_fn, base_key, state.round_idx, pmask, K),
            }
    return ServerState(params, opt_state, state.round_idx + 1, ef, stale, state.abuf), metrics


def _make_fedavg_round(
    loss_fn: Callable,
    plan: FederatedPlan,
    base_key,
    client_sharding: Optional[ClientSharding] = None,
) -> Callable[[ServerState, PyTree], tuple[ServerState, dict]]:
    """Returns round_step(state, round_batch) -> (state, metrics).

    round_batch leaves: (K, S_local, b, ...); must contain "weight"
    (K, S_local, b) marking real examples (the paper's n_k weighting).
    """
    client_opt = sgd(plan.client_lr)
    server_opt = make_server_optimizer(plan)
    sigma_fn = (lambda r: fvn_lib.fvn_sigma(plan.fvn, r)) if plan.fvn.enabled else None
    plane = _plan_server_plane(plan)
    latency_fn = make_latency_fn(plan.latency) if plan.latency.enabled else None
    if client_sharding is not None:
        client_sharding.check_clients(plan.clients_per_round)

    def round_step(state: ServerState, round_batch: PyTree):
        return _fedavg_round_body(
            loss_fn, client_opt, server_opt, sigma_fn, base_key, state, round_batch, plane,
            latency_fn, client_sharding,
        )

    return round_step


def _make_fedsgd_round(
    loss_fn: Callable,
    plan: FederatedPlan,
    base_key,
) -> Callable[[ServerState, PyTree], tuple[ServerState, dict]]:
    """Large-model engine: one local step at the round-start weights.

    round_batch leaves: (K, 1, b, ...) (same layout as fedavg with
    S_local = 1). Equivalent to fedavg(local_steps=1) up to FVN
    granularity: grads are taken at w^r for every client, so the round
    collapses to one example-weighted forward/backward — weights stay
    FSDP-sharded, no per-client weight replicas exist.
    """
    _check_fedsgd_aggregator(plan.aggregation.name)
    _check_fedsgd_compression(plan.compression)
    _check_fedsgd_corruption(plan.corruption.kind)
    server_opt = make_server_optimizer(plan)
    sigma_fn = (lambda r: fvn_lib.fvn_sigma(plan.fvn, r)) if plan.fvn.enabled else None
    plane = _plan_server_plane(plan)
    latency_fn = make_latency_fn(plan.latency) if plan.latency.enabled else None

    def round_step(state: ServerState, round_batch: PyTree):
        return _fedsgd_round_body(
            loss_fn, server_opt, sigma_fn, plan.client_lr, base_key, state, round_batch, plane,
            latency_fn,
        )

    return round_step


def _check_fedsgd_aggregator(aggregator: str) -> None:
    if aggregator != "weighted_mean":
        raise ValueError(
            "fedsgd collapses clients into one weighted forward/backward — "
            "per-client deltas never exist, so robust aggregators "
            f"({aggregator!r}) need the fedavg engine"
        )


def _check_fedsgd_compression(compression: Optional[CompressionConfig]) -> None:
    if compression is not None and compression.error_feedback:
        raise ValueError(
            "error feedback keeps a per-client compression residual, but "
            "fedsgd collapses clients into one weighted forward/backward — "
            "per-client deltas never exist; use the fedavg engine"
        )


def _check_fedsgd_corruption(kind: str) -> None:
    from repro.core.corruption import DELTA_KINDS

    if kind in DELTA_KINDS:
        raise ValueError(
            "delta corruptions transform per-client deltas, but fedsgd "
            "collapses clients into one weighted forward/backward — use "
            f"the fedavg engine for corruption kind {kind!r} (the "
            "data-plane 'label_shuffle' adversary works on either engine)"
        )


def _fedsgd_round_body(
    loss_fn,
    server_opt,
    sigma_fn,
    client_lr,
    base_key,
    state: ServerState,
    round_batch: PyTree,
    plane: Optional[ServerPlane] = None,
    latency_fn=None,
):
    plane = plane or _PARITY_PLANE
    K, S = jax.tree.leaves(round_batch)[0].shape[:2]
    ckey, qkey, _, _ = _plane_keys(base_key, state.round_idx)
    with jax.named_scope("server"), jax.named_scope("cohort"):
        round_batch, pmask = _apply_cohort(plane, ckey, round_batch)
    with jax.named_scope("client"):
        flat = jax.tree.map(lambda x: x.reshape((K * S * x.shape[2],) + x.shape[3:]), round_batch)
        key = fvn_lib.fvn_key(base_key, state.round_idx, 0, 0)
        p_eval = (
            state.params
            if sigma_fn is None
            else fvn_lib.perturb(state.params, key, sigma_fn(state.round_idx))
        )
        data_key = jax.random.fold_in(key, 1)
        (loss, _aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(p_eval, flat, data_key)
    with jax.named_scope("server"):
        with jax.named_scope("aggregate"):
            # delta of the 1-step client update = client_lr * grad
            wbar = jax.tree.map(lambda g: client_lr * g.astype(jnp.float32), grads)
        if plane.compression.kind != "none":
            # the collapsed engine has no per-client deltas; quantizing the
            # aggregate is the server-side proxy (bytes still counted
            # per reporting client in the wire metrics)
            with jax.named_scope("uplink"):
                wbar = plane.compress(wbar, qkey)
        with jax.named_scope("server_step"):
            updates, opt_state = server_opt.update(wbar, state.opt_state, state.params)
            params = apply_updates(state.params, updates)
        with jax.named_scope("round_metrics"):
            w = flat.get("weight")
            n = w.sum() if w is not None else jnp.asarray(K * S, jnp.float32)
            metrics = {
                "loss": loss,
                "examples": n,
                "delta_norm": jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(wbar))),
                # delta corruptions are fedavg-only (no per-client deltas here);
                # the data-plane label_shuffle adversary reports host-side
                "corrupted": jnp.float32(0.0),
                **_wire_metrics(plane, state.params, pmask, K),
                **_sim_time_metrics(latency_fn, base_key, state.round_idx, pmask, K),
            }
    return ServerState(
        params, opt_state, state.round_idx + 1, state.ef, state.stale, state.abuf
    ), metrics


def _check_sharding_engine(engine: str, client_sharding) -> None:
    if client_sharding is not None and engine == "fedsgd":
        raise ValueError(
            "fedsgd collapses clients into one weighted forward/backward — "
            "there is no per-client axis to shard; use the fedavg or async "
            "engine with client sharding (fedsgd weights shard over the "
            "model axes instead, see launch.sharding)"
        )


def make_round_step(loss_fn, plan: FederatedPlan, base_key, client_sharding=None):
    _check_sharding_engine(plan.engine, client_sharding)
    if plan.engine == "async":
        from repro.core.async_engine import make_async_round

        return make_async_round(loss_fn, plan, base_key, client_sharding)
    if plan.engine == "fedsgd":
        return _make_fedsgd_round(loss_fn, plan, base_key)
    return _make_fedavg_round(loss_fn, plan, base_key, client_sharding)


# ----------------------------------------------------------------------
# Hyper-parameterized round steps: every scalar knob a sweep varies
# (client/server lr, warmup/decay, FVN std + ramp) enters as a *traced*
# input instead of a Python constant, so ONE compiled round function
# serves every point of a sweep grid that shares batch shapes and the
# structural plan (engine + server optimizer family).
# ----------------------------------------------------------------------

HYPER_KEYS = (
    "client_lr",
    "server_lr",
    "warmup_rounds",
    "decay_rounds",
    "decay_rate",
    "fvn_std",
    "fvn_ramp",
    # server-plane knobs (cohort + aggregator), all traced
    "participation",
    "straggler_frac",
    "straggler_keep",
    "trim_frac",
    "dp_clip",
    "dp_sigma",
    # adversary knobs: rate/magnitude traced, kind static —
    # one compilation per (aggregator, kind) across a grid
    "corrupt_rate",
    "corrupt_scale",
    # async/wall-clock knobs: the staleness-discount exponent and the
    # latency model's scale/jitter are traced (buffer size and the
    # device-tier tables are static structure)
    "async_beta",
    "latency_base_s",
    "latency_spread",
)


def plan_hypers(plan: FederatedPlan) -> dict:
    """The plan's dynamic scalars as f32 arrays (FVN off -> std 0)."""
    return {
        "client_lr": jnp.float32(plan.client_lr),
        "server_lr": jnp.float32(plan.server_lr),
        "warmup_rounds": jnp.float32(plan.server_warmup_rounds),
        "decay_rounds": jnp.float32(plan.server_decay_rounds),
        "decay_rate": jnp.float32(plan.server_decay_rate),
        "fvn_std": jnp.float32(plan.fvn.std if plan.fvn.enabled else 0.0),
        "fvn_ramp": jnp.float32(plan.fvn.ramp_rounds if plan.fvn.enabled else 0),
        "participation": jnp.float32(plan.cohort.participation),
        "straggler_frac": jnp.float32(plan.cohort.straggler_frac),
        "straggler_keep": jnp.float32(plan.cohort.straggler_keep),
        "trim_frac": jnp.float32(plan.aggregation.trim_frac),
        "dp_clip": jnp.float32(plan.aggregation.dp_clip),
        "dp_sigma": jnp.float32(plan.aggregation.dp_sigma),
        "corrupt_rate": jnp.float32(plan.corruption.rate),
        "corrupt_scale": jnp.float32(plan.corruption.scale),
        "async_beta": jnp.float32(plan.asynchrony.staleness_beta),
        "latency_base_s": jnp.float32(plan.latency.base_s),
        "latency_spread": jnp.float32(plan.latency.spread),
    }


def _hyper_server_lr(hypers, count):
    """Unifies constant / linear-rampup / rampup+exp-decay (the three
    schedules of server_lr_schedule) into one traced formula, matching
    plan.server_lr_schedule exactly — including the decay path's
    max(warmup, 1) floor on the warmup window."""
    c = jnp.asarray(count, jnp.float32)
    w = jnp.where(
        hypers["decay_rounds"] > 0,
        jnp.maximum(hypers["warmup_rounds"], 1.0),
        hypers["warmup_rounds"],
    )
    warm = jnp.where(w > 0, jnp.minimum(c / jnp.maximum(w, 1.0), 1.0), 1.0)
    decay = jnp.where(
        hypers["decay_rounds"] > 0,
        hypers["decay_rate"]
        ** (jnp.maximum(c - w, 0.0) / jnp.maximum(hypers["decay_rounds"], 1.0)),
        1.0,
    )
    return hypers["server_lr"] * warm * decay


def _hyper_fvn_sigma(hypers, round_idx):
    c = jnp.asarray(round_idx, jnp.float32)
    frac = jnp.where(
        hypers["fvn_ramp"] > 0, jnp.minimum(c / jnp.maximum(hypers["fvn_ramp"], 1.0), 1.0), 1.0
    )
    return hypers["fvn_std"] * frac


def make_hyper_round_step(
    loss_fn,
    engine: str = "fedavg",
    server_optimizer: str = "adam",
    aggregator: str = "weighted_mean",
    compression: Optional[CompressionConfig] = None,
    corruption: str = "none",
    latency: Optional[LatencyConfig] = None,
    buffer_size: Optional[int] = None,
    client_sharding: Optional[ClientSharding] = None,
):
    """Returns round_step(state, round_batch, hypers, base_key).

    Only ``engine``, ``server_optimizer``, ``aggregator``,
    ``compression``, the ``corruption`` *kind*, the ``latency`` model's
    tier tables and the async ``buffer_size`` are compile-time
    structure (they change the graph / the wire layout); everything in
    ``hypers`` (see HYPER_KEYS / plan_hypers) is traced. The FVN
    perturbation, the cohort draw and the corruption draw always stay
    in the graph with traced knobs (sigma 0.0 / participation 1.0 /
    corrupt_rate 0.0 == off, bit-identical to the plain path), so
    on/off points share the compilation too. Because the cohort draw is
    unconditional, round batches must carry the data plane's "weight"
    leaf — the legacy weight-less layout is plan-path only.

    The latency draw is structural (``latency=None`` or
    ``enabled=False`` keeps it out of sync graphs entirely) because a
    zero-base draw cannot be distinguished from "no model" at trace
    time without burning RNG; its base/spread knobs are traced so one
    compilation serves a latency grid. ``engine="async"`` always draws
    arrivals and requires ``buffer_size`` (a static buffer shape).
    """
    from repro import optim

    server_opt_fns = {
        "adam": optim.adam,
        "sgd": optim.sgd,
        "momentum": optim.momentum,
        "yogi": optim.yogi,
    }
    make_server = server_opt_fns[server_optimizer]
    _check_sharding_engine(engine, client_sharding)
    if engine == "fedsgd":
        _check_fedsgd_aggregator(aggregator)
        _check_fedsgd_compression(compression)
        _check_fedsgd_corruption(corruption)
    if engine == "async":
        if not buffer_size or buffer_size < 1:
            raise ValueError(
                "the async engine's buffer is compile-time structure: pass "
                f"buffer_size >= 1 to make_hyper_round_step (got {buffer_size!r})"
            )
        latency = latency or LatencyConfig()

    def round_step(state: ServerState, round_batch: PyTree, hypers: dict, base_key):
        server_opt = make_server(lambda count: _hyper_server_lr(hypers, count))
        sigma_fn = lambda r: _hyper_fvn_sigma(hypers, r)
        plane = _make_server_plane(
            aggregator,
            compression,
            (hypers["participation"], hypers["straggler_frac"], hypers["straggler_keep"]),
            {
                "trim_frac": hypers["trim_frac"],
                "dp_clip": hypers["dp_clip"],
                "dp_sigma": hypers["dp_sigma"],
            },
            corruption_kind=corruption,
            corruption_knobs=(hypers["corrupt_rate"], hypers["corrupt_scale"]),
        )
        latency_fn = None
        if latency is not None and (latency.enabled or engine == "async"):
            latency_fn = make_latency_fn(
                latency, hypers["latency_base_s"], hypers["latency_spread"]
            )
        if engine == "fedsgd":
            return _fedsgd_round_body(
                loss_fn, server_opt, sigma_fn, hypers["client_lr"], base_key, state,
                round_batch, plane, latency_fn,
            )
        client_opt = sgd(lambda count: hypers["client_lr"])
        if engine == "async":
            from repro.core.async_engine import _async_round_body

            return _async_round_body(
                loss_fn, client_opt, server_opt, sigma_fn, base_key, state, round_batch,
                plane, latency_fn, buffer_size, hypers["async_beta"], client_sharding,
            )
        return _fedavg_round_body(
            loss_fn, client_opt, server_opt, sigma_fn, base_key, state, round_batch, plane,
            latency_fn, client_sharding,
        )

    return round_step


def server_state_specs(
    plan: FederatedPlan,
    param_specs,
    moment_specs=None,
    ef_specs=None,
    stale_specs=None,
):
    """PartitionSpec tree matching init_server_state's output.

    ``moment_specs`` lets the launcher FSDP-shard optimizer moments
    independently of the live params (they only touch aggregation).
    ``ef_specs`` shards the per-client EF residuals; the default keeps
    each residual with its client's replica (leading K axis unsharded,
    trailing axes like the params). ``stale_specs`` does the same for
    the stale-replay delta cache. The async buffer's pending deltas
    reuse the same leading-axis layout (buffer slots unsharded)."""
    from jax.sharding import PartitionSpec as P

    from repro.optim.optimizers import AdamState, MomentumState, ScaleState

    moment_specs = param_specs if moment_specs is None else moment_specs
    opt = plan.server_optimizer
    if opt == "sgd":
        os_ = ScaleState(count=P())
    elif opt == "momentum":
        os_ = MomentumState(count=P(), trace=moment_specs)
    else:  # adam | yogi
        os_ = AdamState(count=P(), mu=moment_specs, nu=moment_specs)

    def client_axis_specs(override):
        if override is not None:
            return override
        return jax.tree.map(
            lambda s: P(*((None,) + tuple(s))), param_specs, is_leaf=lambda x: isinstance(x, P)
        )

    ef = client_axis_specs(ef_specs) if plan.compression.error_feedback else None
    stale = client_axis_specs(stale_specs) if plan.corruption.kind == "stale" else None
    abuf = None
    if plan.engine == "async":
        from repro.core.async_engine import AsyncBuffer

        abuf = AsyncBuffer(
            deltas=client_axis_specs(None),
            weights=P(),
            versions=P(),
            count=P(),
            version=P(),
        )
    return ServerState(
        params=param_specs, opt_state=os_, round_idx=P(), ef=ef, stale=stale, abuf=abuf
    )
