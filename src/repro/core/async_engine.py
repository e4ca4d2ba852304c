"""Buffered-asynchronous round engine (FedBuff-style streaming server).

The sync engines simulate the paper's barrier round: sample K clients,
wait for every report, aggregate once. The motivating deployment is
millions of phones reporting whenever they are charging and idle — the
barrier is a simulator artifact, and it prices the round at the
SLOWEST participant's arrival. This engine removes the barrier the way
production async FL does (FedBuff, Nguyen et al. 2022):

- every wave, K sampled clients download the CURRENT params and train
  locally (the same vmapped ``fedavg._client_update``, cohort stage
  and compression/corruption payload pipeline — one code path for
  what a client computes and uploads);
- each upload *arrives* at a simulated time drawn from the device-tier
  latency model (``cohort.LatencyConfig``): tiers are categorical
  compile-time structure, base latency and lognormal jitter are traced
  hyper scalars;
- the server consumes arrivals in time order into a size-B buffer
  (``AsyncBuffer``) and applies one optimizer step whenever the buffer
  fills, discounting each buffered delta by its staleness
  ``1 / (1 + s)**beta`` where ``s`` counts server versions applied
  since that client downloaded;
- the buffer PERSISTS across waves in ``ServerState.abuf``: a
  straggler's update lands in a later flush (stale-discounted) instead
  of being dropped, exactly the behaviour the ``ServerState.stale``
  replay cache approximated adversarially in PR 4.

Staleness discipline: all of a wave's clients download at the wave's
opening version ``v0``; a flush mid-wave bumps the server version, so
later arrivals of the same wave are already one version stale when
they eventually flush. The discount SCALES each delta *before* the
aggregator's weight normalization — a discount folded into the
aggregation weights would cancel whenever a flush's staleness is
uniform (the weighted mean renormalizes), which is precisely the
common case.

Wall-clock accounting: a wave's simulated duration ``sim_time_s`` is
the arrival time of its LAST FLUSH — the moment the final server step
of the wave landed. Arrivals after the last flush sit in the buffer
and are paid for in the wave that flushes them. A wave with no flush
costs its last participant arrival (the stream still had to be
observed). This is what gives async its genuine edge over the barrier
engine on the CFMQ wall-clock axis: the tail of the latency
distribution stops gating every server step.

Parity (tested bit-for-bit): with B = K, full participation, one
device tier and zero jitter spread, a wave inserts K equal-time
arrivals in client order (the arrival argsort is stable, so equal
times keep the identity permutation), flushes exactly once with
staleness 0 — ``staleness_discount`` returns exactly 1.0 — and the
flush reduces to the sync engine's aggregate + server step.

Everything jit-friendly: ``buffer_size`` is static (it shapes the
buffer), ``beta`` and the latency knobs are traced, and the arrival
stream is a ``lax.scan`` whose flush is a ``lax.cond`` — one
compilation serves an async sweep grid.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import fvn as fvn_lib
from repro.core.cohort import make_latency_fn
from repro.core.fedavg import (
    ServerPlane,
    ServerState,
    _apply_cohort,
    _client_axis_zeros,
    _client_key_fanout,
    _client_update_stage,
    _delta_payload_stage,
    _latency_key,
    _plane_keys,
    _wire_metrics,
    _plan_server_plane,
)
from repro.core.plan import FederatedPlan, make_server_optimizer
from repro.optim import apply_updates, sgd

PyTree = Any


class AsyncBuffer(NamedTuple):
    """The server's pending-update buffer (lives in ServerState.abuf).

    Slots [0, count) are filled; a flush logically empties the buffer
    by resetting ``count`` (stale slot payloads are overwritten before
    they can be read again). ``version`` counts applied server steps —
    the staleness clock."""

    deltas: PyTree          # (B, ...) pending per-client deltas
    weights: jnp.ndarray    # (B,) f32 example counts n_k per slot
    versions: jnp.ndarray   # (B,) i32 server version at download time
    count: jnp.ndarray      # () i32 filled slots
    version: jnp.ndarray    # () i32 server version (total flushes)


def init_async_buffer(params: PyTree, buffer_size: int) -> AsyncBuffer:
    return AsyncBuffer(
        deltas=_client_axis_zeros(params, buffer_size),
        weights=jnp.zeros((buffer_size,), jnp.float32),
        versions=jnp.zeros((buffer_size,), jnp.int32),
        count=jnp.zeros((), jnp.int32),
        version=jnp.zeros((), jnp.int32),
    )


def staleness_discount(staleness, beta):
    """``1/(1+s)**beta`` computed as ``exp(-beta * log1p(s))``: exactly
    1.0 (bitwise) both at s == 0 for any beta and at beta == 0 for any
    s — exp(0.0) is exact — so the sync-parity and unweighted edge
    cases cost no tolerance."""
    return jnp.exp(-beta * jnp.log1p(jnp.asarray(staleness, jnp.float32)))


def _async_round_body(
    loss_fn,
    client_opt,
    server_opt,
    sigma_fn,
    base_key,
    state: ServerState,
    round_batch: PyTree,
    plane: ServerPlane,
    latency_fn: Callable,
    buffer_size: int,
    beta,
    sharding=None,
):
    """One wave of the buffered-async engine (one jitted graph):
    client deltas -> cohort -> payload pipeline -> time-ordered arrival
    stream -> buffer inserts -> staleness-discounted flushes. The stages
    carry the sync body's ``jax.named_scope``s (``client``, ``server``
    and its children; see ``fedavg._fedavg_round_body``).

    With ``sharding`` only the client-update stage shards (the heavy
    per-client local training); the arrival stream is inherently
    sequential server-side state and stays on the gathered global axis,
    so the sharded wave is bit-for-bit the vmap wave."""
    B = buffer_size
    K = jax.tree.leaves(round_batch)[0].shape[0]
    ckey, qkey, akey, xkey = _plane_keys(base_key, state.round_idx)

    with jax.named_scope("server"), jax.named_scope("cohort"):
        round_batch, pmask = _apply_cohort(plane, ckey, round_batch)

    with jax.named_scope("client"):
        deltas, losses, n_k = _client_update_stage(
            loss_fn, client_opt, sigma_fn, base_key, state.params, round_batch,
            state.round_idx, sharding,
        )

    with jax.named_scope("server"), jax.named_scope("uplink"):
        ckeys = _client_key_fanout(plane, qkey, K)
        deltas, ef, cmask, stale = _delta_payload_stage(
            plane, deltas, state.ef, pmask, ckeys, xkey, state.stale
        )

    # Arrival order: participants by simulated upload time, then
    # non-participants (time +inf — they never upload). The argsort is
    # stable, so the zero-spread parity configuration (all times equal)
    # keeps the identity permutation and stays bit-compatible with the
    # sync engine's client order.
    with jax.named_scope("server"):
        times = latency_fn(_latency_key(base_key, state.round_idx), K)
        order = jnp.argsort(jnp.where(pmask > 0, times, jnp.inf))
        arr = (
            jax.tree.map(lambda d: d[order], deltas),
            n_k[order],
            pmask[order],
            times[order],
        )
    v0 = state.abuf.version  # every wave client downloaded at wave start

    def arrival(carry, inp):
        params, opt_state, buf, flushed, t_last, stale_sum, applied = carry
        d_i, w_i, p_i, t_i = inp

        # Insert: always WRITE slot buf.count (it is beyond the filled
        # region, so a non-participant's write is never read), but only
        # a participant bumps count. A dropped client therefore
        # occupies no slot and triggers no flush.
        new_deltas = jax.tree.map(
            lambda bl, d: jax.lax.dynamic_update_index_in_dim(bl, d, buf.count, 0),
            buf.deltas,
            d_i,
        )
        new_w = jax.lax.dynamic_update_index_in_dim(buf.weights, w_i, buf.count, 0)
        new_v = jax.lax.dynamic_update_index_in_dim(buf.versions, v0, buf.count, 0)
        count = buf.count + (p_i > 0).astype(jnp.int32)

        def flush(op):
            params, opt_state, flushed, t_last, stale_sum, applied = op
            with jax.named_scope("aggregate"):
                s = (buf.version - new_v).astype(jnp.float32)  # (B,) >= 0
                disc = staleness_discount(s, beta)
                # Discount BEFORE aggregation: the aggregator normalizes its
                # weights, so a uniform per-flush discount folded into the
                # weights would cancel exactly.
                scaled = jax.tree.map(
                    lambda d: d * disc.reshape((B,) + (1,) * (d.ndim - 1)), new_deltas
                )
                fkey = jax.random.fold_in(akey, buf.version)
                wbar = plane.aggregate(scaled, new_w, jnp.ones((B,), jnp.float32), fkey)
            with jax.named_scope("server_step"):
                updates, opt_state = server_opt.update(wbar, opt_state, params)
                params = apply_updates(params, updates)
            return (params, opt_state, flushed + 1, t_i, stale_sum + s.sum(),
                    applied + B, jnp.zeros((), jnp.int32), buf.version + 1)

        def hold(op):
            params, opt_state, flushed, t_last, stale_sum, applied = op
            return (params, opt_state, flushed, t_last, stale_sum, applied,
                    count, buf.version)

        params, opt_state, flushed, t_last, stale_sum, applied, count, version = jax.lax.cond(
            count == B, flush, hold,
            (params, opt_state, flushed, t_last, stale_sum, applied),
        )
        buf = AsyncBuffer(new_deltas, new_w, new_v, count, version)
        return (params, opt_state, buf, flushed, t_last, stale_sum, applied), None

    init = (state.params, state.opt_state, state.abuf, jnp.zeros((), jnp.int32),
            jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.int32))
    with jax.named_scope("server"):
        (params, opt_state, buf, flushed, t_last, stale_sum, applied), _ = jax.lax.scan(
            arrival, init, arr
        )

    # Wave wall-clock: the last flush's arrival time. Updates buffered
    # past the last flush are paid for by the wave that flushes them; a
    # flushless wave still observes its stream to the last participant.
    with jax.named_scope("server"), jax.named_scope("round_metrics"):
        t_stream = (times * pmask).max()
        sim_time = jnp.where(flushed > 0, t_last, t_stream)
        # delta_norm here is the wave's total parameter displacement (the
        # sync engines report the aggregated pseudo-gradient norm; a wave
        # applies 0..K server steps, so displacement is the analogue).
        disp = jax.tree.map(lambda a, b: a - b, params, state.params)
        n = jnp.maximum(n_k.sum(), 1.0)
        metrics = {
            "loss": (losses * n_k).sum() / n,
            "examples": n_k.sum(),
            "delta_norm": jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(disp))),
            "corrupted": cmask.sum(),
            **_wire_metrics(plane, state.params, pmask, K),
            "sim_time_s": sim_time,
            "server_steps": flushed.astype(jnp.float32),
            "staleness_mean": stale_sum / jnp.maximum(applied.astype(jnp.float32), 1.0),
        }
    return ServerState(params, opt_state, state.round_idx + 1, ef, stale, buf), metrics


def make_async_round(
    loss_fn: Callable,
    plan: FederatedPlan,
    base_key,
    client_sharding=None,
) -> Callable[[ServerState, PyTree], tuple[ServerState, dict]]:
    """Returns round_step(state, round_batch) -> (state, metrics) for
    plan.engine == "async". round_batch layout matches the fedavg
    engine: (K, S_local, b, ...) with a "weight" leaf. The state must
    come from ``init_server_state`` (it carries the AsyncBuffer). The
    arrival latency model is plan.latency — the async engine always
    draws arrival times (it needs the order), whether or not
    ``latency.enabled`` marks sync rounds for wall-clock pricing."""
    client_opt = sgd(plan.client_lr)
    server_opt = make_server_optimizer(plan)
    sigma_fn = (lambda r: fvn_lib.fvn_sigma(plan.fvn, r)) if plan.fvn.enabled else None
    plane = _plan_server_plane(plan)
    latency_fn = make_latency_fn(plan.latency)
    buffer_size = plan.asynchrony.resolve_buffer(plan.clients_per_round)
    beta = plan.asynchrony.staleness_beta
    if client_sharding is not None:
        client_sharding.check_clients(plan.clients_per_round)

    def round_step(state: ServerState, round_batch: PyTree):
        return _async_round_body(
            loss_fn, client_opt, server_opt, sigma_fn, base_key, state, round_batch,
            plane, latency_fn, buffer_size, beta, client_sharding,
        )

    return round_step
