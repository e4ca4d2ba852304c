"""Federated engine correctness: the paper's Alg. 1 invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FederatedPlan, FVNConfig, init_server_state, make_round_step

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - deterministic fallback below
    HAVE_HYPOTHESIS = False

W_TRUE = np.random.default_rng(42).normal(size=(4, 2)).astype(np.float32)


def loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"]
    w = batch["weight"]
    l = jnp.sum((pred - batch["y"]) ** 2 * w[:, None]) / jnp.maximum(w.sum(), 1)
    return l, {}


def make_batch(K, S, b, seed=0, weights=None):
    r = np.random.default_rng(seed)
    x = r.normal(size=(K, S, b, 4)).astype(np.float32)
    y = x @ W_TRUE
    w = np.ones((K, S, b), np.float32) if weights is None else weights
    return {"x": jnp.array(x), "y": jnp.array(y), "weight": jnp.array(w)}


def params0():
    return {"w": jnp.zeros((4, 2))}


def test_single_client_single_step_equals_sgd():
    plan = FederatedPlan(clients_per_round=1, client_lr=0.1,
                         server_optimizer="sgd", server_lr=1.0)
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(0)))
    state = init_server_state(plan, params0())
    batch = make_batch(1, 1, 8)
    state2, _ = step(state, batch)
    g = jax.grad(lambda p: loss_fn(p, jax.tree.map(lambda a: a[0, 0], batch), None)[0])(params0())
    manual = params0()["w"] - 0.1 * g["w"]
    np.testing.assert_allclose(np.asarray(state2.params["w"]), np.asarray(manual), atol=1e-6)


def test_fedsgd_equals_fedavg_one_local_step():
    kw = dict(clients_per_round=4, client_lr=0.1, server_optimizer="sgd", server_lr=1.0)
    batch = make_batch(4, 1, 8, seed=1)
    outs = []
    for engine in ("fedavg", "fedsgd"):
        plan = FederatedPlan(engine=engine, **kw)
        st_ = init_server_state(plan, params0())
        st2, _ = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(0)))(st_, batch)
        outs.append(np.asarray(st2.params["w"]))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)


def _check_client_permutation_invariance(perm_seed):
    plan = FederatedPlan(clients_per_round=4, client_lr=0.1,
                         server_optimizer="adam", server_lr=0.05)
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(0)))
    state = init_server_state(plan, params0())
    batch = make_batch(4, 2, 4, seed=2)
    perm = np.random.default_rng(perm_seed).permutation(4)
    batch_p = jax.tree.map(lambda a: a[perm], batch)
    s1, _ = step(state, batch)
    s2, _ = step(state, batch_p)
    np.testing.assert_allclose(np.asarray(s1.params["w"]),
                               np.asarray(s2.params["w"]), atol=1e-5)


@pytest.mark.parametrize("perm_seed", [0, 17, 123, 999])
def test_client_permutation_invariance_deterministic(perm_seed):
    _check_client_permutation_invariance(perm_seed)


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(perm_seed=st.integers(0, 1000))
    def test_client_permutation_invariance(perm_seed):
        _check_client_permutation_invariance(perm_seed)


def test_zero_weight_clients_contribute_nothing():
    plan = FederatedPlan(clients_per_round=3, client_lr=0.1,
                         server_optimizer="sgd", server_lr=1.0)
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(0)))
    state = init_server_state(plan, params0())
    w = np.ones((3, 2, 4), np.float32)
    w[2] = 0.0                                  # client 2 is all padding
    b3 = make_batch(3, 2, 4, seed=3, weights=w)
    b2 = jax.tree.map(lambda a: a[:2], make_batch(3, 2, 4, seed=3))
    plan2 = FederatedPlan(clients_per_round=2, client_lr=0.1,
                          server_optimizer="sgd", server_lr=1.0)
    s3, _ = step(state, b3)
    s2, _ = jax.jit(make_round_step(loss_fn, plan2, jax.random.PRNGKey(0)))(
        init_server_state(plan2, params0()), b2)
    np.testing.assert_allclose(np.asarray(s3.params["w"]),
                               np.asarray(s2.params["w"]), atol=1e-6)


def test_example_weighted_aggregation():
    """A client with 3x the examples pulls the average 3x harder (n_k/n)."""
    plan = FederatedPlan(clients_per_round=2, client_lr=0.1,
                         server_optimizer="sgd", server_lr=1.0)
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(0)))
    state = init_server_state(plan, params0())
    w = np.ones((2, 1, 8), np.float32)
    w[1, :, 2:] = 0.0                            # client 1 has 2 real examples
    batch = make_batch(2, 1, 8, seed=5, weights=w)
    s, _ = step(state, batch)

    # manual: per-client one SGD step, delta weighted by n_k/n
    deltas = []
    for k in range(2):
        cb = jax.tree.map(lambda a: a[k, 0], batch)
        g = jax.grad(lambda p: loss_fn(p, cb, None)[0])(params0())
        deltas.append(0.1 * g["w"])
    n = np.array([8.0, 2.0])
    wbar = (n[0] * deltas[0] + n[1] * deltas[1]) / n.sum()
    manual = params0()["w"] - wbar
    np.testing.assert_allclose(np.asarray(s.params["w"]), np.asarray(manual), atol=1e-6)


def test_fvn_determinism_and_effect():
    kw = dict(clients_per_round=2, client_lr=0.1,
              server_optimizer="sgd", server_lr=1.0)
    plan = FederatedPlan(fvn=FVNConfig(enabled=True, std=0.05), **kw)
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(9)))
    state = init_server_state(plan, params0())
    batch = make_batch(2, 2, 4, seed=6)
    s1, m1 = step(state, batch)
    s2, m2 = step(state, batch)
    np.testing.assert_allclose(np.asarray(s1.params["w"]), np.asarray(s2.params["w"]))
    plan_off = FederatedPlan(**kw)
    s3, _ = jax.jit(make_round_step(loss_fn, plan_off, jax.random.PRNGKey(9)))(
        init_server_state(plan_off, params0()), batch)
    assert float(jnp.abs(s1.params["w"] - s3.params["w"]).max()) > 1e-7


def test_fvn_sigma_ramp():
    from repro.core.fvn import fvn_sigma

    cfg = FVNConfig(enabled=True, std=0.03, ramp_rounds=100)
    assert float(fvn_sigma(cfg, 0)) == 0.0
    np.testing.assert_allclose(float(fvn_sigma(cfg, 50)), 0.015, rtol=1e-6)
    np.testing.assert_allclose(float(fvn_sigma(cfg, 100)), 0.03, rtol=1e-6)
    np.testing.assert_allclose(float(fvn_sigma(cfg, 500)), 0.03, rtol=1e-6)
    assert float(fvn_sigma(FVNConfig(enabled=False), 10)) == 0.0


def test_convergence_on_regression():
    plan = FederatedPlan(clients_per_round=4, client_lr=0.05,
                         server_optimizer="adam", server_lr=0.05,
                         fvn=FVNConfig(enabled=True, std=0.01, ramp_rounds=10))
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(1)))
    state = init_server_state(plan, params0())
    losses = []
    for r in range(40):
        state, m = step(state, make_batch(4, 3, 8, seed=100 + r))
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.15 * losses[0]


# ------------------------------------------------------ error feedback

def test_error_feedback_recovers_topk_quality_at_same_bytes():
    """The acceptance criterion in miniature: EF21 top-k at 5% reaches
    strictly better final loss than plain top-k at *identical* wire
    bytes (EF changes what travels in the payload, not its size).

    Plain-SGD server (w += wbar), matching the ef_compression grid:
    EF21's guarantee is for the update applied as-is — an adaptive
    server renormalizes the delayed residual bursts and can diverge
    (documented in the grid's docstring and ROADMAP)."""
    from repro.core import CompressionConfig, client_wire_bytes

    def run(comp):
        plan = FederatedPlan(clients_per_round=4, client_lr=0.05,
                             server_optimizer="sgd", server_lr=1.0,
                             compression=comp)
        step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(1)))
        state = init_server_state(plan, params0())
        losses = []
        for r in range(40):
            state, m = step(state, make_batch(4, 3, 8, seed=100 + r))
            losses.append(float(m["loss"]))
        return float(np.mean(losses[-5:])), plan

    plain_loss, plain_plan = run(CompressionConfig(kind="topk", topk_frac=0.05))
    ef_loss, ef_plan = run(CompressionConfig(kind="topk", topk_frac=0.05,
                                             error_feedback=True))
    assert (client_wire_bytes(ef_plan.compression, params0())
            == client_wire_bytes(plain_plan.compression, params0()))
    assert ef_loss < plain_loss


def test_error_feedback_state_threads_through_rounds():
    from repro.core import CompressionConfig

    plan = FederatedPlan(clients_per_round=3, client_lr=0.1,
                         server_optimizer="sgd", server_lr=1.0,
                         compression=CompressionConfig(kind="topk",
                                                       topk_frac=0.2,
                                                       error_feedback=True))
    state = init_server_state(plan, params0())
    assert state.ef is not None
    np.testing.assert_array_equal(np.asarray(state.ef["w"]),
                                  np.zeros((3, 4, 2)))
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(0)))
    state2, _ = step(state, make_batch(3, 2, 4))
    # top-k drops coordinates, so some residual must be nonzero
    assert float(jnp.abs(state2.ef["w"]).max()) > 0
    # without EF no residual state exists
    plan_off = FederatedPlan(clients_per_round=3)
    assert init_server_state(plan_off, params0()).ef is None


def test_error_feedback_keeps_dropped_client_residuals():
    """A non-participant uploads nothing: its residual must survive the
    round untouched (C(0 + e_k) is nonzero, so this needs the explicit
    participant select, unlike the plain path where delta is 0)."""
    from repro.core import CompressionConfig, CohortConfig
    from repro.core.cohort import participation_mask
    from repro.core.fedavg import _plane_keys

    base_key = jax.random.PRNGKey(4)  # a key whose 50% draw drops 2 of 4
    plan = FederatedPlan(clients_per_round=4, client_lr=0.1,
                         server_optimizer="sgd", server_lr=1.0,
                         cohort=CohortConfig(participation=0.5),
                         compression=CompressionConfig(kind="topk",
                                                       topk_frac=0.2,
                                                       error_feedback=True))
    state = init_server_state(plan, params0())
    marker = jax.tree.map(lambda e: jnp.full_like(e, 0.125), state.ef)
    state = state._replace(ef=marker)
    step = jax.jit(make_round_step(loss_fn, plan, base_key))
    state2, m = step(state, make_batch(4, 2, 4, seed=7))

    ckey, _, _, _ = _plane_keys(base_key, jnp.zeros((), jnp.int32))
    pmask = np.asarray(participation_mask(jax.random.fold_in(ckey, 0), 4,
                                          plan.cohort.participation))
    assert 0 < pmask.sum() < 4                       # the draw actually split
    ef = np.asarray(state2.ef["w"])
    for k in range(4):
        if pmask[k]:
            assert np.abs(ef[k] - 0.125).max() > 1e-9
        else:
            np.testing.assert_array_equal(ef[k], np.full((4, 2), 0.125))


def test_error_feedback_rejects_fedsgd():
    from repro.core import CompressionConfig, make_hyper_round_step

    plan = FederatedPlan(engine="fedsgd",
                         compression=CompressionConfig(kind="int8",
                                                       error_feedback=True))
    with pytest.raises(ValueError, match="per-client"):
        make_round_step(loss_fn, plan, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="per-client"):
        make_hyper_round_step(loss_fn, engine="fedsgd",
                              compression=plan.compression)
