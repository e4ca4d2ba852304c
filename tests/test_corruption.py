"""Adversarial client-corruption plane: registry semantics, the
corruption x cohort x error-feedback composition invariants, traced
rate/scale compile sharing, and the data-plane label_shuffle knob."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AggregatorConfig,
    CohortConfig,
    CompressionConfig,
    CorruptionConfig,
    FederatedPlan,
    available_corruptions,
    get_corruption,
    init_server_state,
    make_hyper_round_step,
    make_round_step,
    plan_hypers,
)
from repro.core.corruption import DELTA_KINDS, KINDS, make_corruption_fn

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


BASE = dict(clients_per_round=4, client_lr=0.1, server_optimizer="sgd",
            server_lr=1.0)


def run_one(corruption=None, plan_kw=None, seed=0, key=0, state=None,
            rounds=1):
    plan = FederatedPlan(**dict(BASE, **(plan_kw or {})),
                         corruption=corruption or CorruptionConfig())
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(key)))
    state = state if state is not None else init_server_state(plan, params0())
    for r in range(rounds):
        state, m = step(state, make_batch(4, 2, 4, seed=seed + r))
    return state, m


# ------------------------------------------------------------ registry

def test_registry_contents():
    assert set(DELTA_KINDS) == {"sign_flip", "gaussian", "zero", "stale"}
    assert set(available_corruptions()) == set(DELTA_KINDS)
    assert "label_shuffle" in KINDS and "none" in KINDS
    with pytest.raises(KeyError, match="unknown corruption"):
        get_corruption("krum")


def test_config_validation():
    with pytest.raises(ValueError, match="unknown corruption kind"):
        CorruptionConfig(kind="bitrot")
    with pytest.raises(ValueError, match="rate"):
        CorruptionConfig(kind="zero", rate=1.5)
    assert not CorruptionConfig().active
    assert CorruptionConfig(kind="zero", rate=0.1).active
    assert CorruptionConfig(kind="sign_flip", rate=0.1).in_graph
    assert not CorruptionConfig(kind="label_shuffle", rate=0.1).in_graph


def test_fedsgd_rejects_delta_corruptions():
    plan = FederatedPlan(engine="fedsgd",
                        corruption=CorruptionConfig(kind="sign_flip", rate=0.1))
    with pytest.raises(ValueError, match="fedsgd"):
        make_round_step(loss_fn, plan, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="fedsgd"):
        make_hyper_round_step(loss_fn, "fedsgd", "adam", corruption="zero")
    # the data-plane adversary composes with either engine
    make_hyper_round_step(loss_fn, "fedsgd", "adam", corruption="label_shuffle")


def test_stale_without_cache_raises():
    fn = make_corruption_fn("stale", 1.0, 1.0)
    deltas = {"w": jnp.ones((3, 2))}
    with pytest.raises(ValueError, match="ServerState"):
        fn(jax.random.PRNGKey(0), deltas, jnp.ones((3,)), None)


# ------------------------------------------------- adversary semantics

def test_rate_zero_is_bit_exact_parity():
    """An armed adversary at rate 0 must equal the honest plane exactly
    (the clean row of a robustness grid is the paper's run)."""
    s_honest, m_honest = run_one()
    for kind in DELTA_KINDS:
        s, m = run_one(CorruptionConfig(kind=kind, rate=0.0, scale=3.0))
        np.testing.assert_array_equal(np.asarray(s_honest.params["w"]),
                                      np.asarray(s.params["w"]))
        assert float(m["corrupted"]) == 0.0


def test_sign_flip_negates_the_update():
    s_honest, _ = run_one()
    s_bad, m = run_one(CorruptionConfig(kind="sign_flip", rate=1.0, scale=1.0))
    assert float(m["corrupted"]) == 4.0
    np.testing.assert_allclose(np.asarray(s_bad.params["w"]),
                               -np.asarray(s_honest.params["w"]), atol=1e-7)


def test_zero_update_freezes_the_server():
    s, m = run_one(CorruptionConfig(kind="zero", rate=1.0))
    np.testing.assert_array_equal(np.asarray(s.params["w"]),
                                  np.asarray(params0()["w"]))
    assert float(m["corrupted"]) == 4.0


def test_gaussian_noise_tracks_delta_scale():
    """Noise rides at scale x rms(delta): honest direction survives at
    tiny scale, drowns at huge scale."""
    s_honest, _ = run_one()
    s_small, _ = run_one(CorruptionConfig(kind="gaussian", rate=1.0, scale=1e-3))
    s_big, _ = run_one(CorruptionConfig(kind="gaussian", rate=1.0, scale=1e3))
    honest = np.asarray(s_honest.params["w"])
    small = np.linalg.norm(np.asarray(s_small.params["w"]) - honest)
    big = np.linalg.norm(np.asarray(s_big.params["w"]) - honest)
    assert small < 1e-3 * np.linalg.norm(honest) * 10
    assert big > 1e2 * np.linalg.norm(honest)


def test_stale_replays_last_transmission():
    """Round 0 an all-stale cohort sends the zero cache (server frozen);
    round 1 it replays round 0's honest deltas — two corrupted rounds
    land where ONE honest round would have."""
    cfg = CorruptionConfig(kind="stale", rate=1.0, scale=1.0)
    s_stale, m = run_one(cfg, rounds=2)
    assert s_stale.stale is not None
    s_honest, _ = run_one()                      # one honest round, same data
    np.testing.assert_allclose(np.asarray(s_stale.params["w"]),
                               np.asarray(s_honest.params["w"]), atol=1e-6)


def test_corruption_never_changes_wire_bytes():
    """A corrupted participant still pays full uplink: CFMQ accounting
    is identical under any adversary (the grid moves quality only)."""
    _, m_honest = run_one()
    for kind in DELTA_KINDS:
        _, m = run_one(CorruptionConfig(kind=kind, rate=1.0))
        assert float(m["uplink_bytes"]) == float(m_honest["uplink_bytes"])
        assert float(m["downlink_bytes"]) == float(m_honest["downlink_bytes"])
        assert float(m["participants"]) == float(m_honest["participants"])


# -------------------------------------- composition: cohort x EF x adv

def test_corrupted_nonparticipant_contributes_nothing():
    """Regression (cohort x corruption x error_feedback): a client that
    is both corrupted and a cohort non-participant must contribute
    neither delta nor EF residual update — dropout always wins."""
    from repro.core.cohort import participation_mask
    from repro.core.fedavg import _plane_keys

    base_key = jax.random.PRNGKey(4)  # a key whose 50% draw drops 2 of 4
    plan_kw = dict(cohort=CohortConfig(participation=0.5),
                   compression=CompressionConfig(kind="topk", topk_frac=0.2,
                                                 error_feedback=True))
    cfg = CorruptionConfig(kind="sign_flip", rate=1.0, scale=5.0)
    plan = FederatedPlan(**dict(BASE, **plan_kw), corruption=cfg)
    state = init_server_state(plan, params0())
    marker = jax.tree.map(lambda e: jnp.full_like(e, 0.125), state.ef)
    state = state._replace(ef=marker)
    step = jax.jit(make_round_step(loss_fn, plan, base_key))
    state2, m = step(state, make_batch(4, 2, 4, seed=7))

    ckey, _, _, _ = _plane_keys(base_key, jnp.zeros((), jnp.int32))
    pmask = np.asarray(participation_mask(jax.random.fold_in(ckey, 0), 4,
                                          plan.cohort.participation))
    assert 0 < pmask.sum() < 4                      # the draw actually split
    # every corrupted client is a participant: cmask = drawn * pmask
    assert float(m["corrupted"]) == float(pmask.sum())
    ef = np.asarray(state2.ef["w"])
    for k in range(4):
        if pmask[k]:
            assert np.abs(ef[k] - 0.125).max() > 1e-9
        else:
            np.testing.assert_array_equal(ef[k], np.full((4, 2), 0.125))


def test_corrupted_dropped_client_delta_is_not_resurrected():
    """sign_flip at rate 1 with a partial cohort must equal sign_flip
    applied to the participants only: a dropped client's zero delta
    stays zero (flipping 0 is 0, but a stale/gaussian adversary could
    re-inject mass — the cmask*pmask select is what prevents it)."""
    plan_kw = dict(cohort=CohortConfig(participation=0.5))
    # stale with a warm cache is the dangerous kind: round 2's replay
    # would hand every client (participant or not) a nonzero delta
    cfg = CorruptionConfig(kind="stale", rate=1.0, scale=1.0)
    plan = FederatedPlan(**dict(BASE, **plan_kw), corruption=cfg)
    step = jax.jit(make_round_step(loss_fn, plan, jax.random.PRNGKey(5)))
    state = init_server_state(plan, params0())
    for r in range(3):
        state, m = step(state, make_batch(4, 2, 4, seed=30 + r))
        assert float(m["corrupted"]) <= float(m["participants"])
        # stale cache rows of non-participants never update; all rows
        # stay finite
        assert np.isfinite(np.asarray(state.stale["w"])).all()


def test_hyper_path_matches_plan_path_under_attack():
    plan = FederatedPlan(
        clients_per_round=4, client_lr=0.1, server_optimizer="adam",
        server_lr=0.05,
        cohort=CohortConfig(participation=0.6),
        aggregation=AggregatorConfig(name="trimmed_mean", trim_frac=0.2),
        corruption=CorruptionConfig(kind="sign_flip", rate=0.5, scale=2.0))
    key = jax.random.PRNGKey(11)
    plain = jax.jit(make_round_step(loss_fn, plan, key))
    hyper = jax.jit(make_hyper_round_step(loss_fn, "fedavg", "adam",
                                          "trimmed_mean",
                                          corruption="sign_flip"))
    hypers = plan_hypers(plan)
    s1 = s2 = init_server_state(plan, params0())
    for r in range(3):
        batch = make_batch(4, 2, 4, seed=20 + r)
        s1, m1 = plain(s1, batch)
        s2, m2 = hyper(s2, batch, hypers, key)
    np.testing.assert_allclose(np.asarray(s1.params["w"]),
                               np.asarray(s2.params["w"]), atol=1e-6)
    assert float(m1["corrupted"]) == float(m2["corrupted"])


def test_hyper_shares_compile_across_adversary_rates():
    """rate/scale are traced: a whole adversary-rate grid hits ONE
    compilation per (aggregator, kind) — the acceptance criterion."""
    hyper = jax.jit(make_hyper_round_step(loss_fn, "fedavg", "adam",
                                          corruption="sign_flip"))
    key = jax.random.PRNGKey(0)
    batch = make_batch(4, 2, 4)
    for rate, scale in [(0.0, 1.0), (0.3, 3.0), (1.0, 0.5)]:
        plan = FederatedPlan(
            clients_per_round=4,
            corruption=CorruptionConfig(kind="sign_flip", rate=rate,
                                        scale=scale))
        state = init_server_state(plan, params0())
        hyper(state, batch, plan_hypers(plan), key)
    assert hyper._cache_size() == 1


# --------------------------------------------- data plane: label_shuffle

def _tiny_corpus():
    from repro.data import make_speaker_corpus

    return make_speaker_corpus(num_speakers=8, vocab_size=16, feat_dim=4,
                               mean_utterances=10.0, seed=0)


def test_label_shuffle_helper_permutes_valid_rows_only():
    from repro.data import label_shuffle

    rng = np.random.default_rng(0)
    labels = np.arange(12, dtype=np.int32).reshape(6, 2)
    label_len = np.arange(6, dtype=np.int32)
    valid = np.array([True, True, True, True, False, False])
    before = labels.copy()
    n = label_shuffle(labels, label_len, valid, rng)
    assert n == 4
    # padding rows untouched; valid rows are a permutation, rows intact
    np.testing.assert_array_equal(labels[4:], before[4:])
    assert sorted(map(tuple, labels[:4])) == sorted(map(tuple, before[:4]))
    np.testing.assert_array_equal(labels[:, 0] // 2, label_len)  # rows move together
    # fewer than two valid rows: nothing to permute
    assert label_shuffle(labels, label_len, valid & (label_len == 0), rng) == 0


def test_label_shuffle_rejects_iid_runs():
    """IID rounds bypass the FederatedSampler, so a label_shuffle plan
    would silently never fire — both drivers must refuse instead."""
    from repro.launch.sweeps import SweepPoint, SweepRunner
    from repro.launch.train import run_federated_asr

    plan = FederatedPlan(
        corruption=CorruptionConfig(kind="label_shuffle", rate=0.5))
    with pytest.raises(ValueError, match="label_shuffle"):
        run_federated_asr(None, None, plan, rounds=1, iid=True)
    runner = SweepRunner.__new__(SweepRunner)      # no corpus build needed
    point = SweepPoint(id="bad", plan=plan, rounds=1, iid=True)
    with pytest.raises(ValueError, match="label_shuffle"):
        runner.run_point(point)


def test_sampler_label_shuffle_rate_zero_is_identity():
    from repro.data import FederatedSampler

    corpus = _tiny_corpus()
    clean = FederatedSampler(corpus, 4, 2, seed=3)
    knob = FederatedSampler(corpus, 4, 2, seed=3, label_shuffle_rate=0.0)
    a, b = clean.next_round(), knob.next_round()
    np.testing.assert_array_equal(a.labels, b.labels)
    np.testing.assert_array_equal(a.features, b.features)
    assert knob.corrupted_counts == []


def test_sampler_label_shuffle_poisons_labels_not_features():
    from repro.data import FederatedSampler

    corpus = _tiny_corpus()
    clean = FederatedSampler(corpus, 4, 2, seed=3)
    bad = FederatedSampler(corpus, 4, 2, seed=3, label_shuffle_rate=1.0)
    a, b = clean.next_round(), bad.next_round()
    np.testing.assert_array_equal(a.features, b.features)
    np.testing.assert_array_equal(a.mask, b.mask)
    assert bad.corrupted_counts == [4]
    K = a.labels.shape[0]
    moved = 0
    for k in range(K):
        la = a.labels[k].reshape(-1, a.labels.shape[-1])
        lb = b.labels[k].reshape(-1, b.labels.shape[-1])
        # same multiset of transcripts per client, possibly reordered
        assert sorted(map(tuple, la)) == sorted(map(tuple, lb))
        moved += int((la != lb).any())
    assert moved >= 2          # shuffling visibly moved most clients' labels


@pytest.mark.slow
def test_robustness_grid_smoke_end_to_end(tmp_path):
    """The CI gate's invariants, in-process: per-row corrupted counts,
    exact wire bytes, one compilation per (aggregator, kind), and the
    trimmed-beats-weighted claim under sign_flip."""
    from repro.launch.sweeps import SweepRunner, check_robustness, run_grid

    runner = SweepRunner(seed=0, eval_examples=24, pad_steps=True)
    frontier = run_grid("robustness", smoke=True, runner=runner,
                        out=str(tmp_path / "robust.json"), log=lambda *a: None)
    check_robustness(frontier, log=lambda *a: None)
    ids = {r["id"] for r in frontier["points"]}
    assert "trimmed_mean_sign_flip_r30" in ids
    # label_shuffle rows report host-side realized counts
    ls = next(r for r in frontier["points"]
              if r["id"] == "weighted_mean_label_shuffle_r30")
    assert ls["corrupted_mean"] > 0
    # ONE compilation per (aggregator, adversary-kind): 2 aggregators x
    # {honest, sign_flip} — label_shuffle rides the honest entry, and
    # every rate of a kind shares its entry's single compilation
    assert len(runner._jit_cache) == 4
    assert all(fn._cache_size() == 1 for fn in runner._jit_cache.values())
