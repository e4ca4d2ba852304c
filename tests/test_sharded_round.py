"""Client-sharded round parity: the refactor's correctness bar.

The sharded execution path (``fedavg.ClientSharding`` over a mesh with
a named ``clients`` axis) must reproduce the plain vmap round
BIT-FOR-BIT on a 1-device mesh — fp32, the int8/int4 code-domain fast
path, the async engine, and the hyper path all included. The reduction
story makes this provable rather than hoped-for: the code fast path's
cross-client ops are a pmax (exact), an int32 code psum (exact and
order-independent), and an f32 psum of integer-valued n_k (exact below
2^24); the per-client scan itself is untouched because the sharded body
runs the same vmap on each shard's slice with global client indices.

On a multi-device host mesh (these tests skip unless
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` was exported
before jax initialized — the dedicated CI job does this) the code-path
variants stay bitwise; fp32 is allclose-only because XLA fuses the
per-client matmul differently at per-shard batch sizes.

Also here: the VirtualPopulation sampling contract (deterministic,
distinct, O(visited) host state at million-client scale) and the cost
predictor's sharded feature layout.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CompressionConfig, FederatedPlan
from repro.core.engine import build_round_engine
from repro.core.fedavg import ClientSharding
from repro.launch.mesh import make_federated_mesh

W_TRUE = np.random.default_rng(42).normal(size=(4, 2)).astype(np.float32)


def loss_fn(params, batch, rng):
    pred = batch["x"] @ params["w"]
    w = batch["weight"]
    return jnp.sum((pred - batch["y"]) ** 2 * w[:, None]) / jnp.maximum(w.sum(), 1), {}


def make_batch(K, S, b, seed=0):
    r = np.random.default_rng(seed)
    x = r.normal(size=(K, S, b, 4)).astype(np.float32)
    return {"x": jnp.array(x), "y": jnp.array(x @ W_TRUE),
            "weight": jnp.ones((K, S, b), jnp.float32)}


def params0():
    return {"w": jnp.zeros((4, 2), jnp.float32)}


def _plan(name, K):
    return {
        "fp32": FederatedPlan(clients_per_round=K),
        "int8": FederatedPlan(clients_per_round=K,
                              compression=CompressionConfig(kind="int8")),
        "int4p": FederatedPlan(clients_per_round=K,
                               compression=CompressionConfig(kind="int4", packed=True)),
        "topk": FederatedPlan(clients_per_round=K,
                              compression=CompressionConfig(kind="topk")),
        "int8ef": FederatedPlan(clients_per_round=K,
                                compression=CompressionConfig(
                                    kind="int8", error_feedback=True)),
        "topkef": FederatedPlan(clients_per_round=K,
                                compression=CompressionConfig(
                                    kind="topk", error_feedback=True)),
        "async": FederatedPlan(clients_per_round=K, engine="async"),
    }[name]


def _run_pair(plan, sharding, K):
    base = build_round_engine(plan, loss_fn, base_key=jax.random.PRNGKey(0))
    shard = build_round_engine(plan, loss_fn, base_key=jax.random.PRNGKey(0),
                               client_sharding=sharding)
    assert base.structural_key != shard.structural_key
    batch = make_batch(K, 2, 3)
    s0 = base.init_state(params0())
    sa, ma = jax.jit(base.step)(s0, batch)
    sb, mb = jax.jit(shard.step)(s0, batch)
    return (sa, ma), (sb, mb), (base, shard, s0, batch)


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _assert_tree_close(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb),
                                   rtol=1e-6, atol=1e-7)


# ------------------------------------------------- 1-device bit-for-bit

VARIANTS = ["fp32", "int8", "int4p", "topk", "int8ef", "topkef", "async"]


@pytest.mark.parametrize("name", VARIANTS)
def test_one_device_mesh_is_bitwise(name):
    """The hard bar: a 1-shard mesh reproduces the vmap round exactly —
    state leaves AND every metric, plan-constant AND hyper path."""
    K = 4
    sh = ClientSharding(make_federated_mesh(1))
    (sa, ma), (sb, mb), (base, shard, s0, batch) = _run_pair(_plan(name, K), sh, K)
    _assert_tree_equal(sa, sb)
    for k in ma:
        np.testing.assert_array_equal(np.asarray(ma[k]), np.asarray(mb[k]),
                                      err_msg=k)
    ha, mha = jax.jit(base.hyper_step)(s0, batch, base.hypers(),
                                       jax.random.PRNGKey(0))
    hb, mhb = jax.jit(shard.hyper_step)(s0, batch, shard.hypers(),
                                        jax.random.PRNGKey(0))
    _assert_tree_equal(ha, hb)
    for k in mha:
        np.testing.assert_array_equal(np.asarray(mha[k]), np.asarray(mhb[k]),
                                      err_msg=k)


# --------------------------------------------------- 8-device host mesh

needs_8 = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8 "
           "before jax initializes (the sharded-smoke CI job)")


@needs_8
@pytest.mark.parametrize("name", ["int8", "int4p", "int8ef", "async"])
def test_eight_device_code_paths_bitwise(name):
    """Across real shards the code-domain variants keep the SERVER
    STATE bitwise: pmax, int32 psum and the integer-valued n_k psum are
    all exact, and the per-client scan arithmetic is shard-local. The
    reported mean-loss metric is an f32 sum reduced in a different
    order (8 partials + psum vs one pass over 16), so it gets a 1-ulp
    tolerance; integer-semantics metrics stay exact."""
    K = 16
    sh = ClientSharding(make_federated_mesh(8))
    (sa, ma), (sb, mb), _ = _run_pair(_plan(name, K), sh, K)
    _assert_tree_equal(sa, sb)
    for k in ("participants", "corrupted", "server_steps"):
        np.testing.assert_array_equal(np.asarray(ma[k]), np.asarray(mb[k]),
                                      err_msg=k)
    for k in ma:
        np.testing.assert_allclose(np.asarray(ma[k]), np.asarray(mb[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)


@needs_8
def test_eight_device_fp32_allclose():
    """fp32 deltas are f32-summed, and XLA fuses the per-client matmul
    differently at per-shard batch 2 vs global 16 — allclose, not
    bitwise, is the honest contract off the code path."""
    K = 16
    sh = ClientSharding(make_federated_mesh(8))
    (sa, _), (sb, _), _ = _run_pair(_plan("fp32", K), sh, K)
    _assert_tree_close(sa, sb)


@needs_8
def test_eight_device_convergence_matches():
    """Five sharded rounds track five vmap rounds on the same stream."""
    K = 16
    plan = _plan("int8", K)
    sh = ClientSharding(make_federated_mesh(8))
    base = build_round_engine(plan, loss_fn, base_key=jax.random.PRNGKey(0))
    shard = build_round_engine(plan, loss_fn, base_key=jax.random.PRNGKey(0),
                               client_sharding=sh)
    sa = sb = base.init_state(params0())
    for r in range(5):
        batch = make_batch(K, 2, 3, seed=r)
        sa, ma = jax.jit(base.step)(sa, batch)
        sb, mb = jax.jit(shard.step)(sb, batch)
    _assert_tree_equal(sa, sb)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-6)


# ----------------------------------------------- construction contracts

def test_sharding_validation():
    sh = ClientSharding(make_federated_mesh(1))
    assert sh.num_shards == 1
    assert sh.structural() == ("clients_sharded", "clients", 1)
    with pytest.raises(ValueError, match="needs"):
        make_federated_mesh(max(9, jax.device_count() + 1))
    with pytest.raises(ValueError):
        make_federated_mesh(0)
    # fedsgd has no per-client axis to shard
    plan = FederatedPlan(clients_per_round=4, engine="fedsgd")
    with pytest.raises(ValueError, match="fedsgd"):
        build_round_engine(plan, loss_fn, base_key=jax.random.PRNGKey(0),
                           client_sharding=sh)


@needs_8
def test_sharding_requires_divisible_cohort():
    sh = ClientSharding(make_federated_mesh(8))
    plan = FederatedPlan(clients_per_round=12)   # 12 % 8 != 0
    with pytest.raises(ValueError, match="divide"):
        build_round_engine(plan, loss_fn, base_key=jax.random.PRNGKey(0),
                           client_sharding=sh)


# ------------------------------------------------- virtual populations

def _vp(n_clients=1_000_000, seed=1):
    from repro.data import VirtualPopulation, make_speaker_corpus

    base = make_speaker_corpus(num_speakers=12, vocab_size=32, feat_dim=8,
                               mean_utterances=10.0, seed=seed)
    return VirtualPopulation(base, n_clients)


def test_virtual_population_sampling_deterministic():
    """Fixed seed -> identical cohorts; every draw distinct and in
    range; all three registry strategies run in O(K log P) over a
    million-client population."""
    from repro.data import get_strategy

    vp = _vp()
    for name in ("uniform", "weighted-by-examples", "stratified"):
        strat = get_strategy(name)
        a = strat(np.random.default_rng(7), vp, 32)
        b = strat(np.random.default_rng(7), vp, 32)
        np.testing.assert_array_equal(a, b)
        assert len(set(int(v) for v in a)) == 32
        assert a.min() >= 0 and a.max() < vp.num_clients
        c = strat(np.random.default_rng(8), vp, 32)
        assert not np.array_equal(a, c)


def test_virtual_population_memory_envelope():
    """A round over 1e6 virtual clients must not allocate any N-sized
    array: sampler state stays O(participants-visited)."""
    from repro.data import FederatedSampler

    vp = _vp()
    s = FederatedSampler(vp, clients_per_round=32, local_batch_size=2,
                         data_limit=2, seed=0)
    for _ in range(3):
        rb = s.next_round()
        assert rb.features.shape[0] == 32
    assert len(s._orders) <= 3 * 32
    assert len(s._cursors) <= 3 * 32
    assert max(s._cursors) < vp.num_clients


def test_virtual_population_weighted_follows_counts():
    """weighted-by-examples over the virtual population still tracks
    the base histogram: heavy base speakers surface more often."""
    from repro.data import get_strategy

    vp = _vp(n_clients=120_000)
    counts = vp.base_counts
    rng = np.random.default_rng(0)
    strat = get_strategy("weighted-by-examples")
    hits = np.zeros(len(counts), np.int64)
    for _ in range(200):
        np.add.at(hits, vp.base_of(strat(rng, vp, 16)), 1)
    heavy, light = int(np.argmax(counts)), int(np.argmin(counts))
    assert hits[heavy] > hits[light]


def test_virtual_population_validation():
    from repro.data import VirtualPopulation, make_speaker_corpus

    base = make_speaker_corpus(num_speakers=12, vocab_size=32, feat_dim=8,
                               mean_utterances=10.0, seed=1)
    with pytest.raises(ValueError):
        VirtualPopulation(base, 11)          # fewer clients than speakers
    vp = VirtualPopulation(base, 25)
    assert vp.clone_counts().sum() == 25
    assert vp.num_speakers == 25
    np.testing.assert_array_equal(vp.base_of([0, 12, 24]), [0, 0, 0])
