"""Per-assigned-architecture smoke tests: reduced config (<=2-ish
layers, d_model <= 512, <=4 experts) runs one forward/train step on CPU
— shapes + finiteness — plus one federated round through the engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import base, get_arch, list_archs
from repro.core import FederatedPlan, init_server_state, make_round_step
from repro.models import build_model

ARCHS = list_archs()
RNG = np.random.default_rng(0)


def smoke_batch(arch, cfg, K=2, S=1, b=2, seq=32):
    kind = arch.kind
    w = np.ones((K, S, b), np.float32)
    if kind == "audio":
        return {
            "frames": jnp.asarray(RNG.normal(size=(K, S, b, *base.audio_frames(cfg))), jnp.float32),
            "tokens": jnp.asarray(RNG.integers(0, cfg.vocab, (K, S, b, 16)), jnp.int32),
            "weight": jnp.asarray(w),
        }
    if kind == "vlm":
        return {
            "image_embeds": jnp.asarray(RNG.normal(size=(K, S, b, cfg.n_img_tokens, cfg.vit_dim)), jnp.float32),
            "tokens": jnp.asarray(RNG.integers(0, cfg.lm.vocab, (K, S, b, seq)), jnp.int32),
            "weight": jnp.asarray(w),
        }
    if kind == "rnnt":
        t, u = 12, 6
        return {
            "features": jnp.asarray(RNG.normal(size=(K, S, b, t, cfg.feat_dim)), jnp.float32),
            "labels": jnp.asarray(RNG.integers(1, cfg.vocab, (K, S, b, u)), jnp.int32),
            "frame_len": jnp.full((K, S, b), t, jnp.int32),
            "label_len": jnp.full((K, S, b), u, jnp.int32),
            "weight": jnp.asarray(w),
        }
    vocab = cfg.vocab if hasattr(cfg, "vocab") else cfg.lm.vocab
    return {
        "tokens": jnp.asarray(RNG.integers(0, vocab, (K, S, b, seq)), jnp.int32),
        "weight": jnp.asarray(w),
    }


# the two heaviest archs dominate the suite (~50s combined): their
# round-step smoke runs under the slow mark, CI-only by default
_SLOW_ARCHS = {"zamba2-7b", "deepseek-v2-lite-16b"}


@pytest.mark.parametrize(
    "arch_id",
    [pytest.param(a, marks=pytest.mark.slow) if a in _SLOW_ARCHS
     else a for a in ARCHS])
def test_smoke_forward_and_fed_round(arch_id):
    arch = get_arch(arch_id)
    cfg = arch.make_smoke_config()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))

    batch = smoke_batch(arch, cfg)
    flat = jax.tree.map(lambda a: a[0, 0], batch)
    loss, aux = jax.jit(bundle.loss_fn)(params, flat, jax.random.PRNGKey(1))
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), arch_id

    plan = FederatedPlan(clients_per_round=2, local_batch_size=2,
                         client_lr=0.05, engine=arch.engine)
    step = jax.jit(make_round_step(bundle.loss_fn, plan, jax.random.PRNGKey(2)))
    state = init_server_state(plan, params)
    state2, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"])), arch_id
    assert float(metrics["delta_norm"]) > 0
    # params actually moved
    moved = any(
        float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max()) > 0
        for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(state2.params)))
    assert moved, arch_id


@pytest.mark.parametrize("arch_id", [a for a in ARCHS
                                     if get_arch(a).kind not in ("rnnt",)])
def test_smoke_decode_step(arch_id):
    arch = get_arch(arch_id)
    cfg = arch.make_smoke_config()
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    B, L = 2, 16
    cache = bundle.init_cache(B, L)
    tok = jnp.asarray(RNG.integers(0, 8, (B, 1)), jnp.int32)
    logits, cache2 = jax.jit(bundle.decode_step)(params, cache, tok,
                                                 jnp.asarray(0, jnp.int32))
    vocab = cfg.vocab if hasattr(cfg, "vocab") else cfg.lm.vocab
    assert logits.shape == (B, vocab)
    assert bool(jnp.all(jnp.isfinite(logits))), arch_id


@pytest.mark.parametrize("arch_id", ["qwen3-8b", "gemma3-4b", "rwkv6-1.6b",
                                     "deepseek-v2-lite-16b"])
def test_smoke_decode_matches_prefill(arch_id):
    """Stateful decode == teacher-forced forward on the same tokens."""
    import dataclasses

    arch = get_arch(arch_id)
    cfg = arch.make_smoke_config()
    if getattr(cfg, "moe", None) is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    bundle = build_model(cfg)
    params = bundle.init(jax.random.PRNGKey(0))
    vocab = cfg.vocab if hasattr(cfg, "vocab") else cfg.lm.vocab
    S = 24
    tok = jnp.asarray(RNG.integers(0, vocab, (2, S)), jnp.int32)
    logits_pre, _ = jax.jit(bundle.prefill)(params, {"tokens": tok})
    cache = bundle.init_cache(2, S)
    dstep = jax.jit(bundle.decode_step)
    for t in range(S):
        lg, cache = dstep(params, cache, tok[:, t : t + 1], jnp.asarray(t, jnp.int32))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(logits_pre),
                               atol=2e-4, rtol=2e-4)


def test_full_configs_match_billed_param_counts():
    expected = {
        "phi3.5-moe-42b-a6.6b": (40e9, 44e9),
        "zamba2-7b": (6e9, 7.5e9),
        "deepseek-67b": (64e9, 70e9),
        "command-r-35b": (30e9, 37e9),
        "qwen3-8b": (7.5e9, 9e9),
        "whisper-base": (0.05e9, 0.1e9),
        "llava-next-mistral-7b": (6.8e9, 7.8e9),
        "deepseek-v2-lite-16b": (14e9, 17e9),
        "gemma3-4b": (3.8e9, 5e9),
        "rwkv6-1.6b": (1.4e9, 1.8e9),
        "rnnt-librispeech": (0.09e9, 0.15e9),
    }
    for arch_id, (lo, hi) in expected.items():
        arch = get_arch(arch_id)
        bundle = build_model(arch.make_config())
        struct = jax.eval_shape(lambda b=bundle: b.init(jax.random.PRNGKey(0)))
        n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(struct))
        assert lo <= n <= hi, (arch_id, n)
