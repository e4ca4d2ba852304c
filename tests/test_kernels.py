"""Pallas kernels vs. jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lstm_gates import lstm_gates_fused
from repro.kernels.rnnt_joint import rnnt_joint_fused

def _rng():
    return np.random.default_rng(1234)


def _rand(shape, dtype, rng=None):
    x = (rng or np.random.default_rng(abs(hash(shape)) % 2**31)).normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,H,Kv,D,causal,window",
    [
        (2, 128, 128, 4, 2, 32, True, 0),
        (1, 256, 256, 8, 8, 16, True, 64),
        (2, 128, 128, 4, 1, 32, False, 0),
        (1, 512, 512, 2, 2, 64, True, 0),
        (1, 128, 256, 4, 4, 32, False, 0),   # cross-attention shape
        (2, 150, 150, 2, 2, 64, False, 0),   # encoder head size, 150 = 2 x 64 + 22
        (1, 300, 300, 4, 4, 64, False, 0),   # 300 = 4 x 64 + 44
        (1, 200, 200, 4, 2, 32, True, 48),   # partial last tile under causal + window
    ],
)
def test_flash_attention_sweep(B, Sq, Sk, H, Kv, D, causal, window, dtype):
    q = _rand((B, Sq, H, D), dtype)
    k = _rand((B, Sk, Kv, D), dtype)
    v = _rand((B, Sk, Kv, D), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          tq=64, tk=64, interpret=True)
    expected = ref.attention_ref(q, k, v, causal=causal,
                                 window=window if window else None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


# forward and gradient tolerances of the flash VJP against f32 oracles;
# bf16 operands round P and dS to bf16 (about 2^-9 relative) before
# their matmuls, so its gradients of order 1 land within 3e-2
VJP_TOL = {jnp.float32: (2e-5, 1e-4), jnp.bfloat16: (2e-2, 3e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,D",
    [
        (2, 150, 2, 64),    # one tile of 150 keys, not a multiple of 8 or 128
        (1, 600, 2, 64),    # a 512 tile and a partial one of 88
        (1, 128, 4, 32),
    ],
)
def test_flash_attention_vjp_matches_ref_and_blockwise(B, S, H, D, dtype):
    """The encoder kernel's forward and its own backward (dq, dk, dv)
    against the f32 reference and ``blockwise_attention``, which it
    replaces off the CPU. Interpret mode fills the partial tile's rows
    past the end with NaN, so any leak of them into a result fails."""
    from repro.kernels.flash_attention import flash_attention_vjp
    from repro.models.attention import blockwise_attention

    q, k, v = (_rand((B, S, H, D), dtype, np.random.default_rng(i)) for i in range(3))
    w = _rand((B, S, H, D), jnp.float32, np.random.default_rng(3))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    oracles = {
        "ref": lambda q_, k_, v_: ref.attention_ref(q_, k_, v_, causal=False),
        "blockwise": lambda q_, k_, v_: blockwise_attention(q_, k_, v_, causal=False,
                                                            block_kv=min(512, S)),
    }
    fwd_tol, grad_tol = VJP_TOL[dtype]

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w)

    def kernel(q_, k_, v_):
        return flash_attention_vjp(q_, k_, v_, interpret=True)

    out = kernel(q, k, v)
    grads = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    assert out.dtype == dtype and all(g.dtype == dtype for g in grads)
    for name, fn in oracles.items():
        np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(fn(*f32)),
                                   atol=fwd_tol, rtol=fwd_tol, err_msg=name)
        want = jax.grad(loss(fn), argnums=(0, 1, 2))(*f32)
        for label, got, exp in zip("qkv", grads, want):
            np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(exp),
                                       atol=grad_tol, rtol=grad_tol, err_msg=f"{name} d{label}")


@pytest.mark.parametrize("transform", ["vmap", "checkpoint"])
def test_flash_attention_vjp_under_vmap_and_checkpoint(transform):
    """As ``encode`` runs it: vmapped over the round's clients, and inside
    each encoder layer's ``jax.checkpoint``; both the value and the
    gradients match the f32 reference, client by client."""
    from repro.kernels.flash_attention import flash_attention_vjp

    K, B, S, H, D = 2, 1, 150, 2, 64
    q, k, v = (_rand((K, B, S, H, D), jnp.float32, np.random.default_rng(i)) for i in range(3))
    w = _rand((K, B, S, H, D), jnp.float32, np.random.default_rng(3))

    def kernel(q_, k_, v_):
        return flash_attention_vjp(q_, k_, v_, interpret=True)

    def reference(q_, k_, v_):
        return ref.attention_ref(q_, k_, v_, causal=False)

    def wrap(fn):
        if transform == "vmap":
            return jax.vmap(fn)
        layer = jax.checkpoint(lambda q_, k_, v_: fn(q_, k_, v_) * 2.0)
        return jax.vmap(layer)

    def loss(fn):
        return lambda *a: jnp.sum(wrap(fn)(*a) * w)

    np.testing.assert_allclose(np.asarray(wrap(kernel)(q, k, v)),
                               np.asarray(wrap(reference)(q, k, v)), atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,Kv,D,window,pos",
    [
        (2, 512, 8, 2, 32, 0, 173),
        (1, 1024, 4, 4, 16, 128, 900),
        (3, 256, 2, 1, 64, 0, 0),
        (1, 2048, 8, 8, 32, 0, 2047),
    ],
)
def test_flash_decode_sweep(B, S, H, Kv, D, window, pos, dtype):
    q = _rand((B, H, D), dtype)
    kc = _rand((B, S, Kv, D), dtype)
    vc = _rand((B, S, Kv, D), dtype)
    out = flash_decode(q, kc, vc, jnp.asarray(pos, jnp.int32),
                       window=window, ts=128, interpret=True)
    expected = ref.decode_attention_ref(q, kc, vc, pos,
                                        window=window if window else None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize(
    "B,T,U1,J,V,tq,tu,tv",
    [
        (2, 32, 16, 24, 64, 16, 8, 32),
        (1, 16, 8, 16, 128, 8, 4, 64),
        (2, 24, 12, 8, 48, 8, 4, 16),
        (1, 64, 8, 32, 256, 16, 8, 128),
    ],
)
def test_rnnt_joint_sweep(B, T, U1, J, V, tq, tu, tv):
    e = _rand((B, T, J), jnp.float32)
    g = _rand((B, U1, J), jnp.float32)
    w = _rand((J, V), jnp.float32) * 0.3
    b = _rand((V,), jnp.float32) * 0.1
    lbl = jnp.asarray(np.random.default_rng(7).integers(0, V, (B, U1)), jnp.int32)
    blank, label = rnnt_joint_fused(e, g, w, b, lbl, tq=tq, tu=tu, tv=tv,
                                    interpret=True)
    blank_ref, label_ref = ref.rnnt_joint_ref(e, g, w, b, lbl)
    np.testing.assert_allclose(np.asarray(blank), np.asarray(blank_ref), atol=3e-5)
    np.testing.assert_allclose(np.asarray(label), np.asarray(label_ref), atol=3e-5)


def test_rnnt_joint_custom_vjp_matches_ref_grad():
    from repro.kernels.ops import rnnt_joint

    B, T, U1, J, V = 2, 16, 8, 12, 32
    e = _rand((B, T, J), jnp.float32)
    g = _rand((B, U1, J), jnp.float32)
    w = _rand((J, V), jnp.float32) * 0.3
    b = _rand((V,), jnp.float32) * 0.1
    lbl = jnp.asarray(np.random.default_rng(7).integers(0, V, (B, U1)), jnp.int32)

    def f_kernel(e, g, w, b):
        bb, ll = rnnt_joint(e, g, w, b, lbl)
        return (bb * 1.3 + ll).sum()

    def f_ref(e, g, w, b):
        bb, ll = ref.rnnt_joint_ref(e, g, w, b, lbl)
        return (bb * 1.3 + ll).sum()

    gk = jax.grad(f_kernel, argnums=(0, 1, 2, 3))(e, g, w, b)
    gr = jax.grad(f_ref, argnums=(0, 1, 2, 3))(e, g, w, b)
    for a, bgrad in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bgrad), atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,th", [(4, 512, 256), (1, 128, 128), (8, 1024, 512),
                                    (4, 1152, 128)])
def test_lstm_gates_sweep(B, H, th, dtype):
    gates = _rand((B, 4 * H), dtype)
    c = _rand((B, H), jnp.float32)
    h1, c1 = lstm_gates_fused(gates, c, th=th, interpret=True)
    h2, c2 = ref.lstm_gates_ref(gates, c)
    np.testing.assert_allclose(np.asarray(h1, np.float32), np.asarray(h2, np.float32),
                               atol=TOL[dtype])
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=TOL[dtype])


@pytest.mark.parametrize("B,H,th", [(4, 512, 256), (1, 128, 128), (3, 256, 256),
                                    (8, 1152, 128)])
def test_lstm_gates_fused_backward_matches_autodiff(B, H, th):
    """The fused custom-VJP backward == jax.grad through the jnp
    reference cell, for both output cotangents (h feeds the next
    matmul, c_new the next step's state)."""
    from repro.kernels.lstm_gates import lstm_gates_fused_vjp

    gates = _rand((B, 4 * H), jnp.float32)
    c = _rand((B, H), jnp.float32)
    dh = _rand((B, H), jnp.float32)
    dcn = _rand((B, H), jnp.float32)

    def scalar(fn):
        def f(g_, c_):
            h, cn = fn(g_, c_)
            return (h * dh).sum() + (cn * dcn).sum()
        return f

    gk = jax.grad(scalar(lambda g_, c_: lstm_gates_fused_vjp(
        g_, c_, th=th, interpret=True)), argnums=(0, 1))(gates, c)
    gr = jax.grad(scalar(ref.lstm_gates_ref), argnums=(0, 1))(gates, c)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_lstm_gates_fused_vjp_forward_matches_ref():
    gates = _rand((2, 4 * 256), jnp.float32)
    c = _rand((2, 256), jnp.float32)
    from repro.kernels.lstm_gates import lstm_gates_fused_vjp

    h1, c1 = lstm_gates_fused_vjp(gates, c, th=256, interpret=True)
    h2, c2 = ref.lstm_gates_ref(gates, c)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-6)
    np.testing.assert_allclose(np.asarray(c1), np.asarray(c2), atol=1e-6)


def test_model_lstm_dispatch_matches_ref_path():
    """models.lstm routes through the fused-VJP kernel on TPU and the
    jnp reference on CPU; the tile picker must only offer shapes the
    kernel accepts."""
    from repro.models.lstm import _fused_tile, _lstm_gates_dispatch, lstm_gates

    assert _fused_tile(256) == 256
    assert _fused_tile(128) == 128
    assert _fused_tile(384) == 128
    assert _fused_tile(100) is None
    gates = _rand((2, 4 * 96), jnp.float32)
    c = _rand((2, 96), jnp.float32)
    h1, c1 = _lstm_gates_dispatch(gates, c)       # CPU: the jnp path
    h2, c2 = lstm_gates(gates, c)
    np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


def test_encode_attention_dispatch(monkeypatch):
    """models.encdec runs the encoder's self-attention through the flash
    kernel exactly off the CPU (the backend probe is forced here; the
    kernel then runs in interpret mode) and on the CPU through
    ``blockwise_attention`` with the arguments it always had."""
    from repro.configs import base, get_arch
    from repro.kernels import ops
    from repro.models import encdec

    cfg = get_arch("whisper-base").make_smoke_config()
    params = encdec.init_params(cfg, jax.random.PRNGKey(0))
    frames = _rand((2, *base.audio_frames(cfg)), jnp.float32)
    calls = []
    kernel, blockwise = ops.attention, encdec.blockwise_attention

    def spy_kernel(*a):
        calls.append(("kernel", {}))
        return kernel(*a)

    def spy_blockwise(*a, **kw):
        calls.append(("blockwise", kw))
        return blockwise(*a, **kw)

    monkeypatch.setattr(ops, "attention", spy_kernel)
    monkeypatch.setattr(encdec, "blockwise_attention", spy_blockwise)
    assert not encdec._flash_path()
    on_cpu = encdec.encode(cfg, params, frames)
    assert calls == [("blockwise", {"causal": False, "block_kv": cfg.max_source})]
    calls.clear()
    monkeypatch.setattr(encdec, "_flash_path", lambda: True)
    off_cpu = encdec.encode(cfg, params, frames)
    assert calls == [("kernel", {})]
    np.testing.assert_allclose(np.asarray(off_cpu), np.asarray(on_cpu), atol=1e-4, rtol=1e-4)


def test_blockwise_attention_matches_kernel_oracle():
    """Chain of custody: models' jnp blockwise == kernels' oracle."""
    from repro.models.attention import blockwise_attention

    q = _rand((2, 64, 8, 16), jnp.float32)
    k = _rand((2, 64, 2, 16), jnp.float32)
    v = _rand((2, 64, 2, 16), jnp.float32)
    o1 = blockwise_attention(q, k, v, causal=True, window=24, block_kv=16)
    o2 = ref.attention_ref(q, k, v, causal=True, window=24)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


# ----------------------------------------- fused RNN-T joint bwd (PR 10)


def _joint_case(B, T, U1, J, V, seed=0):
    rng = np.random.default_rng(seed)
    e = jnp.asarray(rng.standard_normal((B, T, J)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((B, U1, J)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((J, V)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((V,)) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.integers(0, V, (B, U1)), jnp.int32)
    return e, g, w, b, lbl


def test_rnnt_joint_forward_lse_output():
    e, g, w, b, lbl = _joint_case(2, 32, 16, 24, 64, seed=3)
    _, _, lse = rnnt_joint_fused(e, g, w, b, lbl, tq=16, tu=8, tv=32,
                                 interpret=True, return_lse=True)
    h = jnp.tanh(e[:, :, None, :] + g[:, None, :, :])
    lse_ref = jax.nn.logsumexp(h @ w + b, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               atol=3e-5)


def test_joint_ref_chunked_multichunk_matches_dense():
    """Regression: with more than one U-chunk the chunked reference used
    to flatten (chunks, T, c) in the wrong axis order, scrambling the U
    axis of both the forward and (through jax.vjp) the backward."""
    from repro.kernels.ops import _joint_ref_chunked

    e, g, w, b, lbl = _joint_case(2, 16, 24, 12, 48, seed=5)
    cb, cl = _joint_ref_chunked(e, g, w, b, lbl, u_chunk=8)
    rb, rl = ref.rnnt_joint_ref(e, g, w, b, lbl)
    np.testing.assert_allclose(np.asarray(cb), np.asarray(rb), atol=3e-5)
    np.testing.assert_allclose(np.asarray(cl), np.asarray(rl), atol=3e-5)


@pytest.mark.parametrize(
    "B,T,U1,J,V,tq,tu,tv",
    [
        (2, 32, 16, 24, 64, 16, 8, 32),   # multi u-tile, multi v-slab
        (1, 16, 8, 16, 128, 8, 4, 64),
        (2, 24, 12, 8, 48, 8, 4, 16),
        (1, 64, 8, 32, 256, 16, 8, 128),
    ],
)
def test_rnnt_joint_bwd_fused_matches_chunked_ref(B, T, U1, J, V, tq, tu, tv):
    """The two backward kernels (dh/de/dg with vocab innermost, dW/db
    with vocab outermost) against autodiff through the chunked jnp
    joint, on the forward's own saved lse."""
    from repro.kernels.ops import _joint_ref_chunked
    from repro.kernels.rnnt_joint import rnnt_joint_bwd_fused

    e, g, w, b, lbl = _joint_case(B, T, U1, J, V, seed=B * T)
    rng = np.random.default_rng(9)
    dbl = jnp.asarray(rng.standard_normal((B, T, U1)), jnp.float32)
    dlb = jnp.asarray(rng.standard_normal((B, T, U1)), jnp.float32)
    _, _, lse = rnnt_joint_fused(e, g, w, b, lbl, tq=tq, tu=tu, tv=tv,
                                 interpret=True, return_lse=True)
    de, dg, dw, db = rnnt_joint_bwd_fused(e, g, w, b, lbl, lse, dbl, dlb,
                                          tq=tq, tu=tu, tv=tv, interpret=True)
    _, vjp = jax.vjp(lambda e_, g_, w_, b_: _joint_ref_chunked(e_, g_, w_, b_, lbl),
                     e, g, w, b)
    for name, a, r in zip(("de", "dg", "dw", "db"), (de, dg, dw, db),
                          vjp((dbl, dlb))):
        denom = float(jnp.abs(r).max()) + 1e-30
        np.testing.assert_allclose(np.asarray(a) / denom,
                                   np.asarray(r) / denom,
                                   atol=5e-5, err_msg=name)


@pytest.mark.parametrize("forced", [False, True], ids=["backend", "kernel"])
def test_rnnt_joint_custom_vjp_pallas_dispatch_multichunk(monkeypatch, forced):
    """End-to-end: ops.rnnt_joint's gradients match plain-jnp reference
    grads with its backward chosen by backend (the chunked jnp
    backward on the CPU) and with the Pallas backward forced through
    the joint's backend predicate — on a multi-chunk U1 so the dispatch
    covers the shape class the chunked path buckets."""
    from repro.kernels import ops

    assert not ops._use_joint_bwd_pallas()
    if forced:
        monkeypatch.setattr(ops, "_use_joint_bwd_pallas", lambda: True)
    pallas, calls = ops.rnnt_joint_bwd_fused, []

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return pallas(*args, **kwargs)

    monkeypatch.setattr(ops, "rnnt_joint_bwd_fused", spy)
    e, g, w, b, lbl = _joint_case(2, 32, 24, 16, 64, seed=11)

    def f(fn):
        def loss(e, g, w, b):
            bb, ll = fn(e, g, w, b, lbl)
            return (bb * 1.3 + ll).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(e, g, w, b)

    gk = f(ops.rnnt_joint)
    assert calls == ([{"interpret": True}] if forced else [])
    gr = f(ref.rnnt_joint_ref)
    for name, a, r in zip(("de", "dg", "dw", "db"), gk, gr):
        denom = float(jnp.abs(r).max()) + 1e-30
        np.testing.assert_allclose(np.asarray(a) / denom,
                                   np.asarray(r) / denom,
                                   atol=1e-4, err_msg=name)
