"""Pallas kernels vs. jnp oracles: shape/dtype sweeps, interpret=True."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention
from repro.kernels.lstm_gates import lstm_gates_fused
from repro.kernels.rnnt_joint import joint_forward, rnnt_joint

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


# ------------------------------------------------------ fused RNN-T joint


def _joint_case(B, T, U1, J, V, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    e = jnp.asarray(rng.standard_normal((B, T, J)), dtype)
    g = jnp.asarray(rng.standard_normal((B, U1, J)), dtype)
    w = jnp.asarray(rng.standard_normal((J, V)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((V,)) * 0.1, jnp.float32)
    lbl = jnp.asarray(rng.integers(0, V, (B, U1)), jnp.int32)
    return e, g, w, b, lbl


def _live(frames, T, U1):
    """(B, T, U1) True at the frames each utterance holds."""
    return np.arange(T)[None, :, None] < np.asarray(frames)[:, None, None]


def _assert_scaled(got, want, atol, names=("de", "dg", "dw", "db")):
    for name, a, r in zip(names, got, want):
        denom = float(jnp.abs(r).max()) + 1e-30
        np.testing.assert_allclose(np.asarray(a) / denom, np.asarray(r) / denom,
                                   atol=atol, err_msg=name)


# B, T, U1, J, V, tq, tv, frames: T and U1 off their tiles (tq, 16), and
# frame counts that leave whole tiles past an utterance
JOINT_SHAPES = [
    (2, 32, 16, 24, 64, 8, 32, (32, 32)),
    (1, 19, 13, 16, 128, 8, 64, (19,)),
    (2, 24, 12, 8, 48, 8, 16, (24, 9)),
    (3, 61, 21, 32, 256, 16, 128, (61, 17, 1)),
]


@pytest.mark.parametrize("B,T,U1,J,V,tq,tv,frames", JOINT_SHAPES)
def test_rnnt_joint_sweep(B, T, U1, J, V, tq, tv, frames):
    """The forward against the dense oracle at every frame an utterance
    holds; the frames of tiles wholly past it read 0."""
    e, g, w, b, lbl = _joint_case(B, T, U1, J, V, seed=T)
    blank, label = rnnt_joint(e, g, w, b, lbl, jnp.asarray(frames), tq=tq, tv=tv,
                              interpret=True)
    assert blank.shape == label.shape == (B, T, U1)
    blank_ref, label_ref = ref.rnnt_joint_ref(e, g, w, b, lbl)
    live = _live(frames, T, U1)
    np.testing.assert_allclose(np.where(live, blank, 0), np.where(live, blank_ref, 0), atol=3e-5)
    np.testing.assert_allclose(np.where(live, label, 0), np.where(live, label_ref, 0), atol=3e-5)
    for i, f in enumerate(frames):
        past = -(-f // tq) * tq
        assert not np.asarray(blank[i, past:]).any() and not np.asarray(label[i, past:]).any()


def test_rnnt_joint_custom_vjp_matches_ref_grad():
    """The kernel's gradient, every frame held, against autodiff of the
    dense oracle."""
    B, T, U1, J, V = 2, 16, 8, 12, 32
    e, g, w, b, lbl = _joint_case(B, T, U1, J, V, seed=7)
    frames = jnp.full((B,), T)

    def f(fn):
        def loss(e, g, w, b):
            bb, ll = fn(e, g, w, b)
            return (bb * 1.3 + ll).sum()
        return jax.grad(loss, argnums=(0, 1, 2, 3))(e, g, w, b)

    gk = f(lambda *a: rnnt_joint(*a, lbl, frames, interpret=True))
    gr = f(lambda *a: ref.rnnt_joint_ref(*a, lbl))
    for a, bgrad in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bgrad), atol=1e-4)


def test_rnnt_joint_forward_lse_output():
    """The forward's log-sum-exp, the backward's anchor, against the dense
    one; 0 on the tile past the second utterance's 8 frames."""
    B, T, U1, J, V = 2, 16, 16, 24, 64
    e, g, w, b, lbl = _joint_case(B, T, U1, J, V, seed=3)
    ids = jnp.stack([jnp.zeros_like(lbl), lbl], axis=1)
    w_pick = jnp.moveaxis(w[:, ids], 0, -1)
    _, _, lse = joint_forward(e, g, w, b, w_pick, b[ids], jnp.array([16, 8]), tq=8, tv=32,
                              interpret=True)
    h = jnp.tanh(e[:, :, None, :] + g[:, None, :, :])
    lse_ref = jax.nn.logsumexp(h @ w + b, axis=-1)
    np.testing.assert_allclose(np.asarray(lse[0]), np.asarray(lse_ref[0]), atol=3e-5)
    np.testing.assert_allclose(np.asarray(lse[1, :8]), np.asarray(lse_ref[1, :8]), atol=3e-5)
    assert not np.asarray(lse[1, 8:]).any()


def test_joint_ref_chunked_multichunk_matches_dense():
    """Regression: with more than one U chunk the chunked joint used to
    flatten (chunks, T, c) in the wrong axis order, scrambling the U axis
    of both the forward and (through autodiff) the backward."""
    from repro.models.rnnt import joint_chunked

    e, g, w, b, lbl = _joint_case(2, 16, 24, 12, 48, seed=5)
    cb, cl = joint_chunked(e, g, w, b, lbl, u_chunk=8)
    rb, rl = ref.rnnt_joint_ref(e, g, w, b, lbl)
    np.testing.assert_allclose(np.asarray(cb), np.asarray(rb), atol=3e-5)
    np.testing.assert_allclose(np.asarray(cl), np.asarray(rl), atol=3e-5)
    cts = jax.random.normal(jax.random.PRNGKey(0), (2,) + cb.shape)
    _, vjp_c = jax.vjp(lambda *a: joint_chunked(*a, lbl, u_chunk=8), e, g, w, b)
    _, vjp_r = jax.vjp(lambda *a: ref.rnnt_joint_ref(*a, lbl), e, g, w, b)
    _assert_scaled(vjp_c(tuple(cts)), vjp_r(tuple(cts)), atol=3e-5)


@pytest.mark.parametrize("B,T,U1,J,V,tq,tv,frames", JOINT_SHAPES)
def test_rnnt_joint_bwd_fused_matches_chunked_ref(B, T, U1, J, V, tq, tv, frames):
    """The backward kernel against autodiff through the chunked jnp
    joint, on cotangents that are 0 past each utterance's frames (as the
    transducer loss gives them): de, dg, dW and db agree, and de is
    exactly 0 at every frame past the utterance."""
    from repro.models.rnnt import joint_chunked

    e, g, w, b, lbl = _joint_case(B, T, U1, J, V, seed=B * T)
    rng = np.random.default_rng(9)
    live = _live(frames, T, U1)
    cts = tuple(jnp.asarray(rng.standard_normal((B, T, U1)) * live, jnp.float32) for _ in "bl")
    _, vjp_k = jax.vjp(lambda *a: rnnt_joint(*a, lbl, jnp.asarray(frames), tq=tq, tv=tv,
                                             interpret=True), e, g, w, b)
    _, vjp_r = jax.vjp(lambda *a: joint_chunked(*a, lbl), e, g, w, b)
    got = vjp_k(cts)
    _assert_scaled(got, vjp_r(cts), atol=5e-5)
    for i, f in enumerate(frames):
        assert not np.asarray(got[0][i, f:]).any()


@pytest.mark.parametrize("forced", [False, True], ids=["backend", "kernel"])
def test_rnnt_joint_custom_vjp_pallas_dispatch_multichunk(monkeypatch, forced):
    """``loss_fn`` on the smoke RNN-T with ragged frame and label counts
    (U1 over one chunk): its joint chosen by backend (the chunked jnp path
    on the CPU) and forced onto the kernel through ``_joint_kernel_path``
    (interpret mode); the loss and every gradient agree with the jnp path."""
    from repro.configs.rnnt_librispeech import make_smoke_config
    from repro.kernels import ops
    from repro.models import rnnt

    cfg = make_smoke_config()
    params = rnnt.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"features": jnp.asarray(rng.standard_normal((3, 37, cfg.feat_dim)), jnp.float32),
             "labels": jnp.asarray(rng.integers(1, cfg.vocab, (3, 11)), jnp.int32),
             "frame_len": jnp.array([37, 12, 5]), "label_len": jnp.array([11, 4, 0])}

    def value_and_grad():  # traced anew at each call
        return jax.jit(jax.value_and_grad(lambda p: rnnt.loss_fn(cfg, p, batch)[0]))(params)

    want = value_and_grad()
    assert not rnnt._joint_kernel_path()
    if forced:
        monkeypatch.setattr(rnnt, "_joint_kernel_path", lambda: True)
    kernel, calls = ops.rnnt_joint, []

    def spy(*args):
        calls.append(args[-1].shape)
        return kernel(*args)

    monkeypatch.setattr(ops, "rnnt_joint", spy)
    got = value_and_grad()
    assert calls == ([(3,)] if forced else [])  # one frame count per utterance
    np.testing.assert_allclose(float(got[0]), float(want[0]), rtol=1e-6)
    for a, r in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        denom = float(jnp.abs(r).max()) + 1e-30
        np.testing.assert_allclose(np.asarray(a) / denom, np.asarray(r) / denom, atol=1e-5)


def test_rnnt_joint_vmapped_over_clients():
    """vmap over K = 3 clients, each with its own weights and frame
    counts, as the round runs the local step: the forward and the
    gradient equal each client's own call of the dense oracle."""
    K, B, T, U1, J, V = 3, 2, 20, 13, 16, 128
    cases = [_joint_case(B, T, U1, J, V, seed=k) for k in range(K)]
    e, g, w, b, lbl = (jnp.stack(x) for x in zip(*cases))
    frames = jnp.array([[20, 3], [9, 20], [1, 16]])
    live = np.stack([_live(f, T, U1) for f in np.asarray(frames)])

    def loss(fn, live):
        def f(e, g, w, b, lbl, frames):
            bb, ll = fn(e, g, w, b, lbl, frames)
            return jnp.sum((bb * 1.3 + ll) * live), (bb, ll)
        return f

    kern = lambda e, g, w, b, lbl, fr: rnnt_joint(e, g, w, b, lbl, fr, tv=64, interpret=True)
    dense = lambda e, g, w, b, lbl, fr: ref.rnnt_joint_ref(e, g, w, b, lbl)
    grads_k, (bk, lk) = jax.vmap(
        lambda *a: jax.grad(loss(kern, a[-1]), argnums=(0, 1, 2, 3), has_aux=True)(*a[:-1])
    )(e, g, w, b, lbl, frames, live)
    for k in range(K):
        grads_r, (br, lr) = jax.grad(loss(dense, live[k]), argnums=(0, 1, 2, 3), has_aux=True)(
            e[k], g[k], w[k], b[k], lbl[k], frames[k])
        np.testing.assert_allclose(np.where(live[k], bk[k], 0), np.where(live[k], br, 0), atol=3e-5)
        np.testing.assert_allclose(np.where(live[k], lk[k], 0), np.where(live[k], lr, 0), atol=3e-5)
        _assert_scaled([x[k] for x in grads_k], grads_r, atol=5e-5)


def test_rnnt_joint_bf16_operands():
    """In the configuration's compute type: bfloat16 e and g (the MXU's
    operands, with Wo cast to bfloat16 inside), float32 log-probs and
    parameter gradients, within bfloat16 rounding of the float32 oracle
    on the same rounded inputs."""
    B, T, U1, J, V = 2, 16, 13, 32, 256
    e, g, w, b, lbl = _joint_case(B, T, U1, J, V, seed=2, dtype=jnp.bfloat16)
    frames = jnp.array([16, 11])
    live = _live(frames, T, U1)

    def loss(fn):
        def f(e, g, w, b):
            bb, ll = fn(e, g, w, b)
            return jnp.sum((bb * 1.3 + ll) * live)
        return f

    kern = lambda *a: rnnt_joint(*a, lbl, frames, tv=128, interpret=True)
    blank, label = kern(e, g, w, b)
    assert blank.dtype == label.dtype == jnp.float32
    w16 = w.astype(jnp.bfloat16).astype(jnp.float32)
    rb, rl = ref.rnnt_joint_ref(e.astype(jnp.float32), g.astype(jnp.float32), w16, b, lbl)
    np.testing.assert_allclose(np.where(live, blank, 0), np.where(live, rb, 0), atol=3e-2)
    np.testing.assert_allclose(np.where(live, label, 0), np.where(live, rl, 0), atol=3e-2)
    gk = jax.grad(loss(kern), argnums=(0, 1, 2, 3))(e, g, w, b)
    assert [x.dtype for x in gk] == [jnp.bfloat16, jnp.bfloat16, jnp.float32, jnp.float32]
    gr = jax.grad(loss(lambda *a: ref.rnnt_joint_ref(*a, lbl)), argnums=(0, 1, 2, 3))(
        e.astype(jnp.float32), g.astype(jnp.float32), w16, b)
    _assert_scaled([x.astype(jnp.float32) for x in gk], gr, atol=3e-2)
