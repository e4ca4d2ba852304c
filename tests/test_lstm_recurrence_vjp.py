"""The LSTM recurrence's custom VJP (``models/lstm.py``): its gradients
equal plain autodiff of the same ``lax.scan``, and its backward carries
no recurrent-weight gradient through the time loop.

The parity cases run vmapped over K clients, as the federated round
does, from the default initial state (``h0``/``c0`` not given, as the
model calls it), from a zero one and from a non-zero one. Every
gradient but ``dW_hh`` is computed by the same per-step backward and
agrees to float rounding; ``dW_hh`` is summed in another order (one
float32-accumulated matmul after the loop instead of one per-step add)
and agrees to bfloat16 rounding.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import lstm

K, B, D, H = 3, 4, 24, 128


def _autodiff_layer(p, xs, h0=None, c0=None, unroll=1):
    """The same layer under plain autodiff of the ``lax.scan``: the
    transposed loop carries the gradient of ``w_hh``."""
    B, _, _ = xs.shape
    H = p["w_hh"].shape[0]
    h0 = jnp.zeros((B, H), xs.dtype) if h0 is None else h0
    c0 = jnp.zeros((B, H), jnp.float32) if c0 is None else c0
    xg = xs @ p["w_ih"].astype(xs.dtype) + p["b"].astype(xs.dtype)
    step = functools.partial(lstm._lstm_step, p["w_hh"])
    (h, c), ys = jax.lax.scan(step, (h0, c0), xg.swapaxes(0, 1), unroll=unroll)
    return ys.swapaxes(0, 1), (h, c)


def _grads(layer, dtype, p, xs, h0=None, c0=None, unroll=1):
    """Per-client gradients of a loss on every output, vmapped over K;
    ``h0``/``c0`` None leaves the layer's initial state at its default."""

    def loss(p, xs, *state):
        state = [state[0].astype(dtype), state[1]] if state else [None, None]
        ys, (h, c) = layer(p, xs.astype(dtype), *state, unroll=unroll)
        f32 = jnp.float32
        return (ys.astype(f32) ** 2).sum() + h.astype(f32).sum() + (c ** 2).sum()

    args = (p, xs) if h0 is None else (p, xs, h0, c0)
    g = jax.jit(jax.vmap(jax.grad(loss, argnums=tuple(range(len(args))))))(*args)
    out = {f"d{k}": g[0][k] for k in ("w_ih", "w_hh", "b")}
    out.update(zip(("dxs", "dh0", "dc0"), g[1:]))
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


def _inputs(S, state, seed=0):
    """Per-client parameters, inputs and initial state: ``state`` is
    "default" (none given), "zero" or "set"."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    p = jax.vmap(lambda k: lstm.lstm_cell_init(k, D, H))(jax.random.split(ks[0], K))
    # inputs exactly representable in bfloat16, so every run sees the same data
    xs = jax.random.normal(ks[1], (K, B, S, D)).astype(jnp.bfloat16).astype(jnp.float32)
    if state == "default":
        return p, xs, None, None
    scale = 0.5 if state == "set" else 0.0
    h0 = (scale * jax.random.normal(ks[2], (K, B, H))).astype(jnp.bfloat16).astype(jnp.float32)
    c0 = scale * jax.random.normal(ks[3], (K, B, H))
    return p, xs, h0, c0


def _check_parity(S, state, dtype, unroll=1):
    args = _inputs(S, state)
    new = _grads(lstm.lstm_layer, dtype, *args, unroll=unroll)
    ref = _grads(_autodiff_layer, dtype, *args, unroll=unroll)
    assert new.keys() == ref.keys()
    eps = float(jnp.finfo(dtype).eps)
    for name in sorted(new.keys() - {"dw_hh"}):
        scale = np.abs(ref[name]).max() + 1e-30
        np.testing.assert_allclose(new[name] / scale, ref[name] / scale, rtol=0, atol=eps, err_msg=name)
    scale = np.abs(ref["dw_hh"]).max() + 1e-30
    whh_tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -7  # bfloat16's eps
    np.testing.assert_allclose(new["dw_hh"] / scale, ref["dw_hh"] / scale, rtol=0, atol=whh_tol)
    return args, new, ref


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("state", ["default", "zero", "set"], ids=lambda s: f"h0c0-{s}")
@pytest.mark.parametrize("S", [1, 2, 17])
def test_vjp_matches_autodiff(S, state, dtype):
    args, new, ref = _check_parity(S, state, dtype)
    if dtype == jnp.bfloat16:
        # one f32-accumulated matmul rounds no per-step product to bf16:
        # dW_hh is no farther from an all-float32 run than autodiff's
        exact = _grads(_autodiff_layer, jnp.float32, *args)["dw_hh"]
        assert np.linalg.norm(new["dw_hh"] - exact) <= np.linalg.norm(ref["dw_hh"] - exact)


def test_vjp_matches_autodiff_unrolled():
    _check_parity(17, "set", jnp.bfloat16, unroll=4)


def _scan_carries(jaxpr):
    """Shapes of the loop carries of every ``scan`` in ``jaxpr`` and the
    jaxprs nested in it."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            n, m = eqn.params["num_consts"], eqn.params["num_carry"]
            out += [tuple(v.aval.shape) for v in eqn.invars[n : n + m]]
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _scan_carries(sub)
    return out


@pytest.mark.parametrize("layer", ["lstm_layer", "autodiff"])
def test_backward_loop_carries_no_w_hh(layer):
    """No scan of the vmapped gradient carries an array shaped like a
    client's ``w_hh``; plain autodiff of the same scan does (the check
    tells the two apart). ``w_hh`` as a read-only const is allowed."""
    fn = lstm.lstm_layer if layer == "lstm_layer" else _autodiff_layer
    p, xs, h0, c0 = _inputs(5, "set")

    def loss(p, xs, h0, c0):
        ys, (h, c) = fn(p, xs, h0, c0)
        return (ys ** 2).sum() + h.sum() + c.sum()

    jaxpr = jax.make_jaxpr(jax.vmap(jax.grad(loss)))(p, xs, h0, c0).jaxpr
    carries = _scan_carries(jaxpr)
    assert len(carries) >= 4  # forward (h, c) and backward (dh, dc) at least
    assert ((K, H, 4 * H) in carries) == (layer == "autodiff"), carries
