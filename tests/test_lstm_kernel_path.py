"""The path both RNN-T cells run on the chip: ``lstm_layer``'s
recurrence under its custom VJP, with the fused Pallas gate kernel and
the kernel's own backward at every step.

On the CPU the dispatcher takes the jnp gates, so here the kernel is
forced through the backend predicate and runs in interpret mode. The
reference is plain autodiff of the jnp ``lax.scan``. The grid is
``test_lstm_recurrence_vjp``'s: vmapped over K clients at H = 128, from
the default, a zero and a non-zero initial state, in float32 and
bfloat16. Every gradient is compared, scaled by its largest entry.
The chunked (rematerialized) scan runs the same kernel at every step.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_lstm_recurrence_vjp import H, _autodiff_layer, _grads, _inputs

from repro.kernels import lstm_gates
from repro.models import lstm


def _force_kernel(monkeypatch):
    """Route the gates to the fused kernel on the CPU; returns the
    keyword arguments of every kernel call."""
    assert not lstm._gate_kernel_path()
    fused, calls = lstm_gates.lstm_gates_fused_vjp, []

    def spy(*a, **kw):
        calls.append(kw)
        return fused(*a, **kw)

    monkeypatch.setattr(lstm_gates, "lstm_gates_fused_vjp", spy)
    monkeypatch.setattr(lstm, "_gate_kernel_path", lambda: True)
    return calls


def _check_kernel_parity(monkeypatch, layer, args, dtype):
    ref = _grads(_autodiff_layer, dtype, *args)
    calls = _force_kernel(monkeypatch)
    new = _grads(layer, dtype, *args)
    assert calls and all(kw == {"th": H, "interpret": True} for kw in calls)
    assert new.keys() == ref.keys()
    atol = 1e-5 if dtype == jnp.float32 else 2.0**-6
    for name in sorted(ref):
        scale = np.abs(ref[name]).max() + 1e-30
        np.testing.assert_allclose(new[name] / scale, ref[name] / scale, rtol=0, atol=atol,
                                   err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("state", ["default", "zero", "set"], ids=lambda s: f"h0c0-{s}")
@pytest.mark.parametrize("S", [1, 2, 17])
def test_gate_kernel_layer_matches_jnp_autodiff(monkeypatch, S, state, dtype):
    _check_kernel_parity(monkeypatch, lstm.lstm_layer, _inputs(S, state), dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gate_kernel_chunked_layer_matches_jnp_autodiff(monkeypatch, dtype):
    layer = functools.partial(lstm.lstm_layer, chunk=4)
    _check_kernel_parity(monkeypatch, layer, _inputs(16, "set"), dtype)


def test_gate_kernel_skips_hidden_off_the_lane_tile(monkeypatch):
    """A hidden size that is no multiple of 128 keeps the jnp gates on
    every backend, the kernel's included."""
    gates = jax.random.normal(jax.random.PRNGKey(0), (4, 4 * 96))
    c = jax.random.normal(jax.random.PRNGKey(1), (4, 96))
    calls = _force_kernel(monkeypatch)
    h, c_new = lstm._lstm_gates_dispatch(gates, c)
    assert calls == []
    h_ref, c_ref = lstm.lstm_gates(gates, c)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(c_new), np.asarray(c_ref))
