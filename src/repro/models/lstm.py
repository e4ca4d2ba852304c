"""LSTM stack (the paper's RNN-T encoder substrate).

The input matmul is hoisted out of the time loop (one (B*S) x d_in x 4h
matmul); each step adds h @ w_hh and applies the gate nonlinearities +
state update, the Pallas ``lstm_gates`` kernel's target (ref path
inline here). Sequence iteration is ``lax.scan`` under a custom VJP
whose backward carries only (dh, dc) through the reversed time loop and
forms the recurrent weight's gradient after it, as one matmul over all
S*B rows: ``dW_hh = sum_t h_{t-1}^T dgates_t``. Multi-layer stacks loop
per layer.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


@dataclasses.dataclass(frozen=True)
class LSTMConfig:
    d_in: int
    d_hidden: int
    n_layers: int


def lstm_cell_init(key, d_in: int, d_hidden: int, dtype=jnp.float32):
    k1, k2 = jax.random.split(key)
    return {
        "w_ih": dense_init(k1, d_in, 4 * d_hidden, dtype),
        "w_hh": dense_init(k2, d_hidden, 4 * d_hidden, dtype),
        "b": jnp.zeros((4 * d_hidden,), dtype),
    }


def lstm_gates(gates: jnp.ndarray, c: jnp.ndarray):
    """Fused gate nonlinearities + cell update (jnp reference of the
    Pallas kernel). gates: (..., 4h) pre-activation [i, f, g, o]."""
    h4 = gates.shape[-1]
    h = h4 // 4
    gf = gates.astype(jnp.float32)
    i = jax.nn.sigmoid(gf[..., :h])
    f = jax.nn.sigmoid(gf[..., h : 2 * h] + 1.0)  # forget-gate bias +1
    g = jnp.tanh(gf[..., 2 * h : 3 * h])
    o = jax.nn.sigmoid(gf[..., 3 * h :])
    c_new = f * c.astype(jnp.float32) + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new.astype(gates.dtype), c_new.astype(c.dtype)


def _fused_tile(h: int):
    """Largest lane-friendly tile dividing h (None: shape won't tile)."""
    for th in (256, 128):
        if h % th == 0:
            return th
    return None


def _gate_kernel_path() -> bool:
    """The cell's gate math runs the fused Pallas kernel off the CPU;
    on the CPU, the jnp ``lstm_gates``."""
    return jax.default_backend() != "cpu"


def _lstm_gates_dispatch(gates: jnp.ndarray, c: jnp.ndarray):
    """Off-CPU: the Pallas fused kernel with its fused custom-VJP
    backward (the cell's backward dominates the federated round's
    per-client scan). CPU: the jnp reference — same math, XLA-fused,
    and what every parity test pins. The one shape gate is H % 128: a
    hidden size that does not tile into 128-lane blocks keeps the
    reference on every backend. Forced on the CPU, the kernel runs in
    interpret mode."""
    if not _gate_kernel_path() or c.shape[-1] % 128:
        return lstm_gates(gates, c)
    th = _fused_tile(c.shape[-1])
    from repro.kernels.lstm_gates import lstm_gates_fused_vjp

    return lstm_gates_fused_vjp(gates, c, th=th, interpret=jax.default_backend() == "cpu")


def lstm_cell_step(p, x, h, c):
    """x: (B, d_in); h, c: (B, d_hidden)."""
    gates = x @ p["w_ih"].astype(x.dtype) + h @ p["w_hh"].astype(x.dtype) + p["b"].astype(x.dtype)
    return lstm_gates(gates, c)


def _lstm_step(w_hh, carry, xg_t):
    h, c = carry
    gates = xg_t + h @ w_hh.astype(xg_t.dtype)
    h, c = _lstm_gates_dispatch(gates, c)
    return (h, c), h


def _recurrence_scan(w_hh, xg, h0, c0, unroll):
    """xg: (S, B, 4h) time-major input pre-activations -> ys (S, B, h),
    final (h, c). ``h0`` None is the zero initial state."""
    if h0 is None:
        h0 = jnp.zeros(xg.shape[1:-1] + w_hh.shape[:1], xg.dtype)
    (h, c), ys = jax.lax.scan(functools.partial(_lstm_step, w_hh), (h0, c0), xg, unroll=unroll)
    return ys, h, c


_lstm_recurrence = jax.custom_vjp(_recurrence_scan, nondiff_argnums=(4,))


def _lstm_recurrence_fwd(w_hh, xg, h0, c0, unroll):
    # w_hh is closed over, not differentiated: the transposed scan then
    # carries only (dh, dc) and reads w_hh as a constant.
    out, vjp = jax.vjp(lambda xg, h0, c0: _recurrence_scan(w_hh, xg, h0, c0, unroll), xg, h0, c0)
    return out, (w_hh, h0, out[0], vjp)


def _lstm_recurrence_bwd(unroll, res, cts):
    w_hh, h0, ys, vjp = res
    dxg, dh0, dc0 = vjp(cts)
    # dgates_t is dxg_t; h_{t-1} is ys[t-1], h0 at t = 0. Separate dots,
    # so no shifted copy of ys is made; every product summed in float32.
    # A zero initial state adds nothing: its dot would still write a
    # float32 (H, 4H) result for the main dot to read back.
    with jax.named_scope("w_hh_grad"):
        dot = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)
        dw = dot("sbh,sbg->hg", ys[:-1], dxg[1:])
        if h0 is not None:
            dw = dw + dot("bh,bg->hg", h0, dxg[0])
    return dw.astype(w_hh.dtype), dxg, dh0, dc0


_lstm_recurrence.defvjp(_lstm_recurrence_fwd, _lstm_recurrence_bwd)


def lstm_layer(p, xs, h0=None, c0=None, unroll: int = 1, chunk: int = 0):
    """xs: (B, S, d_in) -> (B, S, d_hidden), (h, c) final.

    ``unroll`` replicates the step body inside each while iteration so
    the recurrent weight matrix is fetched once per ``unroll`` steps
    (the §Perf weight-amortization lever). The ``lax.scan`` path's
    backward never threads a w_hh gradient through the time loop: the
    reversed scan emits dgates_t (the gradient of the hoisted input
    pre-activations) and reads w_hh only for dh_{t-1} = dgates_t w_hh^T;
    ``dW_hh`` is then one float32-accumulated matmul contracted over
    (S, B), plus ``h0``'s term where an initial state is given, under
    the ``w_hh_grad`` scope. ``chunk`` runs the
    gradient-checkpointed scan under plain autodiff instead."""
    B = xs.shape[0]
    d_h = p["w_hh"].shape[0]
    h = jnp.zeros((B, d_h), xs.dtype) if h0 is None else h0
    c = jnp.zeros((B, d_h), jnp.float32) if c0 is None else c0
    # hoist the input matmul out of the scan (one big MXU matmul)
    xg = xs @ p["w_ih"].astype(xs.dtype) + p["b"].astype(xs.dtype)  # (B, S, 4h)

    if chunk:
        from repro.models.layers import chunked_scan

        step = functools.partial(_lstm_step, p["w_hh"])
        (h, c), ys = chunked_scan(step, (h, c), xg.swapaxes(0, 1), chunk=chunk, unroll=unroll)
    else:
        ys, h, c = _lstm_recurrence(p["w_hh"], xg.swapaxes(0, 1), h0, c, unroll)
    return ys.swapaxes(0, 1), (h, c)


def lstm_stack_init(key, cfg: LSTMConfig, dtype=jnp.float32):
    keys = jax.random.split(key, cfg.n_layers)
    return [
        lstm_cell_init(keys[i], cfg.d_in if i == 0 else cfg.d_hidden, cfg.d_hidden, dtype)
        for i in range(cfg.n_layers)
    ]


def lstm_stack(params, xs, unroll: int = 1, chunk: int = 0):
    """List-of-layers forward. Returns (B, S, d_hidden)."""
    states = []
    for p in params:
        xs, st = lstm_layer(p, xs, unroll=unroll, chunk=chunk)
        states.append(st)
    return xs, states


def lstm_stack_step(params, x, states):
    """Single-step (decode). x: (B, d_in); states: [(h, c)] per layer."""
    new_states = []
    for p, (h, c) in zip(params, states):
        x, c = lstm_cell_step(p, x, h, c)
        new_states.append((x, c))
    return x, new_states


def lstm_stack_init_state(cfg: LSTMConfig, batch: int, dtype=jnp.float32):
    return [
        (jnp.zeros((batch, cfg.d_hidden), dtype), jnp.zeros((batch, cfg.d_hidden), jnp.float32))
        for _ in range(cfg.n_layers)
    ]
