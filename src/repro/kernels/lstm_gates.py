"""Fused LSTM gate nonlinearities + state update (Pallas TPU).

The per-step LSTM cell after the matmuls is four sigmoids/tanhs and
two multiplies over (B, H) — on TPU a chain of small VPU ops whose
HBM round-trips between unfused HLOs dominate the step at decode
batch sizes. The kernel fuses them in one VMEM-resident pass.
Gates layout: (B, 4, H) [i | f | g | o]; each block carries the whole
(small) batch and the grid tiles H only, so every block's last two
dims are either full array dims or multiples of 128 lanes — the shape
Mosaic accepts for bf16 gates and an f32 cell.

``lstm_gates_fused_vjp`` adds a custom-VJP wrapper whose backward is a
second fused kernel: it saves only (gates, c) — the matmul outputs the
training graph keeps alive anyway — recomputes the four cheap
activations in VMEM and emits (dgates, dc_prev) in one pass. Without
it, autodiff through the cell stores every intermediate activation
(i, f, g, o, c_new, tanh(c_new): 6 extra (B, H) residuals *per scan
step*) and replays the chain as ~a dozen unfused HLOs; the LSTM cell
dominates the per-client ``lax.scan`` inside the federated round's
vmapped local steps, so this backward is the round step's hottest
gradient path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _gate(g_ref, k: int):
    return g_ref[:, k, :].astype(jnp.float32)  # (B, th) of block (B, 4, th)


def _kernel(g_ref, c_ref, h_out_ref, c_out_ref):
    c = c_ref[...].astype(jnp.float32)  # (B, th)
    i = jax.nn.sigmoid(_gate(g_ref, 0))
    f = jax.nn.sigmoid(_gate(g_ref, 1) + 1.0)
    gg = jnp.tanh(_gate(g_ref, 2))
    o = jax.nn.sigmoid(_gate(g_ref, 3))
    c_new = f * c + i * gg
    h_new = o * jnp.tanh(c_new)
    h_out_ref[...] = h_new.astype(h_out_ref.dtype)
    c_out_ref[...] = c_new.astype(c_out_ref.dtype)


def _specs(B: int, th: int):
    """(gates, state) block specs for a grid over H tiles: a (B, 4, th)
    gates block and a (B, th) block of h / c / their cotangents."""
    return (pl.BlockSpec((B, 4, th), lambda hi: (0, 0, hi)),
            pl.BlockSpec((B, th), lambda hi: (0, hi)))


def lstm_gates_fused(
    gates: jnp.ndarray, c: jnp.ndarray, *, th: int = 256, interpret: bool = False
):
    """gates: (B, 4H) preactivations [i|f|g|o]; c: (B, H).
    Returns (h_new, c_new) matching ref.lstm_gates_ref."""
    B, H4 = gates.shape
    H = H4 // 4
    th = min(th, H)
    assert H % th == 0, (H, th)
    g3 = gates.reshape(B, 4, H)

    gspec, sspec = _specs(B, th)
    h_new, c_new = pl.pallas_call(
        _kernel,
        grid=(H // th,),
        in_specs=[gspec, sspec],
        out_specs=[sspec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H), gates.dtype),
            jax.ShapeDtypeStruct((B, H), c.dtype),
        ],
        interpret=interpret,
    )(g3, c)
    return h_new, c_new


def _bwd_kernel(g_ref, c_ref, dh_ref, dcn_ref, dg_ref, dc_ref):
    c = c_ref[...].astype(jnp.float32)  # (B, th)
    dh = dh_ref[...].astype(jnp.float32)
    dcn = dcn_ref[...].astype(jnp.float32)
    i = jax.nn.sigmoid(_gate(g_ref, 0))
    f = jax.nn.sigmoid(_gate(g_ref, 1) + 1.0)
    gg = jnp.tanh(_gate(g_ref, 2))
    o = jax.nn.sigmoid(_gate(g_ref, 3))
    t = jnp.tanh(f * c + i * gg)  # tanh(c_new), recomputed in VMEM
    dc = dcn + dh * o * (1.0 - t * t)
    dg_ref[:, 0, :] = (dc * gg * i * (1.0 - i)).astype(dg_ref.dtype)
    dg_ref[:, 1, :] = (dc * c * f * (1.0 - f)).astype(dg_ref.dtype)
    dg_ref[:, 2, :] = (dc * i * (1.0 - gg * gg)).astype(dg_ref.dtype)
    dg_ref[:, 3, :] = (dh * t * o * (1.0 - o)).astype(dg_ref.dtype)
    dc_ref[...] = (dc * f).astype(dc_ref.dtype)


def lstm_gates_bwd_fused(gates, c, dh, dc_next, *, th: int = 256, interpret: bool = False):
    """Fused backward of the cell: (gates, c, dh, dc_next) ->
    (dgates (B, 4H), dc_prev (B, H)) in one VMEM pass, recomputing the
    activations from the saved pre-activations instead of storing six
    per-step residual tensors."""
    B, H4 = gates.shape
    H = H4 // 4
    th = min(th, H)
    assert H % th == 0, (H, th)
    g3 = gates.reshape(B, 4, H)

    gspec, sspec = _specs(B, th)
    dg3, dc_prev = pl.pallas_call(
        _bwd_kernel,
        grid=(H // th,),
        in_specs=[gspec, sspec, sspec, sspec],
        out_specs=[gspec, sspec],
        out_shape=[
            jax.ShapeDtypeStruct((B, 4, H), gates.dtype),
            jax.ShapeDtypeStruct((B, H), c.dtype),
        ],
        interpret=interpret,
    )(g3, c, dh, dc_next)
    return dg3.reshape(B, H4), dc_prev


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lstm_gates_vjp(gates, c, th, interpret):
    return lstm_gates_fused(gates, c, th=th, interpret=interpret)


def _lstm_gates_fwd(gates, c, th, interpret):
    out = lstm_gates_fused(gates, c, th=th, interpret=interpret)
    return out, (gates, c)


def _lstm_gates_bwd(th, interpret, res, cts):
    gates, c = res
    dh, dc_next = cts
    return lstm_gates_bwd_fused(gates, c, dh, dc_next, th=th, interpret=interpret)


_lstm_gates_vjp.defvjp(_lstm_gates_fwd, _lstm_gates_bwd)


def lstm_gates_fused_vjp(gates, c, *, th: int = 256, interpret: bool = False):
    """The training-path entry point: fused forward AND fused custom-VJP
    backward (autodiff through the raw ``pallas_call`` is unsupported,
    and the unfused jnp backward is the round step's hot spot)."""
    return _lstm_gates_vjp(gates, c, th, interpret)

