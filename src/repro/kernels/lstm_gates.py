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


# ---------------------------------------------------------- full-scan kernel
# The per-step gates kernel above still re-streams w_hh (H x 4H) from
# HBM every scan iteration — at the paper's hidden sizes that refetch
# is the LSTM layer's dominant HBM traffic. The scan kernel below runs
# the WHOLE sequence in one pallas_call with grid=(S,): w_hh is a
# constant-index input block (fetched once, VMEM-resident for all S
# steps — TPU grids are sequential, so revisited blocks stay put), the
# (h, c) carry lives in VMEM scratch, and each step does one (B, H) x
# (H, 4H) MXU matmul plus the fused gate math. The backward is a second
# scan kernel over the reversed grid that recomputes each step's gate
# preactivations in VMEM from the saved (ys, cs) sequences — only two
# (S, B, H) residuals instead of autodiff's ~six per-step activation
# tensors — and accumulates dw_hh in a VMEM scratch written once at the
# end.


from jax.experimental.pallas import tpu as pltpu  # noqa: E402


def _split_gates(gates, H: int):
    i = jax.nn.sigmoid(gates[:, :H])
    f = jax.nn.sigmoid(gates[:, H : 2 * H] + 1.0)
    g = jnp.tanh(gates[:, 2 * H : 3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H :])
    return i, f, g, o


def _scan_kernel(xg_ref, whh_ref, h0_ref, c0_ref, ys_ref, cs_ref, h_s, c_s):
    s = pl.program_id(0)
    H = whh_ref.shape[0]

    @pl.when(s == 0)
    def _init():
        h_s[...] = h0_ref[...].astype(jnp.float32)
        c_s[...] = c0_ref[...].astype(jnp.float32)

    h, c = h_s[...], c_s[...]
    gates = xg_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h, whh_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    i, f, g, o = _split_gates(gates, H)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    h_s[...] = h_new
    c_s[...] = c_new
    ys_ref[0] = h_new.astype(ys_ref.dtype)
    cs_ref[0] = c_new.astype(cs_ref.dtype)


def lstm_scan_fused(xg, w_hh, h0, c0, *, interpret: bool = False):
    """xg: (S, B, 4H) time-major hoisted input preactivations (x @ w_ih
    + b); w_hh: (H, 4H); h0, c0: (B, H). Returns (ys, cs): (S, B, H)
    hidden and cell sequences (cs is the backward's recompute anchor —
    the training graph keeps ys alive anyway)."""
    S, B, H4 = xg.shape
    H = H4 // 4
    ys, cs = pl.pallas_call(
        _scan_kernel,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, B, H4), lambda s: (s, 0, 0)),
            pl.BlockSpec((H, H4), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H), lambda s: (s, 0, 0)),
            pl.BlockSpec((1, B, H), lambda s: (s, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, B, H), xg.dtype),
            jax.ShapeDtypeStruct((S, B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
        ],
        interpret=interpret,
    )(xg, w_hh, h0, c0)
    return ys, cs


def _scan_bwd_kernel(
    xg_ref, whh_ref, h0_ref, c0_ref, ysp_ref, csp_ref, dys_ref, dhT_ref, dcT_ref,
    dxg_ref, dwhh_ref, dh0_ref, dc0_ref,
    dh_s, dc_s, dw_s,
):
    s = pl.program_id(0)
    S = pl.num_programs(0)
    t = S - 1 - s
    H = whh_ref.shape[0]
    whh = whh_ref[...].astype(jnp.float32)

    @pl.when(s == 0)
    def _init():
        dh_s[...] = dhT_ref[...].astype(jnp.float32)
        dc_s[...] = dcT_ref[...].astype(jnp.float32)
        dw_s[...] = jnp.zeros_like(dw_s)

    # step-(t-1) carry, read from the saved sequences (blocks indexed at
    # max(t-1, 0)); at t == 0 the true predecessor is the initial state
    first = t == 0
    h_prev = jnp.where(first, h0_ref[...].astype(jnp.float32),
                       ysp_ref[0].astype(jnp.float32))
    c_prev = jnp.where(first, c0_ref[...].astype(jnp.float32),
                       csp_ref[0].astype(jnp.float32))

    # recompute this step's gate preactivations in VMEM
    gates = xg_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h_prev, whh, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    i, f, g, o = _split_gates(gates, H)
    c_t = f * c_prev + i * g
    tct = jnp.tanh(c_t)

    dh = dh_s[...] + dys_ref[0].astype(jnp.float32)
    dc = dc_s[...] + dh * o * (1.0 - tct * tct)
    dgates = jnp.concatenate(
        [
            dc * g * i * (1.0 - i),
            dc * c_prev * f * (1.0 - f),
            dc * i * (1.0 - g * g),
            dh * tct * o * (1.0 - o),
        ],
        axis=-1,
    )  # (B, 4H)
    dxg_ref[0] = dgates.astype(dxg_ref.dtype)
    dw_s[...] += jax.lax.dot_general(
        h_prev, dgates, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (H, 4H)
    dh_s[...] = jax.lax.dot_general(
        dgates, whh, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (B, H)
    dc_s[...] = dc * f

    @pl.when(s == S - 1)
    def _final():
        dwhh_ref[...] = dw_s[...].astype(dwhh_ref.dtype)
        dh0_ref[...] = dh_s[...].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_s[...].astype(dc0_ref.dtype)


def lstm_scan_bwd_fused(xg, w_hh, h0, c0, ys, cs, dys, dhT, dcT, *, interpret: bool = False):
    """Reversed-grid backward of ``lstm_scan_fused``: one grid step per
    time step t = S-1..0, gate preactivations recomputed in VMEM from
    (xg, ys, cs), dw_hh accumulated in VMEM scratch and written once.
    Returns (dxg, dw_hh, dh0, dc0)."""
    S, B, H4 = xg.shape
    H = H4 // 4

    def rev(s):
        return S - 1 - s

    def prev(s):
        return jnp.maximum(S - 2 - s, 0)

    dxg, dwhh, dh0, dc0 = pl.pallas_call(
        _scan_bwd_kernel,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, B, H4), lambda s: (rev(s), 0, 0)),
            pl.BlockSpec((H, H4), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
            pl.BlockSpec((1, B, H), lambda s: (prev(s), 0, 0)),
            pl.BlockSpec((1, B, H), lambda s: (prev(s), 0, 0)),
            pl.BlockSpec((1, B, H), lambda s: (rev(s), 0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, B, H4), lambda s: (rev(s), 0, 0)),
            pl.BlockSpec((H, H4), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
            pl.BlockSpec((B, H), lambda s: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, B, H4), jnp.float32),
            jax.ShapeDtypeStruct((H, H4), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
            jax.ShapeDtypeStruct((B, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((B, H), jnp.float32),
            pltpu.VMEM((H, H4), jnp.float32),
        ],
        interpret=interpret,
    )(xg, w_hh, h0, c0, ys, cs, dys, dhT, dcT)
    return dxg, dwhh, dh0, dc0


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _lstm_scan_vjp(xg, w_hh, h0, c0, interpret):
    ys, cs = lstm_scan_fused(xg, w_hh, h0, c0, interpret=interpret)
    return ys, ys[-1], cs[-1]


def _lstm_scan_fwd(xg, w_hh, h0, c0, interpret):
    ys, cs = lstm_scan_fused(xg, w_hh, h0, c0, interpret=interpret)
    return (ys, ys[-1], cs[-1]), (xg, w_hh, h0, c0, ys, cs)


def _lstm_scan_bwd(interpret, res, cts):
    xg, w_hh, h0, c0, ys, cs = res
    dys, dhT, dcT = cts
    dxg, dwhh, dh0, dc0 = lstm_scan_bwd_fused(
        xg, w_hh, h0, c0, ys, cs, dys, dhT, dcT, interpret=interpret
    )
    return (dxg.astype(xg.dtype), dwhh.astype(w_hh.dtype),
            dh0.astype(h0.dtype), dc0.astype(c0.dtype))


_lstm_scan_vjp.defvjp(_lstm_scan_fwd, _lstm_scan_bwd)


def lstm_scan_fused_vjp(xg, w_hh, h0, c0, *, interpret: bool = False):
    """Training-path entry point for the full-scan kernel: returns
    (ys (S, B, H), h_final, c_final) with the fused reversed-scan
    custom-VJP backward. The outer input matmul (xs @ w_ih + b) stays
    under normal autodiff — only the recurrence is kernel-resident."""
    return _lstm_scan_vjp(xg, w_hh, h0, c0, interpret)
