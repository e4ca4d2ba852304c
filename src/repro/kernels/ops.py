"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute in interpret mode; on TPU they compile to
Mosaic. ``rnnt_joint`` carries a custom_vjp whose backward is chosen by
backend: the fused Pallas backward (recomputing the joint tile in VMEM
with the forward's shape bucketing) off-CPU, the U-chunked jnp
rematerializer on CPU — both preserve the forward's O(B·T·U) memory
during training.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention_vjp
from repro.kernels.lstm_gates import lstm_gates_fused
from repro.kernels.rnnt_joint import rnnt_joint_bwd_fused, rnnt_joint_fused


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


@jax.jit
def attention(q, k, v):
    """Non-causal attention of equal-length, equal-head q, k, v
    (B, S, H, D), differentiable: the fused flash kernel and its own
    backward (``kernels/flash_attention.py``)."""
    return flash_attention_vjp(q, k, v, interpret=_on_cpu())


@functools.partial(jax.jit, static_argnames=("window",))
def decode_attention(q, k_cache, v_cache, pos, window: int = 0):
    return flash_decode(q, k_cache, v_cache, pos, window=window, interpret=_on_cpu())


@jax.jit
def lstm_gates(gates, c):
    return lstm_gates_fused(gates, c, interpret=_on_cpu())


# ------------------------------------------------------------ rnnt joint

def _joint_ref_chunked(enc_proj, pred_proj, w_out, bias, labels, u_chunk: int = 8):
    """U-chunked jnp joint (differentiable; used for the custom bwd)."""
    B, T, J = enc_proj.shape
    U1 = pred_proj.shape[1]
    n_chunks = max(1, U1 // u_chunk)
    pad = (-U1) % n_chunks
    g = jnp.pad(pred_proj, ((0, 0), (0, pad), (0, 0))) if pad else pred_proj
    l = jnp.pad(labels, ((0, 0), (0, pad))) if pad else labels
    c = g.shape[1] // n_chunks
    gc = g.reshape(B, n_chunks, c, J).swapaxes(0, 1)
    lc = l.reshape(B, n_chunks, c).swapaxes(0, 1)

    def body(_, inp):
        g_i, l_i = inp
        h = jnp.tanh(
            enc_proj[:, :, None, :].astype(jnp.float32) + g_i[:, None, :, :].astype(jnp.float32)
        )
        logits = h @ w_out.astype(jnp.float32) + bias.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        blank = logits[..., 0] - lse
        idx = l_i[:, None, :, None].astype(jnp.int32)
        lab = jnp.take_along_axis(logits, idx, axis=-1)[..., 0] - lse
        return None, (blank, lab)

    _, (blanks, labs) = jax.lax.scan(body, None, (gc, lc))
    # (n_chunks, B, T, c) -> (B, T, n_chunks*c): chunk axis must land
    # OUTSIDE the within-chunk axis, adjacent to it, before flattening
    blank_lp = jnp.moveaxis(blanks, 0, 2).reshape(B, T, -1)[:, :, :U1]
    label_lp = jnp.moveaxis(labs, 0, 2).reshape(B, T, -1)[:, :, :U1]
    return blank_lp, label_lp


@jax.custom_vjp
def rnnt_joint(enc_proj, pred_proj, w_out, bias, labels):
    return rnnt_joint_fused(enc_proj, pred_proj, w_out, bias, labels, interpret=_on_cpu())


def _rnnt_joint_fwd(enc_proj, pred_proj, w_out, bias, labels):
    blank, label, lse = rnnt_joint_fused(
        enc_proj, pred_proj, w_out, bias, labels, interpret=_on_cpu(), return_lse=True
    )
    return (blank, label), (enc_proj, pred_proj, w_out, bias, labels, lse)


def _use_joint_bwd_pallas() -> bool:
    """The joint's backward runs the Pallas kernels off the CPU."""
    return not _on_cpu()


def _rnnt_joint_bwd(res, cts):
    enc_proj, pred_proj, w_out, bias, labels, lse = res
    if _use_joint_bwd_pallas():
        de, dg, dw, db = rnnt_joint_bwd_fused(
            enc_proj, pred_proj, w_out, bias, labels, lse, cts[0], cts[1], interpret=_on_cpu()
        )
    else:
        _, vjp = jax.vjp(
            lambda e, g, w, b: _joint_ref_chunked(e, g, w, b, labels),
            enc_proj,
            pred_proj,
            w_out,
            bias,
        )
        de, dg, dw, db = vjp(cts)
    return (
        de.astype(enc_proj.dtype),
        dg.astype(pred_proj.dtype),
        dw.astype(w_out.dtype),
        db.astype(bias.dtype),
        None,
    )


rnnt_joint.defvjp(_rnnt_joint_fwd, _rnnt_joint_bwd)
