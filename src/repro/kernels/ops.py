"""Jit'd public wrappers for the Pallas kernels.

On CPU the kernels execute in interpret mode; on TPU they compile to
Mosaic.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.decode_attention import flash_decode
from repro.kernels.flash_attention import flash_attention_vjp
from repro.kernels.lstm_gates import lstm_gates_fused
from repro.kernels.rnnt_joint import rnnt_joint as rnnt_joint_kernel


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


def rnnt_joint(enc_proj, pred_proj, w_out, bias, labels, frames):
    """The RNN-T joint's (blank, label) log-probs, differentiable: the
    fused kernel and its own backward (``kernels/rnnt_joint.py``)."""
    return rnnt_joint_kernel(enc_proj, pred_proj, w_out, bias, labels, frames, interpret=_on_cpu())
