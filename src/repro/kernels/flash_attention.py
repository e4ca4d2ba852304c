"""Flash attention (Pallas TPU): a forward with causal / sliding-window /
GQA / soft-capped scores, and ``flash_attention_vjp``, the encoder's
non-causal self-attention with its own backward kernel.

The kernels read q, k, v (B, S, H, D) as they lie, (B, S, H * D) with
every head of a sequence tile in one block, and loop over the heads
inside: no transpose or pad in HBM. Each score tile is held transposed,
keys on sublanes and queries on lanes, sT = k · qᵀ of shape (tk, tq), so
the per-query numbers (running max and sum, log-sum-exp, rowsum(dO · O))
are (1, tq) rows, broadcast over the keys and stored lane-dense as
(B, H, S). The scores and probabilities live only in VMEM tiles, in both
directions:

    forward   sT = k qᵀ, oᵀ += vᵀ pT          (online softmax over k tiles)
    backward  sT = k qᵀ, pT = exp(sT - lse), dv += pT dO, dpT = v dOᵀ,
              dsT = pT (dpT - rowsum(dO · O)), dk += dsT q, dqᵀ += kᵀ dsT

q, k, v and dO go to the MXU in the dtype they arrive in, accumulating
in float32; the softmax statistics and the accumulators are float32,
and P and dS are cast to the operand dtype only as matmul operands.

A sequence longer than a tile ends in a partial tile, whose rows past
the end hold whatever the buffer held. The forward masks those keys'
scores (in the last key tile only) and zeroes their values; a query
row past the end only ever feeds its own output column, which is never
written back. The backward zeroes both ends' rows (q, O, dO and lse;
k and v), so nothing from them reaches a gradient.

The backward forms rowsum(dO · O) itself, per query tile and head: done
by XLA outside, the float32 product came out laid out across the heads
and took a copy and a reduce of 98 MB per call at whisper-libri-fp32's
shapes. It is one kernel over a (batch, key tile, query tile) grid: a
key tile's dK and dV accumulate over the query tiles in VMEM, and the
sequence's whole dQᵀ (H x D x S float32: 3 MB at 8 x 64 x 1,536) stays in
VMEM across the key tiles, so each score tile is recomputed once, where
a separate dQ kernel would recompute it twice.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
TILE = 512
# the backward's blocks, accumulators and score tiles at TILE and 8 x 64
# heads come to over 17 MB, past the 16 MB a kernel may take by default on
# a v5e (which has 128 MB of VMEM)
BWD_VMEM_BYTES = 32 * 2**20
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ
_TN = (((0,), (0,)), ((), ()))   # aᵀ · b


def _valid(tile_idx, t: int, length: int, shape, axis: int):
    """Whether each position of a tile of ``shape`` along ``axis`` lies
    before ``length``: the tile ``tile_idx`` of size ``t``."""
    return tile_idx * t + jax.lax.broadcasted_iota(jnp.int32, shape, axis) < length


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                heads: int, kv_heads: int, scale: float, causal: bool, window: int,
                logit_softcap: float, kv_len: int, tk: int, n_k: int):
    qi, ki = pl.program_id(1), pl.program_id(2)
    tq = q_ref.shape[1]
    D, Dv = q_ref.shape[2] // heads, v_ref.shape[2] // kv_heads
    group = heads // kv_heads

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        keep = None
        if masked:
            keep = _valid(ki, tk, kv_len, (tk, tq if causal or window else 1), 0)
            if causal or window:
                k_pos = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 0)
                q_pos = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tk, tq), 1)
                if causal:
                    keep &= k_pos <= q_pos
                if window:
                    keep &= k_pos > q_pos - window
        for h in range(heads):
            g = h // group
            q = q_ref[0, :, h * D:(h + 1) * D] * scale                 # (tq, D)
            v = v_ref[0, :, g * Dv:(g + 1) * Dv]                       # (tk, Dv)
            s = jax.lax.dot_general(k_ref[0, :, g * D:(g + 1) * D], q, _NT,
                                    preferred_element_type=jnp.float32)  # (tk, tq)
            if logit_softcap > 0:
                s = logit_softcap * jnp.tanh(s / logit_softcap)
            if masked:
                s = jnp.where(keep, s, NEG_INF)
                v = jnp.where(_valid(ki, tk, kv_len, (tk, 1), 0), v, 0)
            m_prev = m_ref[h:h + 1]                                    # (1, tq)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            if causal or window:  # a query whose keys so far are all masked has m_new == NEG_INF
                p = jnp.where(keep, p, 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h:h + 1] = corr * l_ref[h:h + 1] + jnp.sum(p, axis=0, keepdims=True)
            acc_ref[h] = corr * acc_ref[h] + jax.lax.dot_general(
                v, p.astype(v.dtype), _TN, preferred_element_type=jnp.float32)  # (Dv, tq)
            m_ref[h:h + 1] = m_new

    if causal or window or (kv_len % tk and n_k == 1):
        step(True)
    elif kv_len % tk:
        pl.when(ki == n_k - 1)(lambda: step(True))
        pl.when(ki < n_k - 1)(lambda: step(False))
    else:
        step(False)

    @pl.when(ki == n_k - 1)
    def _finalize():
        for h in range(heads):
            l = jnp.maximum(l_ref[h:h + 1], 1e-30)
            o_ref[0, :, h * Dv:(h + 1) * Dv] = (acc_ref[h] / l).T.astype(o_ref.dtype)
            lse_ref[0, h:h + 1] = m_ref[h:h + 1] + jnp.log(l)


def _forward(q, k, v, *, causal: bool, window: int, logit_softcap: float,
             tq: int, tk: int, interpret: bool):
    """o (B, Sq, H, Dv) and the log-sum-exp of each query's scores
    (B, H, Sq), float32."""
    B, Sq, H, D = q.shape
    Sk, Kv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    n_q, n_k = pl.cdiv(Sq, tq), pl.cdiv(Sk, tk)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=H, kv_heads=Kv, scale=D ** -0.5, causal=causal,
                          window=window, logit_softcap=logit_softcap, kv_len=Sk, tk=tk,
                          n_k=n_k),
        grid=(B, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, tq, H * D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, tk, Kv * D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, tk, Kv * Dv), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=[pl.BlockSpec((1, tq, H * Dv), lambda b, qi, ki: (b, qi, 0)),
                   pl.BlockSpec((1, H, tq), lambda b, qi, ki: (b, 0, qi))],
        out_shape=[jax.ShapeDtypeStruct((B, Sq, H * Dv), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Sq), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((H, tq), jnp.float32),
            pltpu.VMEM((H, tq), jnp.float32),
            pltpu.VMEM((H, Dv, tq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q.reshape(B, Sq, H * D), k.reshape(B, Sk, Kv * D), v.reshape(B, Sk, Kv * Dv))
    return o.reshape(B, Sq, H, Dv), lse


def flash_attention(
    q: jnp.ndarray,             # (B, Sq, H, D)
    k: jnp.ndarray,             # (B, Sk, Kv, D)
    v: jnp.ndarray,             # (B, Sk, Kv, Dv)
    *,
    causal: bool = True,
    window: int = 0,            # 0 = no window
    logit_softcap: float = 0.0,
    tq: int = TILE,
    tk: int = TILE,
    interpret: bool = False,
) -> jnp.ndarray:
    """Attention forward, (B, Sq, H, Dv) in q's dtype; GQA: kv head
    h // (H // Kv) serves q head h, with no copy in HBM."""
    return _forward(q, k, v, causal=causal, window=window, logit_softcap=logit_softcap,
                    tq=min(tq, q.shape[1]), tk=min(tk, k.shape[1]), interpret=interpret)[0]


def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref,
                dq_acc, dk_acc, dv_acc, *, heads: int, scale: float, length: int,
                t: int, n: int):
    ki, qi = pl.program_id(1), pl.program_id(2)
    D = q_ref.shape[2] // heads

    @pl.when((ki == 0) & (qi == 0))
    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q, o, do, lse = q_ref[0], o_ref[0], do_ref[0], lse_ref[0]
    k, v = k_ref[0], v_ref[0]
    if length % t:   # rows past the end of a partial tile hold stale data
        q_rows = _valid(qi, t, length, (t, 1), 0)
        k_rows = _valid(ki, t, length, (t, 1), 0)
        q, o, do = (jnp.where(q_rows, x, 0) for x in (q, o, do))
        lse = jnp.where(_valid(qi, t, length, (1, t), 1), lse, 0.0)
        k, v = jnp.where(k_rows, k, 0), jnp.where(k_rows, v, 0)
    ones = jnp.ones((1, D), jnp.float32)
    for h in range(heads):
        hd = slice(h * D, (h + 1) * D)
        qh, kh, vh, doh = q[:, hd] * scale, k[:, hd], v[:, hd], do[:, hd]
        # rowsum(dO * O) as a (1, t) row, in float32
        di = jax.lax.dot_general(ones, o[:, hd].astype(jnp.float32) * doh.astype(jnp.float32),
                                 _NT, precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
        s = jax.lax.dot_general(kh, qh, _NT, preferred_element_type=jnp.float32)   # (t, t)
        p = jnp.exp(s - lse[h:h + 1])
        dv_acc[h] += jnp.dot(p.astype(doh.dtype), doh, preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(vh, doh, _NT, preferred_element_type=jnp.float32)
        ds = (p * (dp - di)).astype(qh.dtype)
        dk_acc[h] += jnp.dot(ds, qh, preferred_element_type=jnp.float32)
        dq_acc[qi, h] += jax.lax.dot_general(kh, ds, _TN, preferred_element_type=jnp.float32)

    @pl.when(qi == n - 1)
    def _write_dkv():
        for h in range(heads):
            dk_ref[0, :, h * D:(h + 1) * D] = dk_acc[h].astype(dk_ref.dtype)
            dv_ref[0, :, h * D:(h + 1) * D] = dv_acc[h].astype(dv_ref.dtype)

    @pl.when(ki == n - 1)
    def _write_dq():   # the dq block is written back at every step; this last write stands
        for h in range(heads):
            dq_ref[0, :, h * D:(h + 1) * D] = (dq_acc[qi, h] * scale).T.astype(dq_ref.dtype)


def _backward(q, k, v, o, lse, do, *, interpret: bool):
    B, S, H, D = q.shape
    t = min(TILE, S)
    n = pl.cdiv(S, t)
    q_spec = pl.BlockSpec((1, t, H * D), lambda b, ki, qi: (b, qi, 0))
    k_spec = pl.BlockSpec((1, t, H * D), lambda b, ki, qi: (b, ki, 0))
    row_spec = pl.BlockSpec((1, H, t), lambda b, ki, qi: (b, 0, qi))
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, heads=H, scale=D ** -0.5, length=S, t=t, n=n),
        grid=(B, n, n),
        in_specs=[q_spec, k_spec, k_spec, q_spec, q_spec, row_spec],
        out_specs=[q_spec, k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * D), x.dtype) for x in (q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((n, H, D, t), jnp.float32),
            pltpu.VMEM((H, t, D), jnp.float32),
            pltpu.VMEM((H, t, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=BWD_VMEM_BYTES),
        interpret=interpret,
    )(*(x.reshape(B, S, H * D) for x in (q, k, v, o, do.astype(q.dtype))), lse)
    return tuple(x.reshape(B, S, H, D) for x in (dq, dk, dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attention_vjp(q, k, v, interpret):
    return _attention_fwd(q, k, v, interpret)[0]


def _attention_fwd(q, k, v, interpret):
    t = min(TILE, q.shape[1])
    o, lse = _forward(q, k, v, causal=False, window=0, logit_softcap=0.0, tq=t, tk=t,
                      interpret=interpret)
    return o, (q, k, v, o, lse)


def _attention_bwd(interpret, res, do):
    return _backward(*res, do, interpret=interpret)


_attention_vjp.defvjp(_attention_fwd, _attention_bwd)


def flash_attention_vjp(q, k, v, *, interpret: bool = False):
    """Non-causal attention of q, k, v (B, S, H, D), equal lengths and
    heads, differentiable: the fused forward and the fused backward,
    which recomputes the scores per tile from q, k and the forward's
    log-sum-exp; neither ever writes a score to HBM."""
    if not q.shape == k.shape == v.shape:
        raise ValueError(f"q, k, v shapes differ: {q.shape}, {k.shape}, {v.shape}")
    return _attention_vjp(q, k, v, interpret)
