"""Fused RNN-T joint Pallas TPU kernel, forward and backward.

The joint's logits are (B, T, U1, V) (V = 4096 word-pieces); the
transducer loss reads two numbers of each lattice point: the blank's
and the next label's log-probability. The kernels keep the logits in
VMEM and write only those, with the log-sum-exp as the backward's
recompute anchor.

A tile is ``tq`` frames of one utterance by all of its U1 label slots:

    h      = tanh(e_t + g_u)              (tq * U1, J)  bf16 MXU operand
    logits = h @ Wo[:, slab] + b[slab]     (tq * U1, tv) float32, per slab
    lse    : online over the V slabs       float32
    blank, label = h . w_pick + b_pick     the two picked columns, float32

``w_pick`` (B, 2, U1, J) holds column 0 (the blank) and column
``labels[u]`` of Wo for each label slot. The wrapper gathers it with jnp,
so autodiff scatters its cotangent back into dWo and the kernels never
gather.

Each utterance's frame count rides in as an SMEM block: a tile past it
computes nothing. The loss gives those lattice points a zero cotangent,
so the forward writes zeros there and the backward adds nothing. The
counts are an ordinary input, not scalar prefetch, so that vmap over
clients adds a grid axis rather than a loop of calls.

The backward is one kernel: it recomputes h and the slab logits once
from the saved log-sum-exp and produces dh (then de, and dg summed in a
per-utterance block) and dWo, db, accumulated in blocks that stay in
VMEM over the whole grid. With the forward, that is four matmul units.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TQ = 8  # frames a tile
TV = 512  # vocabulary columns a slab
U_ALIGN = 16  # label slots padded to a bf16 sublane tile
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b


def _hidden(e_ref, g_ref):
    """h = tanh(e + g) of the tile, (tq, U1, J) float32."""
    e = e_ref[0].astype(jnp.float32)
    g = g_ref[0].astype(jnp.float32)
    return jnp.tanh(e[:, None, :] + g[None, :, :])


def _slab_logits(hb, w_ref, b_ref, cols):
    logits = jnp.dot(hb, w_ref[:, cols], preferred_element_type=jnp.float32)
    return logits + b_ref[:, cols]


def _fwd_kernel(
    len_ref, e_ref, g_ref, w_ref, b_ref, wp_ref, bp_ref, blank_ref, label_ref, lse_ref, *, tq, tv
):
    bi, ti = pl.program_id(0), pl.program_id(1)
    live = ti * tq < len_ref[bi, 0]

    @pl.when(live)
    def _compute():
        h = _hidden(e_ref, g_ref)  # (tq, U1, J)
        U1, J = h.shape[1:]
        hb = h.astype(w_ref.dtype).reshape(tq * U1, J)
        m = l = None
        for v0 in range(0, w_ref.shape[1], tv):
            s = _slab_logits(hb, w_ref, b_ref, slice(v0, v0 + tv)).reshape(tq, U1, tv)
            m_s = jnp.max(s, axis=-1)
            if m is None:
                m = m_s
                l = jnp.sum(jnp.exp(s - m[..., None]), axis=-1)
            else:
                m_new = jnp.maximum(m, m_s)
                l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new[..., None]), axis=-1)
                m = m_new
        lse = m + jnp.log(l)
        wp = wp_ref[0].astype(jnp.float32)  # (2, U1, J)
        bp = bp_ref[0]  # (2, U1)
        blank_ref[0] = jnp.sum(h * wp[0][None], axis=-1) + bp[0:1] - lse
        label_ref[0] = jnp.sum(h * wp[1][None], axis=-1) + bp[1:2] - lse
        lse_ref[0] = lse

    @pl.when(jnp.logical_not(live))
    def _skip():
        blank_ref[...] = jnp.zeros_like(blank_ref)
        label_ref[...] = jnp.zeros_like(label_ref)
        lse_ref[...] = jnp.zeros_like(lse_ref)


def _bwd_kernel(
    len_ref,
    e_ref,
    g_ref,
    w_ref,
    b_ref,
    wp_ref,
    lse_ref,
    dbl_ref,
    dlb_ref,
    de_ref,
    dg_ref,
    dw_ref,
    db_ref,
    dwp_ref,
    dbp_ref,
    *,
    tq,
    tv,
):
    bi, ti = pl.program_id(0), pl.program_id(1)

    @pl.when((bi == 0) & (ti == 0))
    def _zero_weights():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    @pl.when(ti == 0)
    def _zero_utterance():
        dg_ref[...] = jnp.zeros_like(dg_ref)
        dwp_ref[...] = jnp.zeros_like(dwp_ref)
        dbp_ref[...] = jnp.zeros_like(dbp_ref)

    live = ti * tq < len_ref[bi, 0]

    @pl.when(live)
    def _compute():
        h = _hidden(e_ref, g_ref)  # (tq, U1, J)
        U1, J = h.shape[1:]
        M = tq * U1
        dbl, dlb = dbl_ref[0], dlb_ref[0]  # (tq, U1)
        neg_c = -(dbl + dlb)[..., None]  # d logits = dbl [v=0] + dlb [v=label] - c p
        hb = h.astype(w_ref.dtype).reshape(M, J)
        hc = (neg_c * h).astype(w_ref.dtype).reshape(M, J)
        lse = lse_ref[0][..., None]
        dh = jnp.zeros((M, J), jnp.float32)
        for v0 in range(0, w_ref.shape[1], tv):
            cols = slice(v0, v0 + tv)
            p = jnp.exp(_slab_logits(hb, w_ref, b_ref, cols).reshape(tq, U1, tv) - lse)
            pb = p.reshape(M, tv).astype(w_ref.dtype)
            dh += jax.lax.dot_general(pb, w_ref[:, cols], _NT, preferred_element_type=jnp.float32)
            dw_ref[:, cols] += jax.lax.dot_general(hc, pb, _TN, preferred_element_type=jnp.float32)
            db_ref[:, cols] += jnp.sum(neg_c * p, axis=(0, 1))[None]
        wp = wp_ref[0].astype(jnp.float32)  # (2, U1, J)
        dh = neg_c * dh.reshape(tq, U1, J) + dbl[..., None] * wp[0] + dlb[..., None] * wp[1]
        dpre = dh * (1.0 - h * h)
        de_ref[0] = jnp.sum(dpre, axis=1)
        dg_ref[0] += jnp.sum(dpre, axis=0)
        dwp_ref[0, 0] += jnp.sum(dbl[..., None] * h, axis=0)
        dwp_ref[0, 1] += jnp.sum(dlb[..., None] * h, axis=0)
        dbp_ref[0] += jnp.stack([jnp.sum(dbl, axis=0), jnp.sum(dlb, axis=0)])

    @pl.when(jnp.logical_not(live))
    def _skip():
        de_ref[...] = jnp.zeros_like(de_ref)


def _vmem_limit(J, V, M, tv, backward):
    """Bytes of VMEM a call needs: Wo resident and double-buffered, in
    the backward dWo too (float32), the tile's (M, J) arrays and the
    slab temporaries, and 8 MiB for the small blocks and the compiler."""
    w = 2 * J * V * 2 + (2 * J * V * 4 if backward else 0)
    tile = M * J * 4 * (5 if backward else 2) + 4 * M * tv * 4
    return w + tile + (8 << 20)


def _specs(tq, U1, J, V):
    return (
        pl.BlockSpec(memory_space=pltpu.SMEM),  # frame counts (B, 1)
        pl.BlockSpec((1, tq, J), lambda b, t: (b, t, 0)),  # e, de
        pl.BlockSpec((1, U1, J), lambda b, t: (b, 0, 0)),  # g, dg
        pl.BlockSpec((J, V), lambda b, t: (0, 0)),  # Wo, dWo
        pl.BlockSpec((1, V), lambda b, t: (0, 0)),  # bias, db
        pl.BlockSpec((1, 2, U1, J), lambda b, t: (b, 0, 0, 0)),  # w_pick
        pl.BlockSpec((1, 2, U1), lambda b, t: (b, 0, 0)),  # b_pick
        pl.BlockSpec((1, tq, U1), lambda b, t: (b, t, 0)),  # lattice
    )


def joint_forward(e, g, w, bias, w_pick, b_pick, frames, *, tq=TQ, tv=TV, interpret=False):
    """blank and label log-probs and the log-sum-exp, each (B, T, U1)
    float32, zero at the frames of tiles wholly past ``frames``.

    e (B, T, J) and g (B, U1, J) in the compute dtype with T % tq == 0 and
    U1 % 16 == 0; w (J, V) in the compute dtype, bias (V,) float32; w_pick
    (B, 2, U1, J) the blank's and each slot's label column of w, b_pick
    (B, 2, U1) theirs of bias; frames (B,) int32."""
    B, T, J = e.shape
    U1, V = g.shape[1], w.shape[1]
    tv = min(tv, V)
    lengths, frames_s, slots, whole, row, picks, pick_rows, lattice = _specs(tq, U1, J, V)
    out = jax.ShapeDtypeStruct((B, T, U1), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tq=tq, tv=tv),
        grid=(B, T // tq),
        in_specs=[lengths, frames_s, slots, whole, row, picks, pick_rows],
        out_specs=[lattice, lattice, lattice],
        out_shape=[out, out, out],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem_limit(J, V, tq * U1, tv, backward=False),
        ),
        interpret=interpret,
    )(frames.reshape(B, 1), e, g, w, bias.reshape(1, V), w_pick, b_pick)


def joint_backward(
    e, g, w, bias, w_pick, frames, lse, dblank, dlabel, *, tq=TQ, tv=TV, interpret=False
):
    """Cotangents of :func:`joint_forward`'s two log-probs, all float32:
    de (B, T, J), dg (B, U1, J), dw (J, V), db (V,), dw_pick (B, 2, U1, J),
    db_pick (B, 2, U1). Tiles past ``frames`` must carry zero cotangents:
    they add nothing."""
    B, T, J = e.shape
    U1, V = g.shape[1], w.shape[1]
    tv = min(tv, V)
    lengths, frames_s, slots, whole, row, picks, pick_rows, lattice = _specs(tq, U1, J, V)
    f32 = jnp.float32
    de, dg, dw, db, dwp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, tq=tq, tv=tv),
        grid=(B, T // tq),
        in_specs=[lengths, frames_s, slots, whole, row, picks, lattice, lattice, lattice],
        out_specs=[frames_s, slots, whole, row, picks, pick_rows],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, J), f32),
            jax.ShapeDtypeStruct((B, U1, J), f32),
            jax.ShapeDtypeStruct((J, V), f32),
            jax.ShapeDtypeStruct((1, V), f32),
            jax.ShapeDtypeStruct((B, 2, U1, J), f32),
            jax.ShapeDtypeStruct((B, 2, U1), f32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(J, V, tq * U1, tv, backward=True),
        ),
        interpret=interpret,
    )(frames.reshape(B, 1), e, g, w, bias.reshape(1, V), w_pick, lse, dblank, dlabel)
    return de, dg, dw, db.reshape(V), dwp, dbp


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _joint(e, g, w, bias, w_pick, b_pick, frames, tq, tv, interpret):
    return _joint_fwd(e, g, w, bias, w_pick, b_pick, frames, tq, tv, interpret)[0]


def _joint_fwd(e, g, w, bias, w_pick, b_pick, frames, tq, tv, interpret):
    wc, b32 = w.astype(e.dtype), bias.astype(jnp.float32)
    blank, label, lse = joint_forward(
        e, g, wc, b32, w_pick, b_pick.astype(jnp.float32), frames, tq=tq, tv=tv, interpret=interpret
    )
    return (blank, label), (e, g, w, bias, w_pick, b_pick, frames, lse)


def _joint_bwd(tq, tv, interpret, res, cts):
    e, g, w, bias, w_pick, b_pick, frames, lse = res
    wc, b32 = w.astype(e.dtype), bias.astype(jnp.float32)
    de, dg, dw, db, dwp, dbp = joint_backward(
        e, g, wc, b32, w_pick, frames, lse, *cts, tq=tq, tv=tv, interpret=interpret
    )
    return (
        de.astype(e.dtype),
        dg.astype(g.dtype),
        dw.astype(w.dtype),
        db.astype(bias.dtype),
        dwp.astype(w_pick.dtype),
        dbp.astype(b_pick.dtype),
        None,
    )


_joint.defvjp(_joint_fwd, _joint_bwd)


def rnnt_joint(enc_proj, pred_proj, w_out, bias, labels, frames, *, tq=TQ, tv=TV, interpret=False):
    """(blank_lp, label_lp), each (B, T, U1) float32, differentiable in
    the first four arguments.

    enc_proj (B, T, J) and pred_proj (B, U1, J) in the compute dtype, the
    MXU's operand type; w_out (J, V) and bias (V,) as the parameters are
    kept (their gradients come back in that type); labels (B, U1) int
    (the last slot unused); frames (B,) each utterance's frame count.
    The lattice is padded to tq frames and 16 label slots; frames past an
    utterance's count read 0 when a whole tile lies past it, and any
    other value otherwise: the transducer loss reads neither."""
    B, T, J = enc_proj.shape
    U1 = pred_proj.shape[1]
    pad_t, pad_u = -T % tq, -U1 % U_ALIGN
    e = jnp.pad(enc_proj, ((0, 0), (0, pad_t), (0, 0)))
    g = jnp.pad(pred_proj, ((0, 0), (0, pad_u), (0, 0)))
    lbl = jnp.pad(labels.astype(jnp.int32), ((0, 0), (0, pad_u)))
    ids = jnp.stack([jnp.zeros_like(lbl), lbl], axis=1)  # (B, 2, U1p): blank, label
    w_pick = jnp.moveaxis(w_out[:, ids], 0, -1).astype(e.dtype)
    frames = frames.astype(jnp.int32)
    blank, label = _joint(e, g, w_out, bias, w_pick, bias[ids], frames, tq, tv, interpret)
    return blank[:, :T, :U1], label[:, :T, :U1]
