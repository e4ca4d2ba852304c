"""RNN-T — the paper's model (Fig. 1): LSTM audio encoder, LSTM label
encoder (prediction network), joint network, softmax over word-pieces.

The joint is the memory hot-spot: naive evaluation materializes
(B, T, U+1, V) logits (V=4096 in the paper). The training path
computes only the (blank, label) log-probs the transducer DP needs:
off the CPU the fused Pallas kernel (repro/kernels/rnnt_joint.py), on
the CPU the U-chunked jnp path here, which is also its oracle.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.asr.rnnt_loss import rnnt_loss_from_logprobs
from repro.asr.specaugment import SpecAugmentConfig, spec_augment
from repro.kernels import ops
from repro.models.layers import dense_init, embed_init
from repro.models.lstm import LSTMConfig, lstm_stack, lstm_stack_init, lstm_stack_init_state, lstm_stack_step


@dataclasses.dataclass(frozen=True)
class RNNTConfig:
    name: str = "rnnt"
    feat_dim: int = 128
    vocab: int = 4096              # word-pieces; id 0 = blank
    enc_layers: int = 8
    enc_hidden: int = 1152
    pred_layers: int = 2
    pred_hidden: int = 1152
    pred_embed: int = 512
    joint_dim: int = 640
    time_stride: int = 1           # frame subsampling before the encoder
    specaug: SpecAugmentConfig = dataclasses.field(default_factory=SpecAugmentConfig)
    dtype: str = "float32"
    param_dtype: str = "float32"
    use_kernel: bool = False       # chooses nothing: the joint is chosen by _joint_kernel_path
    loss_norm: bool = True         # per-label-token NLL normalization
    scan_unroll: int = 1           # LSTM scan unroll (weight amortization)
    scan_chunk: int = 0            # time-chunked remat scan (grad-buffer traffic)

    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)


def init_params(cfg: RNNTConfig, key) -> dict:
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    dt = cfg.pdtype
    enc_in = cfg.feat_dim * cfg.time_stride
    return {
        "encoder": lstm_stack_init(k1, LSTMConfig(enc_in, cfg.enc_hidden, cfg.enc_layers), dt),
        "pred_embed": embed_init(k2, cfg.vocab, cfg.pred_embed, dt),
        "predictor": lstm_stack_init(k3, LSTMConfig(cfg.pred_embed, cfg.pred_hidden, cfg.pred_layers), dt),
        "joint_enc": dense_init(k4, cfg.enc_hidden, cfg.joint_dim, dt),
        "joint_pred": dense_init(k5, cfg.pred_hidden, cfg.joint_dim, dt),
        "joint_out": dense_init(k6, cfg.joint_dim, cfg.vocab, dt),
        "joint_bias": jnp.zeros((cfg.vocab,), dt),
    }


def encode(cfg: RNNTConfig, params, features):
    """features: (B, T, F) -> (B, T', enc_hidden)."""
    x = features.astype(cfg.cdtype)
    if cfg.time_stride > 1:
        B, T, F = x.shape
        T2 = T // cfg.time_stride
        x = x[:, : T2 * cfg.time_stride].reshape(B, T2, F * cfg.time_stride)
    out, _ = lstm_stack(params["encoder"], x, unroll=cfg.scan_unroll, chunk=cfg.scan_chunk)
    return out


def predict(cfg: RNNTConfig, params, labels):
    """labels: (B, U) -> (B, U+1, pred_hidden); position 0 is the
    blank-start state (zero embedding)."""
    B, U = labels.shape
    emb = params["pred_embed"].astype(cfg.cdtype)[labels]       # (B, U, E)
    emb = jnp.concatenate([jnp.zeros_like(emb[:, :1]), emb], axis=1)
    out, _ = lstm_stack(params["predictor"], emb, unroll=cfg.scan_unroll, chunk=cfg.scan_chunk)
    return out


def _joint_inputs(params, enc, pred, labels):
    """The joint's projections e (B, T, J), g (B, U1, J) in the compute
    dtype, and the label id of each of the U1 slots (the last is 0)."""
    e = enc @ params["joint_enc"].astype(enc.dtype)
    g = pred @ params["joint_pred"].astype(pred.dtype)
    lbl = jnp.concatenate([labels, jnp.zeros((labels.shape[0], 1), labels.dtype)], axis=1)
    return e, g, lbl


def joint_logprobs_ref(cfg: RNNTConfig, params, enc, pred, labels, u_chunk: int = 8):
    """(blank_lp, label_lp): (B, T, U1) each, never materializing
    (B, T, U1, V): the joint's projections, then ``joint_chunked``."""
    e, g, lbl = _joint_inputs(params, enc, pred, labels)
    return joint_chunked(e, g, params["joint_out"].astype(enc.dtype),
                         params["joint_bias"].astype(jnp.float32), lbl, u_chunk)


def joint_chunked(e, g, w, b, lbl, u_chunk: int = 8):
    """The joint's (blank, label) log-probs from its projections e (B, T, J)
    and g (B, U1, J), w (J, V), b (V,) and the label ids (B, U1): a scan
    over U1 in chunks, each chunk's logits rebuilt in the backward (the
    CPU path, and the jnp oracle of the kernel)."""
    B, T, _ = e.shape
    U1 = g.shape[1]
    n_chunks = max(1, U1 // u_chunk)
    pad = (-U1) % n_chunks
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
        lbl = jnp.pad(lbl, ((0, 0), (0, pad)))
    c = g.shape[1] // n_chunks
    gc = g.reshape(B, n_chunks, c, -1).swapaxes(0, 1)
    lc = lbl.reshape(B, n_chunks, c).swapaxes(0, 1)

    def body(_, inp):
        g_i, l_i = inp
        h = jnp.tanh(e[:, :, None, :] + g_i[:, None, :, :])    # (B, T, c, J)
        logits = (h @ w).astype(jnp.float32) + b               # (B, T, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        blank = logits[..., 0] - lse
        lab = jnp.take_along_axis(
            logits, l_i[:, None, :, None].astype(jnp.int32), axis=-1
        )[..., 0] - lse
        return None, (blank, lab)

    _, (blanks, labs) = jax.lax.scan(jax.checkpoint(body), None, (gc, lc))
    # (n_chunks, B, T, c) -> (B, T, n_chunks*c): the chunk axis lands
    # just outside the within-chunk axis before flattening
    blank_lp = jnp.moveaxis(blanks, 0, 2).reshape(B, T, -1)[:, :, :U1]
    label_lp = jnp.moveaxis(labs, 0, 2).reshape(B, T, -1)[:, :, :U1]
    return blank_lp, label_lp


def joint_logits(cfg: RNNTConfig, params, enc_t, pred_u):
    """Pointwise joint for decoding. enc_t: (B, H); pred_u: (B, H) ->
    (B, V) logits."""
    e = enc_t @ params["joint_enc"].astype(enc_t.dtype)
    g = pred_u @ params["joint_pred"].astype(pred_u.dtype)
    h = jnp.tanh(e + g)
    return (h @ params["joint_out"].astype(h.dtype)).astype(jnp.float32) + \
        params["joint_bias"].astype(jnp.float32)


def _joint_kernel_path() -> bool:
    """The joint runs the fused Pallas kernel off the CPU; on the CPU,
    ``joint_logprobs_ref``."""
    return jax.default_backend() != "cpu"


def loss_fn(cfg: RNNTConfig, params, batch, rng=None):
    """batch: features (B,T,F), labels (B,U), frame_len (B,), label_len (B,),
    optional weight (B,). Returns (mean loss, aux).

    Each layer runs under its own ``jax.named_scope`` (``specaugment``,
    ``encoder``, ``prednet``, ``joint``, ``rnnt_loss``): the names reach
    every compiled instruction's ``op_name``, so a device trace splits
    the step's time by layer."""
    feats = batch["features"]
    if rng is not None and cfg.specaug.enabled:
        with jax.named_scope("specaugment"):
            feats = spec_augment(rng, feats, cfg.specaug)
    with jax.named_scope("encoder"):
        enc = encode(cfg, params, feats)
    with jax.named_scope("prednet"):
        pred = predict(cfg, params, batch["labels"])
    frame_len = jnp.maximum(batch["frame_len"] // cfg.time_stride, 1)
    with jax.named_scope("joint"):
        if _joint_kernel_path():
            e, g, lbl = _joint_inputs(params, enc, pred, batch["labels"])
            blank_lp, label_lp = ops.rnnt_joint(e, g, params["joint_out"], params["joint_bias"],
                                                lbl, frame_len)
        else:
            blank_lp, label_lp = joint_logprobs_ref(cfg, params, enc, pred, batch["labels"])
    with jax.named_scope("rnnt_loss"):
        nll = rnnt_loss_from_logprobs(blank_lp, label_lp, frame_len, batch["label_len"])
        if cfg.loss_norm:
            nll = nll / jnp.maximum(batch["label_len"].astype(jnp.float32), 1.0)
        w = batch.get("weight", jnp.ones_like(nll))
        denom = jnp.maximum(w.sum(), 1.0)
        loss = (nll * w).sum() / denom
    return loss, {"nll": nll}


def greedy_decode(cfg: RNNTConfig, params, features, frame_len, max_symbols: int = 4):
    """Greedy transducer decode. Returns (B, T*max_symbols) padded token ids
    (0 = blank/pad). Small-scale (eval on the synthetic corpus)."""
    enc = encode(cfg, params, features)                 # (B, T, H)
    B, T, _ = enc.shape
    pcfg = LSTMConfig(cfg.pred_embed, cfg.pred_hidden, cfg.pred_layers)
    state0 = lstm_stack_init_state(pcfg, B, cfg.cdtype)
    # initial predictor output from the zero (start) embedding
    zero_emb = jnp.zeros((B, cfg.pred_embed), cfg.cdtype)
    g0, state0 = lstm_stack_step(params["predictor"], zero_emb, state0)

    def frame_body(carry, t):
        g, state, out, n_out = carry

        def symbol_body(c, _):
            g, state, out, n_out, done = c
            logits = joint_logits(cfg, params, enc[:, t], g)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)       # (B,)
            emit = (tok != 0) & ~done
            emb = params["pred_embed"].astype(cfg.cdtype)[tok]
            g_new, state_new = lstm_stack_step(params["predictor"], emb, state)
            g = jnp.where(emit[:, None], g_new, g)
            state = jax.tree.map(
                lambda new, old: jnp.where(emit.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
                state_new, state)
            out = out.at[jnp.arange(B), n_out].set(jnp.where(emit, tok, out[jnp.arange(B), n_out]))
            n_out = n_out + emit.astype(jnp.int32)
            done = done | ~emit
            return (g, state, out, n_out, done), None

        mask_t = (t < frame_len)
        (g2, state2, out2, n_out2, _), _ = jax.lax.scan(
            symbol_body, (g, state, out, n_out, jnp.zeros((B,), bool)),
            jnp.arange(max_symbols))
        g = jnp.where(mask_t[:, None], g2, g)
        state = jax.tree.map(
            lambda new, old: jnp.where(mask_t.reshape((-1,) + (1,) * (new.ndim - 1)), new, old),
            state2, state)
        out = jnp.where(mask_t[:, None], out2, out)
        n_out = jnp.where(mask_t, n_out2, n_out)
        return (g, state, out, n_out), None

    out0 = jnp.zeros((B, T * max_symbols), jnp.int32)
    (g, state, out, n_out), _ = jax.lax.scan(
        frame_body, (g0, state0, out0, jnp.zeros((B,), jnp.int32)), jnp.arange(T))
    return out
