"""Plain float32 Whisper (arXiv 2212.04356) with its log-mel front end,
written from its description for the benchmark's correctness check.
Imports nothing of the program under test.

It is ``reference/encdec.py``'s model (its attention, MLP and
sinusoids; its decoder and loss repeated here) with the published front
end before the encoder:

- input: log-mel frames (B, 2 * max_source, n_mels);
- ``conv1``: width 3, n_mels -> d_model, padding 1, plus bias, GELU;
- ``conv2``: width 3, d_model -> d_model, stride 2, padding 1, plus bias,
  GELU, giving max_source positions;
- then sinusoidal positions and the encoder's blocks.

Parameters are ``reference/encdec.py``'s layout plus
``frontend {conv1_w (3, n_mels, D), conv1_b (D,), conv2_w (3, D, D),
conv2_b (D,)}``. Each convolution is three shifted matmuls, so the
control's lower precision reaches it as it reaches every other matmul.

The encoder's self-attention is computed ``Q_BLOCK`` queries at a time,
each block a plain softmax over all keys and recomputed in the
backward pass: the whole score tensor of a round's clients would not
fit the chip beside its gradients.

Departures from the published model, shared with the program:

- no biases on the attention and MLP projections;
- the blocks' MLP uses tanh-approximated GELU (the front end's GELU is
  the exact one, as published);
- sinusoid timescales 10000^(2i/d), where Whisper spaces them
  log-evenly from 1 to 10000 over d/2 - 1 steps;
- decoder positions past the learned table wrap around;
- the loss counts a next-token target at every position but the last,
  the padding after each transcript included.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import encdec
from chipbench.reference.common import KeyStream, layer_norm, matmul, normal

Q_BLOCK = 500  # encoder queries a block: a third of Whisper's 1500 positions


def init(conf: dict, key) -> dict:
    params = encdec.init(conf, key)
    ks = KeyStream(jax.random.fold_in(key, 1 << 20))
    dt = jnp.dtype(conf["param_dtype"])
    D, M = conf["d_model"], conf["n_mels"]
    params["frontend"] = {
        "conv1_w": normal(ks, (3, M, D), (3 * M) ** -0.5, dt),
        "conv1_b": jnp.zeros((D,), dt),
        "conv2_w": normal(ks, (3, D, D), (3 * D) ** -0.5, dt),
        "conv2_b": jnp.zeros((D,), dt),
    }
    return params


def conv(x, w, b, stride: int, low=None):
    """out[t] = GELU(b + sum_j x[stride * t + j - 1] @ w[j]), x zero-padded
    by one frame on each side: (B, T, C_in) -> (B, T // stride, C_out)."""
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (1, 1), (0, 0)))
    y = sum(matmul(xp[:, j:j + T:stride], w[j], low) for j in range(3))
    return jax.nn.gelu(y + b.astype(jnp.float32), approximate=False)


def encoder_attention(conf, p, x, low=None):
    """Full self-attention of x (B, S, D), ``Q_BLOCK`` queries at a time."""
    S = x.shape[1]
    if S <= Q_BLOCK or S % Q_BLOCK:
        return encdec.attention(conf, p, x, x, causal=False, low=low)
    blocks = x.reshape(x.shape[0], S // Q_BLOCK, Q_BLOCK, x.shape[2]).swapaxes(0, 1)
    out = jax.lax.map(jax.checkpoint(
        lambda xq: encdec.attention(conf, p, xq, x, causal=False, low=low)), blocks)
    return out.swapaxes(0, 1).reshape(x.shape)


def loss(conf: dict, params, batch, key=None, low=None):
    """batch: features (B, 2 * max_source, n_mels) log-mel frames, labels
    (B, U) tokens, weight (B,). ``key`` is unused: the model draws nothing
    at random."""
    del key
    f = params["frontend"]
    x = conv(batch["features"].astype(jnp.float32), f["conv1_w"], f["conv1_b"], 1, low)
    x = conv(x, f["conv2_w"], f["conv2_b"], 2, low)
    x = x + encdec.sinusoids(x.shape[1], conf["d_model"])[None]

    @jax.checkpoint
    def enc_block(x, lp):
        h = layer_norm(x, lp["norm1"], lp["norm1_b"])
        x = x + encoder_attention(conf, lp["attn"], h, low)
        x = x + encdec.mlp(lp["mlp"], layer_norm(x, lp["norm2"], lp["norm2_b"]), low)
        return x, None

    x, _ = jax.lax.scan(enc_block, x, params["enc_layers"])
    enc = layer_norm(x, params["enc_norm"], params["enc_norm_b"])

    tokens = batch["labels"]
    B, U = tokens.shape
    emb = params["tok_embed"].astype(jnp.float32)
    pe = params["pos_embed"].astype(jnp.float32)
    y = emb[tokens] + pe[jnp.arange(U) % pe.shape[0]][None]

    @jax.checkpoint
    def dec_block(y, lp):
        h = layer_norm(y, lp["norm1"], lp["norm1_b"])
        y = y + encdec.attention(conf, lp["self_attn"], h, h, causal=True, low=low)
        h = layer_norm(y, lp["norm2"], lp["norm2_b"])
        y = y + encdec.attention(conf, lp["cross_attn"], h, enc, causal=False, low=low)
        y = y + encdec.mlp(lp["mlp"], layer_norm(y, lp["norm3"], lp["norm3_b"]), low)
        return y, None

    y, _ = jax.lax.scan(dec_block, y, params["dec_layers"])
    y = layer_norm(y, params["final_norm"], params["final_norm_b"])
    logits = matmul(y, emb.T, low)                               # (B, U, V)
    target = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, target[..., None], axis=-1)[..., 0]
    mask = (jnp.arange(U) < U - 1).astype(jnp.float32)[None, :] * batch["weight"][:, None]
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
