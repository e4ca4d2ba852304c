"""Encoder-decoder transformer (Whisper-style) — [audio] backbone.

With ``n_mels > 0`` the encoder takes log-mel frames (B, 2 *
max_source, n_mels) through Whisper's front end: two width-3
convolutions with GELU, the second of stride 2, then sinusoidal
positions. With ``n_mels == 0`` it takes precomputed frame embeddings
(B, max_source, d_model) in their place. Then: bidirectional encoder,
causal decoder with cross-attention, teacher-forced CE loss, and a
cached decode step (self-attn KV cache + precomputed cross KV).

The stages run under ``jax.named_scope``s (``encoder`` with
``frontend`` and each layer's ``enc_attention`` inside it, ``decoder``,
``lm_loss``), which the benchmark reads from the compiled instructions.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.attention import (
    AttnConfig,
    attn_init,
    blockwise_attention,
    decode_attention,
    _project_qkv,
)
from repro.models.layers import (
    embed_init,
    layer_norm,
    lm_loss,
    mlp_apply,
    mlp_init,
    sinusoidal_positions,
    stacked,
)


@dataclasses.dataclass(frozen=True)
class EncDecConfig:
    name: str
    enc_layers: int
    dec_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    max_source: int = 1500
    max_target: int = 448
    act: str = "gelu"
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    loss_chunk: int = 64
    n_mels: int = 0         # 0: the encoder takes d_model-wide frame embeddings

    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def attn_cfg(self, causal: bool) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model, n_heads=self.n_heads, n_kv=self.n_kv,
            head_dim=self.head_dim, rope_theta=0.0, causal=causal,
        )


def _ln_init(d, dt):
    return jnp.ones((d,), dt), jnp.zeros((d,), dt)


def _enc_layer_init(key, cfg: EncDecConfig):
    ka, km = jax.random.split(key)
    dt = cfg.pdtype
    s1, b1 = _ln_init(cfg.d_model, dt)
    s2, b2 = _ln_init(cfg.d_model, dt)
    return {
        "norm1": s1, "norm1_b": b1, "norm2": s2, "norm2_b": b2,
        "attn": attn_init(ka, cfg.attn_cfg(False), dt),
        "mlp": mlp_init(km, cfg.d_model, cfg.d_ff, gated=False, dtype=dt),
    }


def _dec_layer_init(key, cfg: EncDecConfig):
    ka, kx, km = jax.random.split(key, 3)
    dt = cfg.pdtype
    s1, b1 = _ln_init(cfg.d_model, dt)
    s2, b2 = _ln_init(cfg.d_model, dt)
    s3, b3 = _ln_init(cfg.d_model, dt)
    return {
        "norm1": s1, "norm1_b": b1, "norm2": s2, "norm2_b": b2,
        "norm3": s3, "norm3_b": b3,
        "self_attn": attn_init(ka, cfg.attn_cfg(True), dt),
        "cross_attn": attn_init(kx, cfg.attn_cfg(False), dt),
        "mlp": mlp_init(km, cfg.d_model, cfg.d_ff, gated=False, dtype=dt),
    }


def _frontend_init(key, cfg: EncDecConfig):
    k1, k2 = jax.random.split(key)
    D, dt = cfg.d_model, cfg.pdtype

    def conv(k, c_in):  # (width, in, out), fan-in scaled
        return (jax.random.normal(k, (3, c_in, D)) * (3 * c_in) ** -0.5).astype(dt)

    return {"conv1_w": conv(k1, cfg.n_mels), "conv1_b": jnp.zeros((D,), dt),
            "conv2_w": conv(k2, D), "conv2_b": jnp.zeros((D,), dt)}


def init_params(cfg: EncDecConfig, key) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    dt = cfg.pdtype
    fs, fb = _ln_init(cfg.d_model, dt)
    es, eb = _ln_init(cfg.d_model, dt)
    params = {
        "enc_layers": stacked(_enc_layer_init, k1, cfg.enc_layers, cfg),
        "enc_norm": es, "enc_norm_b": eb,
        "tok_embed": embed_init(k2, cfg.vocab, cfg.d_model, dt),
        "pos_embed": (jax.random.normal(k3, (cfg.max_target, cfg.d_model)) * 0.01).astype(dt),
        "dec_layers": stacked(_dec_layer_init, k4, cfg.dec_layers, cfg),
        "final_norm": fs, "final_norm_b": fb,
    }
    if cfg.n_mels:
        params["frontend"] = _frontend_init(jax.random.fold_in(key, 5), cfg)
    return params


def _conv(x, w, b, stride: int):
    """Width-3 convolution over time with padding 1: x (B, T, C_in),
    w (3, C_in, C_out) -> (B, T // stride, C_out), then exact GELU."""
    y = jax.lax.conv_general_dilated(x, w.astype(x.dtype), (stride,), [(1, 1)],
                                     dimension_numbers=("NWC", "WIO", "NWC"))
    return jax.nn.gelu(y + b.astype(x.dtype), approximate=False)


def frontend(cfg: EncDecConfig, params, mels):
    """Whisper's front end: mels (B, 2 * S, n_mels) -> (B, S, d_model)."""
    p = params["frontend"]
    x = _conv(mels.astype(cfg.cdtype), p["conv1_w"], p["conv1_b"], 1)
    return _conv(x, p["conv2_w"], p["conv2_b"], 2)


def _flash_path() -> bool:
    """The encoder's self-attention runs the fused flash kernel off the
    CPU; on the CPU, ``blockwise_attention``."""
    return jax.default_backend() != "cpu"


def _self_attention(q, k, v):
    if _flash_path():
        return ops.attention(q, k, v)
    return blockwise_attention(q, k, v, causal=False, block_kv=min(512, q.shape[1]))


def encode(cfg: EncDecConfig, params, frames):
    """frames: (B, 2 * S, n_mels) log-mel frames, or with ``n_mels == 0``
    (B, S, d_model) embeddings -> (B, S, d_model)."""
    with jax.named_scope("encoder"):
        if cfg.n_mels:
            with jax.named_scope("frontend"):
                x = frontend(cfg, params, frames)
        else:
            x = frames.astype(cfg.cdtype)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)[None]
        acfg = cfg.attn_cfg(False)

        @jax.checkpoint
        def body(xc, lp):
            h = layer_norm(xc, lp["norm1"], lp["norm1_b"])
            q, k, v = _project_qkv(lp["attn"], acfg, h, jnp.zeros(xc.shape[:2], jnp.int32))
            with jax.named_scope("enc_attention"):
                o = _self_attention(q, k, v)
            xc = xc + o.reshape(xc.shape[0], xc.shape[1], -1) @ lp["attn"]["wo"].astype(xc.dtype)
            h2 = layer_norm(xc, lp["norm2"], lp["norm2_b"])
            xc = xc + mlp_apply(lp["mlp"], h2, cfg.act)
            return xc, None

        x, _ = jax.lax.scan(body, x, params["enc_layers"])
        return layer_norm(x, params["enc_norm"], params["enc_norm_b"])


def _cross_kv(lp, acfg, enc_out):
    """Precompute cross-attention K, V from encoder output."""
    B, T, _ = enc_out.shape
    k = (enc_out @ lp["cross_attn"]["wk"].astype(enc_out.dtype)).reshape(B, T, acfg.n_kv, acfg.head_dim)
    v = (enc_out @ lp["cross_attn"]["wv"].astype(enc_out.dtype)).reshape(B, T, acfg.n_kv, acfg.head_dim)
    return k, v


def _dec_layer(cfg, lp, x, enc_out, pos_q):
    acfg = cfg.attn_cfg(True)
    xcfg = cfg.attn_cfg(False)
    h = layer_norm(x, lp["norm1"], lp["norm1_b"])
    q, k, v = _project_qkv(lp["self_attn"], acfg, h, pos_q)
    o = blockwise_attention(q, k, v, causal=True, block_kv=min(512, x.shape[1]))
    x = x + o.reshape(*x.shape[:2], -1) @ lp["self_attn"]["wo"].astype(x.dtype)
    h2 = layer_norm(x, lp["norm2"], lp["norm2_b"])
    q2, _, _ = _project_qkv(lp["cross_attn"], xcfg, h2, jnp.zeros_like(pos_q))
    ck, cv = _cross_kv(lp, xcfg, enc_out)
    o2 = blockwise_attention(q2, ck, cv, causal=False, block_kv=min(512, enc_out.shape[1]))
    x = x + o2.reshape(*x.shape[:2], -1) @ lp["cross_attn"]["wo"].astype(x.dtype)
    h3 = layer_norm(x, lp["norm3"], lp["norm3_b"])
    x = x + mlp_apply(lp["mlp"], h3, cfg.act)
    return x, (k, v)


def decode_train(cfg: EncDecConfig, params, tokens, enc_out):
    """Teacher-forced decoder. tokens: (B, U)."""
    B, U = tokens.shape
    x = params["tok_embed"].astype(cfg.cdtype)[tokens]
    pe = params["pos_embed"].astype(x.dtype)
    x = x + pe[jnp.arange(U) % pe.shape[0]][None]   # wraps past max_target
    pos_q = jnp.broadcast_to(jnp.arange(U), (B, U))

    @jax.checkpoint
    def body(xc, lp):
        xo, _ = _dec_layer(cfg, lp, xc, enc_out, pos_q)
        return xo, None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    return layer_norm(x, params["final_norm"], params["final_norm_b"])


def loss_fn(cfg: EncDecConfig, params, batch, rng=None):
    """batch: frames (as ``encode`` takes them), tokens (B, U)."""
    enc_out = encode(cfg, params, batch["frames"])
    with jax.named_scope("decoder"):
        h = decode_train(cfg, params, batch["tokens"], enc_out)
    with jax.named_scope("lm_loss"):
        loss = lm_loss(h, params["tok_embed"].astype(cfg.cdtype).T, batch["tokens"],
                       chunk=min(cfg.loss_chunk, h.shape[1]),
                       weight=batch.get("weight"))
    return loss, {"lm_loss": loss}


# -------------------------------------------------------------- serving

def init_cache(cfg: EncDecConfig, batch: int, seq_len: int):
    dt = cfg.cdtype
    L, Kv, D = cfg.dec_layers, cfg.n_kv, cfg.head_dim
    T = cfg.max_source
    return {
        "self_k": jnp.zeros((L, batch, seq_len, Kv, D), dt),
        "self_v": jnp.zeros((L, batch, seq_len, Kv, D), dt),
        "cross_k": jnp.zeros((L, batch, T, Kv, D), dt),
        "cross_v": jnp.zeros((L, batch, T, Kv, D), dt),
    }


def prefill(cfg: EncDecConfig, params, frames, tokens):
    """Encode source + teacher-forced pass over a token prefix, building
    the decode cache. Returns (last logits, cache)."""
    enc_out = encode(cfg, params, frames)
    B, U = tokens.shape
    x = params["tok_embed"].astype(cfg.cdtype)[tokens]
    pe = params["pos_embed"].astype(x.dtype)
    pos = jnp.arange(U) % pe.shape[0]
    x = x + pe[pos][None]
    pos_q = jnp.broadcast_to(jnp.arange(U), (B, U))
    xcfg = cfg.attn_cfg(False)

    def body(xc, lp):
        xo, kv = _dec_layer(cfg, lp, xc, enc_out, pos_q)
        ck, cv = _cross_kv(lp, xcfg, enc_out)
        return xo, (kv[0], kv[1], ck, cv)

    x, (sk, sv, ck, cv) = jax.lax.scan(body, x, params["dec_layers"])
    x = layer_norm(x, params["final_norm"], params["final_norm_b"])
    logits = (x[:, -1] @ params["tok_embed"].astype(cfg.cdtype).T).astype(jnp.float32)
    cache = {"self_k": sk, "self_v": sv, "cross_k": ck, "cross_v": cv}
    return logits, cache


def decode_step(cfg: EncDecConfig, params, cache, tokens, pos):
    """One decoder token against the cache. tokens: (B, 1); pos scalar."""
    B = tokens.shape[0]
    x = params["tok_embed"].astype(cfg.cdtype)[tokens]
    pe = params["pos_embed"].astype(x.dtype)
    x = x + pe[pos % pe.shape[0]][None, None]
    acfg = cfg.attn_cfg(True)
    xcfg = cfg.attn_cfg(False)

    def body(xc, inp):
        lp, sk, sv, ck, cv = inp
        h = layer_norm(xc, lp["norm1"], lp["norm1_b"])
        q, k, v = _project_qkv(lp["self_attn"], acfg, h,
                               jnp.broadcast_to(pos[None], (B, 1)) if pos.ndim == 0 else pos)
        sk = jax.lax.dynamic_update_slice(sk, k.astype(sk.dtype), (0, pos, 0, 0))
        sv = jax.lax.dynamic_update_slice(sv, v.astype(sv.dtype), (0, pos, 0, 0))
        o = decode_attention(q[:, 0], sk, sv, pos)
        xc = xc + o.reshape(B, 1, -1) @ lp["self_attn"]["wo"].astype(xc.dtype)
        h2 = layer_norm(xc, lp["norm2"], lp["norm2_b"])
        q2, _, _ = _project_qkv(lp["cross_attn"], xcfg, h2, jnp.zeros((B, 1), jnp.int32))
        T = ck.shape[1]
        o2 = decode_attention(q2[:, 0], ck, cv, jnp.asarray(T - 1, jnp.int32))
        xc = xc + o2.reshape(B, 1, -1) @ lp["cross_attn"]["wo"].astype(xc.dtype)
        h3 = layer_norm(xc, lp["norm3"], lp["norm3_b"])
        xc = xc + mlp_apply(lp["mlp"], h3, cfg.act)
        return xc, (sk, sv)

    x, (sk, sv) = jax.lax.scan(
        body, x,
        (params["dec_layers"], cache["self_k"], cache["self_v"],
         cache["cross_k"], cache["cross_v"]),
    )
    x = layer_norm(x, params["final_norm"], params["final_norm_b"])
    logits = (x[:, 0] @ params["tok_embed"].astype(cfg.cdtype).T).astype(jnp.float32)
    new_cache = dict(cache, self_k=sk, self_v=sv)
    return logits, new_cache
