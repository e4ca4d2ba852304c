"""Unified model interface: build_model(config) -> ModelBundle.

A ModelBundle binds a config to the pure functions the federated
engine, launcher, and dry-run consume. Dispatch is on config dataclass
type; every assigned architecture's config file constructs one of the
four config families (TransformerConfig / HybridConfig / RWKV stack /
EncDecConfig / VLMConfig / RNNTConfig).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, hybrid, keyword, rnnt, transformer, vlm
from repro.models.layers import dense_init, embed_init, lm_loss, stacked
from repro.models.rwkv import (
    RWKVConfig,
    rwkv_init_state,
    rwkv_layer_forward,
    rwkv_layer_init,
)


@dataclasses.dataclass(frozen=True)
class RWKVModelConfig:
    name: str
    n_layers: int
    rwkv: RWKVConfig
    vocab: int
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    loss_chunk: int = 256

    @property
    def cdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)


@dataclasses.dataclass
class ModelBundle:
    name: str
    kind: str                    # dense | moe | hybrid | ssm | audio | vlm | rnnt | keyword
    config: Any
    init: Callable               # (key) -> params
    loss_fn: Callable            # (params, batch, rng) -> (loss, aux)
    prefill: Optional[Callable] = None      # (params, batch) -> (logits, cache)
    decode_step: Optional[Callable] = None  # (params, cache, tokens, pos) -> (logits, cache)
    init_cache: Optional[Callable] = None   # (batch, seq_len, ring=False) -> cache

    def param_count(self, params) -> int:
        # shapes only: counts concrete params and eval_shape'd ones alike
        return sum(math.prod(x.shape) for x in jax.tree.leaves(params))


# ------------------------------------------------------------- rwkv model

def _rwkv_init(cfg: RWKVModelConfig, key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "embed": embed_init(k1, cfg.vocab, cfg.rwkv.d_model, cfg.pdtype),
        "layers": stacked(rwkv_layer_init, k2, cfg.n_layers, cfg.rwkv, cfg.pdtype),
        "final_norm": jnp.ones((cfg.rwkv.d_model,), cfg.pdtype),
        "final_norm_b": jnp.zeros((cfg.rwkv.d_model,), cfg.pdtype),
        "unembed": dense_init(k3, cfg.rwkv.d_model, cfg.vocab, cfg.pdtype),
    }


def _rwkv_forward(cfg: RWKVModelConfig, params, tokens, states=None):
    from repro.models.rwkv import _ln

    x = params["embed"].astype(cfg.cdtype)[tokens]

    def body(xc, inp):
        if states is None:
            lp = inp
            xo, _ = rwkv_layer_forward(lp, cfg.rwkv, xc, None)
            return xo, None
        lp, st = inp
        xo, st2 = rwkv_layer_forward(lp, cfg.rwkv, xc, st)
        return xo, st2

    if states is None:
        body = jax.checkpoint(body)

    xs = params["layers"] if states is None else (params["layers"], states)
    x, new_states = jax.lax.scan(body, x, xs)
    x = _ln(x, params["final_norm"], params["final_norm_b"])
    return x, new_states


def _rwkv_loss(cfg: RWKVModelConfig, params, batch, rng=None):
    h, _ = _rwkv_forward(cfg, params, batch["tokens"])
    loss = lm_loss(h, params["unembed"].astype(cfg.cdtype), batch["tokens"],
                   chunk=min(cfg.loss_chunk, batch["tokens"].shape[1]),
                   weight=batch.get("weight"))
    return loss, {"lm_loss": loss}


def _rwkv_init_cache(cfg: RWKVModelConfig, batch: int, seq_len: int, ring: bool = False):
    one = rwkv_init_state(cfg.rwkv, batch, cfg.cdtype)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a, (cfg.n_layers,) + a.shape).copy(), one)


def _rwkv_decode(cfg: RWKVModelConfig, params, cache, tokens, pos, ring: bool = False):
    h, states = _rwkv_forward(cfg, params, tokens, states=cache)
    logits = (h[:, 0] @ params["unembed"].astype(cfg.cdtype)).astype(jnp.float32)
    return logits, states


def _rwkv_prefill(cfg: RWKVModelConfig, params, batch):
    tokens = batch["tokens"]
    cache = _rwkv_init_cache(cfg, tokens.shape[0], 0)
    h, states = _rwkv_forward(cfg, params, tokens, states=cache)
    logits = (h[:, -1] @ params["unembed"].astype(cfg.cdtype)).astype(jnp.float32)
    return logits, states


# ------------------------------------------------------------- dispatch

def build_model(cfg, kind: Optional[str] = None) -> ModelBundle:
    if isinstance(cfg, transformer.TransformerConfig):
        kind = kind or ("moe" if cfg.moe is not None else "dense")
        return ModelBundle(
            name=cfg.name, kind=kind, config=cfg,
            init=partial(transformer.init_params, cfg),
            loss_fn=partial(transformer.loss_fn, cfg),
            prefill=lambda params, batch: transformer.prefill(cfg, params, batch["tokens"]),
            decode_step=partial(transformer.decode_step, cfg),
            init_cache=partial(transformer.init_cache, cfg),
        )
    if isinstance(cfg, hybrid.HybridConfig):
        return ModelBundle(
            name=cfg.name, kind="hybrid", config=cfg,
            init=partial(hybrid.init_params, cfg),
            loss_fn=partial(hybrid.loss_fn, cfg),
            prefill=None,   # hybrid serving enters via decode (SSM prefill = scan)
            decode_step=partial(hybrid.decode_step, cfg),
            init_cache=lambda batch, seq_len, ring=False: hybrid.init_cache(cfg, batch, seq_len),
        )
    if isinstance(cfg, RWKVModelConfig):
        return ModelBundle(
            name=cfg.name, kind="ssm", config=cfg,
            init=partial(_rwkv_init, cfg),
            loss_fn=partial(_rwkv_loss, cfg),
            prefill=partial(_rwkv_prefill, cfg),
            decode_step=partial(_rwkv_decode, cfg),
            init_cache=partial(_rwkv_init_cache, cfg),
        )
    if isinstance(cfg, encdec.EncDecConfig):
        return ModelBundle(
            name=cfg.name, kind="audio", config=cfg,
            init=partial(encdec.init_params, cfg),
            loss_fn=partial(encdec.loss_fn, cfg),
            prefill=lambda params, batch: encdec.prefill(cfg, params, batch["frames"], batch["tokens"]),
            decode_step=partial(encdec.decode_step, cfg),
            init_cache=lambda batch, seq_len, ring=False: encdec.init_cache(cfg, batch, seq_len),
        )
    if isinstance(cfg, vlm.VLMConfig):
        return ModelBundle(
            name=cfg.name, kind="vlm", config=cfg,
            init=partial(vlm.init_params, cfg),
            loss_fn=partial(vlm.loss_fn, cfg),
            prefill=partial(vlm.prefill, cfg),
            decode_step=partial(vlm.decode_step, cfg),
            init_cache=partial(vlm.init_cache, cfg),
        )
    if isinstance(cfg, rnnt.RNNTConfig):
        return ModelBundle(
            name=cfg.name, kind="rnnt", config=cfg,
            init=partial(rnnt.init_params, cfg),
            loss_fn=partial(rnnt.loss_fn, cfg),
        )
    if isinstance(cfg, keyword.KeywordConfig):
        return ModelBundle(
            name=cfg.name, kind="keyword", config=cfg,
            init=partial(keyword.init_params, cfg),
            loss_fn=partial(keyword.loss_fn, cfg),
        )
    raise TypeError(f"unknown config type {type(cfg)}")
