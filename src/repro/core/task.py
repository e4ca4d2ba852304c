"""FederatedTask — the engine's task-level entry point.

``build_round_engine`` used to take a bare ``loss_fn``, which left
everything above the loss — eval/decode, the task's quality metric,
the batch layout — hardcoded in the drivers (``launch.train`` carried
an RNN-T ``greedy_decode``/WER path no other model could use). A
``FederatedTask`` bundles the full task contract:

- the model (a ``ModelBundle``: init / loss_fn / param_count),
- a jit-traceable ``adapt_batch`` mapping the engine's round-batch
  layout ({features, labels, frame_len, label_len, weight}) onto the
  model's batch contract (LM models read ``labels`` as ``tokens``;
  the enc-dec reads ``features`` as precomputed frames),
- ``evaluate(params, corpus, n)`` -> {"quality", "quality_hard"} in
  the task's own metric (WER for ASR, perplexity for LM/enc-dec,
  classification error for keyword spotting),
- ``client_quality(params, batch)`` -> per-client quality over a
  stacked (C, n, ...) eval batch — the per-client evaluation plane's
  quality hook (``repro.core.clienteval``).

``build_round_engine(plan, task)`` consumes a task directly (the bare
``loss_fn`` form keeps working); the task name joins the engine's
``structural_key`` so two tasks never share a jit cache entry.

Two registries map configs to tasks:

- ``task_for_config(cfg)`` dispatches on the zoo config type (any
  ``repro.configs`` smoke/full config becomes a task), and
- ``get_task(name)`` / ``available_tasks()`` name container-scale
  tasks — one per model family plus the keyword-spotting tiny model
  where a million-virtual-client round is cheap enough for CI.

Every task trains on the same speaker-split corpus: LM tasks read the
label sequences (per-speaker Dirichlet vocab skew = real non-IID text)
and the keyword task reads the first word-piece as the class label
(vocab skew = label shift), so the paper's non-IID ladder moves every
task, not just ASR.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

# Caps exp(loss) so an early-training eval can't overflow to inf and
# poison downstream pareto/fairness arithmetic.
_PPL_CLIP = 20.0


@dataclasses.dataclass(frozen=True)
class FederatedTask:
    """One federated workload: model + batch adapter + eval metric."""

    name: str
    kind: str  # the ModelBundle kind (rnnt | audio | dense | moe | ssm | keyword)
    quality_metric: str  # "wer" | "ppl" | "err" — what "quality" means
    bundle: object  # repro.models.ModelBundle
    evaluate: Callable  # (params, corpus, n) -> {"quality", "quality_hard"}
    adapt_batch: Optional[Callable] = None  # engine batch -> model batch
    client_quality: Optional[Callable] = None  # (params, (C, n, ...) batch) -> (C,)
    make_corpus: Callable = None  # (seed, **CorpusConfig overrides) -> corpus

    @functools.cached_property
    def loss_fn(self) -> Callable:
        """The engine-facing loss: the model's loss behind the batch
        adapter. Cached so every engine built from this task shares one
        function object (and one jit trace cache)."""
        base = self.bundle.loss_fn
        adapt = self.adapt_batch
        if adapt is None:
            return base

        def loss_fn(params, batch, rng=None):
            return base(params, adapt(batch), rng)

        return loss_fn


# ---------------------------------------------------------------- corpus


def default_corpus(seed: int = 0, *, vocab: int = 64, feat_dim: int = 16, **overrides):
    """The shared 48-speaker corpus. At its defaults it is the
    container-scale corpus every task trains on (bit-identical to the
    historical ``launch.train.tiny_asr_setup`` corpus); published-width
    tasks pass their config's ``vocab``/``feat_dim``. ``overrides``
    reach ``CorpusConfig`` (e.g. ``max_label_len``)."""
    from repro.data import make_speaker_corpus

    return make_speaker_corpus(
        num_speakers=48, vocab_size=vocab, feat_dim=feat_dim, mean_utterances=24.0,
        seed=seed, **overrides,
    )


def _eval_batch(ev: dict) -> dict:
    """An ``eval_split`` dict in the engine-batch layout (weight 1)."""
    return {
        "features": jnp.asarray(ev["features"]),
        "labels": jnp.asarray(ev["labels"]),
        "frame_len": jnp.asarray(ev["frame_len"]),
        "label_len": jnp.asarray(ev["label_len"]),
        "weight": jnp.ones((ev["labels"].shape[0],), jnp.float32),
    }


# ------------------------------------------------------- batch adapters


def _lm_adapt(batch: dict) -> dict:
    """LM models read the word-piece label sequence as tokens — the
    per-speaker vocab skew makes this genuinely non-IID text."""
    return {"tokens": batch["labels"], "weight": batch.get("weight")}


def _encdec_adapt(batch: dict) -> dict:
    """Enc-dec (Whisper-style): the corpus features are the encoder's
    frames, log-mel bins (``n_mels`` wide) or, with ``n_mels == 0``,
    frame embeddings (``d_model`` wide)."""
    return {
        "frames": batch["features"],
        "tokens": batch["labels"],
        "weight": batch.get("weight"),
    }


# ------------------------------------------------------ eval functions


@functools.lru_cache(maxsize=None)
def _jitted_rnnt_decode(cfg):
    """One jitted greedy_decode per config; jit's own cache then keys
    on the eval-batch shapes, so repeated sweep-point evals at the
    same (cfg, shape) reuse one compilation."""
    from repro.models.rnnt import greedy_decode

    return jax.jit(functools.partial(greedy_decode, cfg))


def _decode_wer(cfg, params, ev) -> float:
    from repro.asr.wer import wer

    n = ev["labels"].shape[0]
    hyp = _jitted_rnnt_decode(cfg)(
        params, jnp.asarray(ev["features"]), jnp.asarray(ev["frame_len"])
    )
    refs = [ev["labels"][i, : ev["label_len"][i]].tolist() for i in range(n)]
    hyps = [h[h != 0].tolist() for h in np.asarray(hyp)]
    return wer(refs, hyps)


def _wer_evaluate(cfg) -> Callable:
    """ASR eval: greedy RNN-T decode + WER on the clean and hard
    (Other-style) eval splits."""

    def evaluate(params, corpus, n: int = 64) -> dict:
        return {
            "quality": _decode_wer(cfg, params, corpus.eval_split(n)),
            "quality_hard": _decode_wer(cfg, params, corpus.eval_split(n, hard=True)),
        }

    return evaluate


def _ppl_evaluate(loss_fn) -> Callable:
    """LM/enc-dec eval: clipped perplexity of the task loss over the
    eval splits (one jitted loss per task, shape-cached by jit)."""
    jloss = jax.jit(lambda p, b: loss_fn(p, b)[0])

    def one(params, ev) -> float:
        return float(np.exp(min(float(jloss(params, _eval_batch(ev))), _PPL_CLIP)))

    def evaluate(params, corpus, n: int = 64) -> dict:
        return {
            "quality": one(params, corpus.eval_split(n)),
            "quality_hard": one(params, corpus.eval_split(n, hard=True)),
        }

    return evaluate


def _err_evaluate(cfg) -> Callable:
    """Keyword eval: classification error rate of the pooled MLP."""
    from repro.models.keyword import predict

    jpredict = jax.jit(functools.partial(predict, cfg))

    def one(params, ev) -> float:
        pred = np.asarray(
            jpredict(params, jnp.asarray(ev["features"]), jnp.asarray(ev["frame_len"]))
        )
        return float(np.mean(pred != ev["labels"][:, 0]))

    def evaluate(params, corpus, n: int = 64) -> dict:
        return {
            "quality": one(params, corpus.eval_split(n)),
            "quality_hard": one(params, corpus.eval_split(n, hard=True)),
        }

    return evaluate


# -------------------------------------------- per-client quality hooks


def _ppl_client_quality(loss_fn) -> Callable:
    """(C,) clipped perplexity per tracked client, one vmapped jit."""
    jloss = jax.jit(jax.vmap(lambda p, b: loss_fn(p, b)[0], in_axes=(None, 0)))

    def client_quality(params, batch) -> np.ndarray:
        losses = np.asarray(jloss(params, batch), np.float64)
        return np.exp(np.minimum(losses, _PPL_CLIP))

    return client_quality


def _err_client_quality(cfg) -> Callable:
    """(C,) weighted classification error per tracked client."""
    from repro.models.keyword import forward

    def one(params, b):
        logits = forward(cfg, params, b["features"], b["frame_len"])
        hit = (jnp.argmax(logits, axis=-1) == b["labels"][:, 0]).astype(jnp.float32)
        w = b["weight"]
        return 1.0 - (hit * w).sum() / jnp.maximum(w.sum(), 1.0)

    jerr = jax.jit(jax.vmap(one, in_axes=(None, 0)))

    def client_quality(params, batch) -> np.ndarray:
        return np.asarray(jerr(params, batch), np.float64)

    return client_quality


def _wer_client_quality(cfg) -> Callable:
    """(C,) WER per tracked client: one jitted decode over the
    flattened (C * n) batch, host-side per-client edit distance."""
    from repro.asr.wer import wer

    def client_quality(params, batch) -> np.ndarray:
        C, n = np.asarray(batch["weight"]).shape
        feats = jnp.asarray(batch["features"]).reshape((C * n,) + batch["features"].shape[2:])
        flens = jnp.asarray(batch["frame_len"]).reshape(C * n)
        hyp = np.asarray(_jitted_rnnt_decode(cfg)(params, feats, flens)).reshape(C, n, -1)
        labels = np.asarray(batch["labels"])
        label_len = np.asarray(batch["label_len"])
        weight = np.asarray(batch["weight"])
        out = np.zeros((C,), np.float64)
        for c in range(C):
            real = np.flatnonzero(weight[c] > 0)
            refs = [labels[c, i, : label_len[c, i]].tolist() for i in real]
            hyps = [hyp[c, i][hyp[c, i] != 0].tolist() for i in real]
            out[c] = wer(refs, hyps) if refs else 0.0
        return out

    return client_quality


# ------------------------------------------------------------ dispatch

# ModelBundle kind -> (quality metric, batch adapter). None adapter =
# the model consumes the engine layout directly.
_KIND_ADAPTERS = {
    "rnnt": ("wer", None),
    "audio": ("ppl", _encdec_adapt),
    "dense": ("ppl", _lm_adapt),
    "moe": ("ppl", _lm_adapt),
    "ssm": ("ppl", _lm_adapt),
    "hybrid": ("ppl", _lm_adapt),
    "keyword": ("err", None),
}


def task_for_config(cfg, name: Optional[str] = None) -> FederatedTask:
    """THE zoo-config -> task mapping: build the model bundle, pick the
    batch adapter + quality metric by model kind, wire the eval fns.
    Any ``repro.configs`` smoke config becomes a federated task."""
    from repro.models import build_model

    bundle = build_model(cfg)
    if bundle.kind not in _KIND_ADAPTERS:
        raise ValueError(
            f"no federated task adapter for model kind {bundle.kind!r} "
            f"(config {type(cfg).__name__}); the speaker corpus has no "
            f"modality for it — adapters exist for {sorted(_KIND_ADAPTERS)}"
        )
    metric, adapt = _KIND_ADAPTERS[bundle.kind]
    if adapt is None:
        loss_fn = bundle.loss_fn
    else:
        loss_fn = lambda p, b, rng=None: bundle.loss_fn(p, adapt(b), rng)  # noqa: E731
    if metric == "wer":
        evaluate = _wer_evaluate(cfg)
        client_quality = _wer_client_quality(cfg)
    elif metric == "err":
        evaluate = _err_evaluate(cfg)
        client_quality = _err_client_quality(cfg)
    else:
        evaluate = _ppl_evaluate(loss_fn)
        client_quality = _ppl_client_quality(loss_fn)
    return FederatedTask(
        name=name or cfg.name,
        kind=bundle.kind,
        quality_metric=metric,
        bundle=bundle,
        evaluate=evaluate,
        adapt_batch=adapt,
        client_quality=client_quality,
        make_corpus=default_corpus,
    )


def arch_task(arch_id: str, full: bool = False) -> FederatedTask:
    """A task from the ``--arch`` registry: its smoke config on the
    container-scale corpus, or with ``full`` its published widths on a
    corpus built at the config's own vocab and feature dim (``feat_dim``
    where the config has one)."""
    from repro.configs import get_arch

    arch = get_arch(arch_id)
    if not full:
        return task_for_config(arch.make_smoke_config(), name=arch_id)
    cfg = arch.make_config()
    corpus = functools.partial(
        default_corpus, vocab=cfg.vocab, feat_dim=getattr(cfg, "feat_dim", 16)
    )
    return dataclasses.replace(task_for_config(cfg, name=arch_id), make_corpus=corpus)


# ----------------------------------------------------- named registry

_TASKS: dict = {}


def register_task(name: str) -> Callable:
    """Decorator: register a task factory ``(seed) -> FederatedTask``."""

    def deco(factory):
        _TASKS[name] = factory
        return factory

    return deco


def available_tasks() -> list:
    return sorted(_TASKS)


def get_task(name: str, seed: int = 0) -> FederatedTask:
    if name not in _TASKS:
        raise KeyError(f"unknown task {name!r}; available: {available_tasks()}")
    return _TASKS[name](seed)


@register_task("asr-rnnt")
def _asr_rnnt_task(seed: int = 0) -> FederatedTask:
    """The paper's task at container scale (tiny_asr_setup's RNN-T)."""
    from repro.asr.specaugment import SpecAugmentConfig
    from repro.models.rnnt import RNNTConfig

    cfg = RNNTConfig(
        name="rnnt-tiny",
        feat_dim=16,
        vocab=64,
        enc_layers=2,
        enc_hidden=96,
        pred_layers=1,
        pred_hidden=96,
        pred_embed=32,
        joint_dim=64,
        time_stride=1,
        specaug=SpecAugmentConfig(
            freq_masks=1, freq_mask_width=3, time_masks=1, time_mask_frac=0.05
        ),
        dtype="float32",
        param_dtype="float32",
    )
    return task_for_config(cfg, name="asr-rnnt")


@register_task("asr-encdec")
def _asr_encdec_task(seed: int = 0) -> FederatedTask:
    """Whisper-style enc-dec over precomputed frame features (d_model
    == the corpus feat_dim, so arena features are the frame embeds)."""
    from repro.models.encdec import EncDecConfig

    cfg = EncDecConfig(
        name="encdec-tiny",
        enc_layers=1,
        dec_layers=1,
        d_model=16,
        n_heads=2,
        n_kv=2,
        head_dim=8,
        d_ff=32,
        vocab=64,
        max_source=24,
        max_target=12,
        dtype="float32",
        loss_chunk=12,
    )
    return task_for_config(cfg, name="asr-encdec")


@register_task("lm-transformer")
def _lm_transformer_task(seed: int = 0) -> FederatedTask:
    from repro.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        name="lm-tiny",
        n_layers=2,
        d_model=32,
        n_heads=2,
        n_kv=2,
        head_dim=16,
        d_ff=64,
        vocab=64,
        dtype="float32",
        loss_chunk=12,
    )
    return task_for_config(cfg, name="lm-transformer")


@register_task("lm-moe")
def _lm_moe_task(seed: int = 0) -> FederatedTask:
    from repro.models.moe import MoEConfig
    from repro.models.transformer import TransformerConfig

    cfg = TransformerConfig(
        name="moe-tiny",
        n_layers=2,
        d_model=32,
        n_heads=2,
        n_kv=2,
        head_dim=16,
        d_ff=64,
        vocab=64,
        moe=MoEConfig(n_experts=4, top_k=2, expert_ff=32, capacity_factor=2.0),
        dtype="float32",
        loss_chunk=12,
    )
    return task_for_config(cfg, name="lm-moe")


@register_task("lm-rwkv")
def _lm_rwkv_task(seed: int = 0) -> FederatedTask:
    from repro.models.model_zoo import RWKVModelConfig
    from repro.models.rwkv import RWKVConfig

    cfg = RWKVModelConfig(
        name="rwkv-tiny",
        n_layers=2,
        rwkv=RWKVConfig(d_model=32, head_size=16, d_ff=64),
        vocab=64,
        dtype="float32",
        loss_chunk=12,
    )
    return task_for_config(cfg, name="lm-rwkv")


@register_task("keyword")
def _keyword_task(seed: int = 0) -> FederatedTask:
    """The million-client CI workload: ~10k params."""
    from repro.models.keyword import KeywordConfig

    return task_for_config(
        KeywordConfig(name="keyword-tiny", feat_dim=16, n_classes=64, hidden=64),
        name="keyword",
    )
