"""The program names the round's stages and the models' layers on its
compiled instructions: the tiny RNN-T round step, compiled on the CPU,
has at least one instruction in every stage of the benchmark's table
(chipbench/scopes.py), on the fedavg fp32 path, the int8 code-domain
fast path and the fedsgd engine; the tiny Whisper round step (log-mel
front end) has one in every stage of the encoder-decoder's table
(chipbench/scopes_encdec.py)."""
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import scopes, scopes_encdec  # noqa: E402
from repro.core import CompressionConfig, FederatedPlan, build_round_engine, get_task  # noqa: E402
from repro.data import FederatedSampler  # noqa: E402

PLANS = {
    "fedavg-fp32": {},
    "fedavg-int8": {"compression": CompressionConfig(kind="int8")},
    "fedsgd": {"engine": "fedsgd"},
}


@pytest.fixture(scope="module")
def tiny():
    task = get_task("asr-rnnt")
    return task, task.make_corpus(0), task.bundle.init(jax.random.PRNGKey(0))


def _compiled_text(tiny, plan_kw) -> str:
    task, corpus, params = tiny
    plan = FederatedPlan(clients_per_round=2, local_batch_size=2, data_limit=4, **plan_kw)
    engine = build_round_engine(plan, task, base_key=jax.random.PRNGKey(1))
    batch = FederatedSampler(corpus, 2, 2, data_limit=4, seed=0).next_round().engine_batch()
    if plan.engine == "fedsgd":  # one local step a round
        batch = {k: v[:, :1] for k, v in batch.items()}
    state = engine.init_state(params)
    return jax.jit(engine.step).lower(state, batch).compile().as_text()


@pytest.mark.parametrize("plan", sorted(PLANS))
def test_every_stage_has_instructions(tiny, plan):
    names = scopes.op_scopes(_compiled_text(tiny, PLANS[plan]))
    assert names
    count = {}
    for op_name in names.values():
        s = scopes.stage(op_name)
        count[s] = count.get(s, 0) + 1
    missing = [s for s in scopes.STAGES if not count.get(s)]
    assert not missing, f"stages with no instruction: {missing} ({count})"
    # what no scope names is a small remainder (reduction bodies, XLA's own)
    assert count.get(scopes.UNSCOPED, 0) < len(names) / 10


def test_w_hh_grad_booked_to_its_layer(tiny):
    """The LSTM backward forms dW_hh after its time loop under the
    ``w_hh_grad`` scope; those instructions exist in the round step and
    count to the encoder's backward and to the prediction network."""
    names = scopes.op_scopes(_compiled_text(tiny, PLANS["fedavg-fp32"]))
    booked = {scopes.stage(n) for n in names.values() if "/w_hh_grad/" in n}
    assert booked == {"encoder_bwd", "prednet"}


def _encdec_op_names() -> dict:
    """{instruction: op_name} of the tiny Whisper round step compiled here."""
    import numpy as np

    from repro.configs import base, get_arch
    from repro.core import task_for_config

    cfg = get_arch("whisper-base").make_smoke_config()
    task = task_for_config(cfg)
    plan = FederatedPlan(clients_per_round=2, local_batch_size=2, data_limit=4)
    engine = build_round_engine(plan, task, base_key=jax.random.PRNGKey(1))
    K, S, b, U = 2, 2, 2, 10
    rng = np.random.default_rng(0)
    batch = {"features": rng.standard_normal((K, S, b, *base.audio_frames(cfg)), np.float32),
             "labels": rng.integers(1, cfg.vocab, (K, S, b, U)).astype(np.int32),
             "frame_len": np.full((K, S, b), 2 * cfg.max_source, np.int32),
             "label_len": np.full((K, S, b), U, np.int32),
             "weight": np.ones((K, S, b), np.float32)}
    state = engine.init_state(task.bundle.init(jax.random.PRNGKey(0)))
    return scopes.op_scopes(jax.jit(engine.step).lower(state, batch).compile().as_text())


def test_encdec_scopes_reach_the_round_step():
    names = _encdec_op_names()
    parts = {p.split("(")[-1].rstrip(")") for n in names.values() for p in n.split("/")}
    assert {"frontend", "encoder", "enc_attention", "decoder", "lm_loss"} <= parts
    count = {}
    for op_name in names.values():
        s = scopes_encdec.stage(op_name)
        count[s] = count.get(s, 0) + 1
    missing = [s for s in scopes_encdec.STAGES if not count.get(s)]
    assert not missing, f"stages with no instruction: {missing} ({count})"
    # what no scope names is nearly all the bodies of reductions (the
    # layer norms', the softmaxes'), which run inside their callers' fusions
    assert count.get(scopes_encdec.UNSCOPED, 0) < len(names) / 5


def test_encoder_flash_kernel_counts_to_enc_attention(monkeypatch):
    """With the encoder on the flash kernel (forced here, in interpret
    mode), every instruction lowered from the kernel's calls, forward,
    recompute and backward, lies in the ``enc_attention`` stage."""
    from repro.models import encdec

    monkeypatch.setattr(encdec, "_flash_path", lambda: True)
    kernel = [n for n in _encdec_op_names().values() if "/jit(attention)/" in n]
    backward = [n for n in kernel if "transpose(jvp(encoder))" in n]
    assert len(backward) < len(kernel)                                 # the forward
    assert any("rematted_computation" in n for n in backward)          # the layer's recompute
    assert any("rematted_computation" not in n for n in backward)      # the backward kernel
    assert {scopes_encdec.stage(n) for n in kernel} == {"enc_attention"}
