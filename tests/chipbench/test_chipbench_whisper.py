"""The program's Whisper with its log-mel front end against the plain
reference (``chipbench/reference/whisper.py``) on seeded random weights,
at a tiny size on the CPU; the family's operation count by hand; and the
encoder-decoder's stage table on hand-made ``op_name``s."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import scopes_encdec
from chipbench.families import whisper
from chipbench.reference import whisper as reference

CONF = {"name": "tiny-whisper", "enc_layers": 2, "dec_layers": 2, "d_model": 16, "n_heads": 2,
        "n_kv": 2, "head_dim": 8, "d_ff": 32, "vocab": 40, "max_source": 12, "max_target": 16,
        "act": "gelu", "dtype": "float32", "param_dtype": "float32", "loss_chunk": 8, "n_mels": 8}
# Both sides compute in float32. The program's online softmax, its
# convolution and its chunked cross-entropy sum in other orders than the
# reference's plain forms: here the losses agree to the last bit and the
# worst gradient leaf to 1.5e-6 of its norm. The tolerances leave room
# above that for other seeds and libraries; bfloat16 compute misses the
# loss's by 5x (5.1e-5) and the gradients' by 200x (2.1e-2).
LOSS_RTOL = 1e-5
GRAD_RTOL = 1e-4


def _batch(seed: int = 0, B: int = 3, U: int = 10):
    rng = np.random.default_rng(seed)
    frames = (B, 2 * CONF["max_source"], CONF["n_mels"])
    return {"features": rng.standard_normal(frames, np.float32),
            "labels": rng.integers(1, CONF["vocab"], (B, U)).astype(np.int32),
            "weight": np.array([1.0, 1.0, 0.0], np.float32)[:B]}


def _program(dtype: str):
    from repro.models.encdec import EncDecConfig, loss_fn

    cfg = EncDecConfig(**dict({k: CONF[k] for k in whisper.FIELDS}, dtype=dtype))

    def loss(params, batch):
        b = {"frames": batch["features"], "tokens": batch["labels"], "weight": batch["weight"]}
        return loss_fn(cfg, params, b)[0]

    return cfg, loss


def _gaps(dtype: str):
    params = reference.init(CONF, jax.random.PRNGKey(3))
    batch = jax.tree.map(jnp.asarray, _batch())
    _, loss = _program(dtype)
    lp, gp = jax.jit(jax.value_and_grad(loss))(params, batch)
    lr, gr = jax.jit(jax.value_and_grad(lambda p, b: reference.loss(CONF, p, b)))(params, batch)
    loss_gap = abs(float(lp) - float(lr)) / abs(float(lr))
    grad_gaps = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)), gp, gr)
    return loss_gap, grad_gaps


def test_reference_layout_is_the_programs():
    from repro.models.encdec import init_params

    cfg, _ = _program("float32")
    ref = jax.eval_shape(lambda k: reference.init(CONF, k), jax.random.PRNGKey(0))
    prog = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    assert jax.tree.structure(ref) == jax.tree.structure(prog)
    assert jax.tree.leaves(jax.tree.map(lambda a, b: a.shape == b.shape and a.dtype == b.dtype,
                                        ref, prog)) == [True] * len(jax.tree.leaves(ref))
    assert set(prog["frontend"]) == {"conv1_w", "conv1_b", "conv2_w", "conv2_b"}


def test_loss_and_gradients_match_the_reference():
    loss_gap, grad_gaps = _gaps("float32")
    assert loss_gap < LOSS_RTOL
    worst = max(jax.tree.leaves(grad_gaps))
    assert worst < GRAD_RTOL, grad_gaps
    assert grad_gaps["frontend"]["conv1_w"] < GRAD_RTOL  # the front end is trained too


def test_bfloat16_compute_misses_the_tolerances():
    """The tolerances are tight enough that the configuration's compute
    in a lower precision fails them."""
    loss_gap, grad_gaps = _gaps("bfloat16")
    assert loss_gap > LOSS_RTOL
    assert max(jax.tree.leaves(grad_gaps)) > GRAD_RTOL


@pytest.mark.parametrize("n_mels, frames, width", [(8, 24, 8), (0, 12, 16)])
def test_encode_gives_max_source_positions(n_mels, frames, width):
    from repro.models.encdec import encode, init_params

    cfg, _ = _program("float32")
    cfg = dataclasses.replace(cfg, n_mels=n_mels)
    params = init_params(cfg, jax.random.PRNGKey(0))
    out = jax.eval_shape(lambda p, x: encode(cfg, p, x), params,
                         jax.ShapeDtypeStruct((2, frames, width), jnp.float32))
    assert out.shape == (2, cfg.max_source, cfg.d_model)
    assert ("frontend" in params) == bool(n_mels)


def test_whisper_forward_flops_by_hand():
    conf = {"d_model": 4, "d_ff": 8, "vocab": 11, "n_heads": 2, "n_kv": 2, "head_dim": 2,
            "enc_layers": 1, "dec_layers": 1, "max_source": 3, "n_mels": 5}
    u, S, D, F, V, Hd, M = 2, 3, 4, 8, 11, 4, 5
    proj = 2 * D * 4 * Hd
    mlp = 2 * 2 * D * F
    enc = S * (proj + mlp) + 4 * S * S * Hd
    dec = (u * (proj + mlp) + 4 * Hd * (u * (u + 1) // 2)
           + 2 * D * 2 * Hd * u + 2 * S * D * 2 * Hd + 4 * Hd * S * u)
    head = 2 * D * V * u
    conv1 = (2 * S) * 3 * M * D * 2   # 6 frames, width 3, 5 -> 4
    conv2 = S * 3 * D * D * 2         # 3 positions, width 3, 4 -> 4
    got = whisper.forward_flops(conf, np.array([1]), np.array([u]))
    assert int(got[0]) == enc + dec + head + conv1 + conv2
    assert int(whisper.train_flops(conf, np.array([1]), np.array([u]))[0]) == 3 * int(got[0])


STEP = "jit(round_step)/client/vmap()/while/body/closed_call"


@pytest.mark.parametrize("op_name, want", [
    (STEP + "/jvp(encoder)/frontend/conv_general_dilated", "enc_dense"),
    (STEP + "/transpose(jvp(encoder))/frontend/conv_general_dilated", "enc_dense"),
    (STEP + "/jvp(encoder)/while/body/checkpoint/dot_general", "enc_dense"),
    (STEP + "/jvp(encoder)/while/body/checkpoint/enc_attention/while/body/exp", "enc_attention"),
    (STEP + "/transpose(jvp(encoder))/while/body/rematted_computation/enc_attention/dot_general",
     "enc_attention"),
    ("jit(round_step)/client/vmap(transpose(jvp(encoder)))/while/body/remat(enc_attention)/mul",
     "enc_attention"),
    (STEP + "/jvp(decoder)/while/body/checkpoint/dot_general", "decoder"),
    (STEP + "/transpose(jvp(decoder))/while/body/dot_general", "decoder"),
    (STEP + "/jvp(lm_loss)/while/body/reduce_max", "vocab_loss"),
    (STEP + "/transpose(jvp(lm_loss))/dot_general", "vocab_loss"),
    (STEP + "/mul", "client_other"),
    ("jit(round_step)/server/aggregate/vmap()/add", "server"),
    ("jit(round_step)/server/server_step/sqrt", "server"),
    ("reduce_sum", "unscoped"),
    ("", "unscoped"),
])
def test_encdec_stage_rules(op_name, want):
    assert scopes_encdec.stage(op_name) == want


def test_encdec_stage_seconds_add_up():
    from chipbench import harness, trace

    hlo = "\n".join(f'  %{n} = f32[4]{{0}} fusion(f32[4]{{0}} %p.0), kind=kLoop,'
                    f' metadata={{op_name="{o}"}}' for n, o in [
                        ("fusion.1", STEP + "/jvp(encoder)/enc_attention/exp"),
                        ("fusion.2", STEP + "/jvp(encoder)/frontend/add"),
                        ("fusion.3", STEP + "/jvp(decoder)/add"),
                        ("fusion.4", STEP + "/jvp(lm_loss)/add"),
                        ("fusion.5", "jit(round_step)/server/server_step/add")])
    # copy.6 (no metadata) starts and ends inside fusion.1: each counts its own part
    events = [["fusion.1", 100, 40], ["copy.6", 110, 10], ["fusion.2", 140, 10],
              ["fusion.3", 150, 20], ["fusion.4", 170, 5], ["fusion.5", 180, 5]]
    rec = harness.Record(cell=None, peak={}, chips=1, hlo=hlo,
                         trace={"window": (100, 300), "rounds": 1,
                                "devices": {"/device:TPU:0": events}})
    got = scopes_encdec.stage_seconds(rec)
    assert got == pytest.approx({"enc_attention": 30e-9, "enc_dense": 10e-9, "decoder": 20e-9,
                                 "vocab_loss": 5e-9, "server": 5e-9, "unscoped": 10e-9})
    assert sum(got.values()) == pytest.approx(trace.busy_ns(events, 100, 300) * 1e-9)
    assert scopes_encdec.stage_ms(rec, "client_other") is None


@pytest.mark.parametrize("events, want", [
    ([["a", 0, 10], ["b", 10, 5]], {"a": 10, "b": 5}),                   # one after another
    ([["a", 0, 10], ["b", 2, 3], ["c", 6, 2]], {"a": 5, "b": 3, "c": 2}),  # two inside one
    ([["a", 0, 10], ["b", 2, 6], ["c", 3, 2]], {"a": 4, "b": 4, "c": 2}),  # nested twice
    ([["a", 0, 10], ["b", 8, 6]], {"a": 8, "b": 6}),                     # b runs past a's end
])
def test_self_times_cover_the_busy_time_once(events, want):
    from chipbench import trace

    got = {n: t for n, _, t in scopes_encdec.self_times(events)}
    assert got == want
    assert sum(got.values()) == trace.busy_ns(events, 0, 100)
