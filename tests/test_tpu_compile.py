"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is attached: the TPU compiler installed with jax compiles for a
v5e that is only described (``topologies.get_topology_desc``), which
refuses what interpret mode never checks — block shapes that do not
tile, and more VMEM than a kernel may use. Shapes are the published
widths of ``rnnt-librispeech`` (H = 1152) and one ``w_hh`` leaf
(n = 1152 * 4608) for the wire kernels.

The topology is described inside a module-scoped fixture, never at
import, so every pytest worker collects the same tests and only the
worker running this file loads the TPU library. The persistent
compilation cache is off around these compiles: an entry compiled for
a described chip cannot be read back without one.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import wire_pack
from repro.kernels.lstm_gates import lstm_gates_bwd_fused, lstm_gates_fused, lstm_gates_fused_vjp
from repro.models.lstm import _fused_tile

H = 1152
N = H * 4 * H  # one encoder w_hh


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B", [1, 4, 8])
def test_lstm_gates_forward_compiles(one_chip, B):
    gates = _spec((B, 4 * H), jnp.bfloat16, one_chip)
    c = _spec((B, H), jnp.float32, one_chip)
    _assert_kernel(lambda g, c_: lstm_gates_fused(g, c_, th=_fused_tile(H)), gates, c)


@pytest.mark.parametrize("B", [1, 4, 8])
def test_lstm_gates_backward_compiles(one_chip, B):
    gates = _spec((B, 4 * H), jnp.bfloat16, one_chip)
    c = _spec((B, H), jnp.float32, one_chip)
    dh = _spec((B, H), jnp.bfloat16, one_chip)
    _assert_kernel(
        lambda g, c_, dh_, dc: lstm_gates_bwd_fused(g, c_, dh_, dc, th=_fused_tile(H)),
        gates, c, dh, c,
    )


def test_lstm_gates_vjp_compiles_vmapped_over_clients(one_chip):
    """The round vmaps the cell over K clients: the batched kernels,
    forward and backward, as the local step's gradient runs them."""
    K, B = 8, 4
    gates = _spec((K, B, 4 * H), jnp.bfloat16, one_chip)
    c = _spec((K, B, H), jnp.float32, one_chip)

    def loss(g, c_):
        h, cn = lstm_gates_fused_vjp(g, c_, th=_fused_tile(H))
        return jnp.sum(h.astype(jnp.float32)) + jnp.sum(cn)

    _assert_kernel(jax.vmap(jax.grad(loss, argnums=(0, 1))), gates, c)


@pytest.mark.parametrize("bits", [8, 4])
def test_keyed_quantize_compiles(one_chip, bits):
    x = _spec((N,), jnp.float32, one_chip)
    scale = _spec((), jnp.float32, one_chip)
    key = _spec((2,), jnp.uint32, one_chip)
    if bits == 8:
        fn = lambda x_, s, k: wire_pack.quantize_with_scale_keyed_pallas(x_, s, k, 8)  # noqa: E731
    else:
        fn = wire_pack.quantize_pack4_keyed_pallas
    _assert_kernel(fn, x, scale, key)


def test_dequantize_compiles(one_chip):
    codes = _spec((N,), jnp.int8, one_chip)
    scale = _spec((), jnp.float32, one_chip)
    _assert_kernel(wire_pack.dequantize_pallas, codes, scale)


def test_nibble_unpack_compiles(one_chip):
    packed = _spec((N // 2,), jnp.int8, one_chip)
    _assert_kernel(lambda p: wire_pack.nibble_unpack_pallas(p, N), packed)


def test_encoder_flash_attention_compiles_vmapped_over_clients(one_chip):
    """whisper-libri-fp32's encoder attention: K = 8 clients of 4
    utterances, 1,500 positions, 8 heads of 64, bf16; the forward and the
    backward kernel, as the local step's gradient runs them."""
    from repro.kernels.flash_attention import flash_attention_vjp

    x = _spec((8, 4, 1500, 8, 64), jnp.bfloat16, one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention_vjp(q, k, v).astype(jnp.float32) ** 2)

    text = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1, 2)))).lower(x, x, x).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2


def test_encoder_kernels_count_to_enc_attention(one_chip, monkeypatch):
    """One whisper-base encoder layer at its published widths, its
    gradient compiled for the chip: the kernel calls (forward, the
    layer's recompute, backward) all carry the ``enc_attention`` scope,
    so the benchmark books them to ``enc_attention_ms``."""
    import dataclasses
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from chipbench import kernels, scopes, scopes_encdec
    from repro.configs import get_arch
    from repro.models import encdec

    cfg = dataclasses.replace(get_arch("whisper-base").make_config(), enc_layers=1)
    params = jax.eval_shape(lambda: encdec.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), params)
    frames = _spec((1, 2 * cfg.max_source, cfg.n_mels), jnp.float32, one_chip)

    def loss(p, f):
        return jnp.sum(encdec.encode(cfg, p, f).astype(jnp.float32))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(jax.grad(loss)).lower(params, frames).compile().as_text()
    names = scopes.op_scopes(text)
    calls = kernels.custom_calls(text)
    assert len(calls) == 3
    assert {scopes_encdec.stage(names[c]) for c in calls} == {"enc_attention"}


# (K, B, T', U+1) of the RNN-T cells' joint: clients, utterances, encoder
# frames, label slots; J 640, V 4096
JOINT_CELLS = {"rnnt-libri-fp32": (4, 4, 1000, 61), "rnnt-query-fp32": (8, 4, 200, 13)}


@functools.lru_cache(maxsize=None)
def _joint_texts(sharding, cell):
    """Compiled text of the joint's forward and of its gradient, each
    vmapped over the cell's clients, as the round's local step runs them."""
    from repro.kernels.rnnt_joint import rnnt_joint

    K, B, T, U1 = JOINT_CELLS[cell]
    J, V = 640, 4096
    args = (
        _spec((K, B, T, J), jnp.bfloat16, sharding),
        _spec((K, B, U1, J), jnp.bfloat16, sharding),
        _spec((K, J, V), jnp.float32, sharding),
        _spec((K, V), jnp.float32, sharding),
        _spec((K, B, U1), jnp.int32, sharding),
        _spec((K, B), jnp.int32, sharding),
    )

    def loss(*a):
        blank, label = rnnt_joint(*a)
        return jnp.sum(blank * 1.3 + label)

    fwd = jax.jit(jax.vmap(rnnt_joint)).lower(*args).compile().as_text()
    grad = jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1, 2, 3)))).lower(*args).compile().as_text()
    return fwd, grad


@pytest.mark.parametrize("cell", sorted(JOINT_CELLS))
def test_rnnt_joint_compiles_vmapped_over_clients(one_chip, cell):
    """The joint kernel at the cell's shapes (T' and U+1 off their tiles):
    one call forward; the forward and the one backward kernel in the
    gradient."""
    fwd, grad = _joint_texts(one_chip, cell)
    assert fwd.count('custom_call_target="tpu_custom_call"') == 1
    assert grad.count('custom_call_target="tpu_custom_call"') == 2


def test_rnnt_joint_vmapped_kernel_in_no_client_loop(one_chip):
    """vmap over clients adds a grid axis to each kernel: the compiled
    gradient holds no loop of calls (the frame counts are an ordinary
    input, not scalar prefetch, which vmap would turn into a loop)."""
    for cell in JOINT_CELLS:
        fwd, grad = _joint_texts(one_chip, cell)
        assert not re.search(r"\bwhile\(", fwd + grad)


def test_joint_kernels_count_to_joint_loss(one_chip, monkeypatch):
    """The RNN-T's loss at its published widths (one LSTM layer each
    side), its gradient compiled for the chip: the joint's two kernel
    calls carry the ``joint`` scope, so the benchmark books them to
    ``joint_loss_ms`` and ``joint_kernel_pct``; the gate kernels stay in
    the LSTMs' stages."""
    import dataclasses
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from chipbench import kernels, scopes
    from repro.configs import get_arch
    from repro.models import rnnt

    cfg = dataclasses.replace(get_arch("rnnt-librispeech").make_config(), enc_layers=1,
                              pred_layers=1)
    params = jax.eval_shape(lambda: rnnt.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), params)
    B, T, U = 4, 64, 12
    batch = {"features": _spec((B, T, cfg.feat_dim), jnp.float32, one_chip),
             "labels": _spec((B, U), jnp.int32, one_chip),
             "frame_len": _spec((B,), jnp.int32, one_chip),
             "label_len": _spec((B,), jnp.int32, one_chip)}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(jax.grad(lambda p, b: rnnt.loss_fn(cfg, p, b)[0])).lower(params, batch) \
        .compile().as_text()
    names = scopes.op_scopes(text)
    stages = [scopes.stage(names[c]) for c in kernels.custom_calls(text)]
    assert stages.count("joint_loss") == 2
    assert set(stages) == {"joint_loss", "encoder_fwd", "encoder_bwd", "prednet"}
