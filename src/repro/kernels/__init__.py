"""Pallas TPU kernels for the perf-critical compute layers.

- ``rnnt_joint``      — fused RNN-T joint + log-softmax + (blank, label)
                        log-probs, with its own backward (the
                        paper-model's memory hot-spot)
- ``flash_attention`` — blockwise causal/window/GQA attention, and the
                        encoder's self-attention with its own backward
- ``decode_attention``— flash-decode (one token vs. a long cache)
- ``lstm_gates``      — fused LSTM cell pointwise update
- ``wire_pack``       — packed-wire payloads for the compression plane
                        (int4 nibble pack/unpack, intN dequant, top-k
                        scatter-unpack)

Each has a jnp oracle in ``ref.py`` and a jit'd wrapper in ``ops.py``
(``wire_pack`` carries its own backend dispatch).
On this CPU-only container they run in interpret mode; TPU is the
compile target (BlockSpec VMEM tiling, MXU-aligned tiles).
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
