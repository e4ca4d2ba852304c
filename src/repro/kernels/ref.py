"""Pure-jnp oracles for every Pallas kernel (the allclose targets).

These are *definitions*, deliberately simple and memory-naive — tests
sweep shapes/dtypes and assert the kernels (interpret=True on CPU)
match them. Production jnp fallbacks live in repro/models (blockwise
formulations); these oracles materialize everything for clarity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1.0e30


def attention_ref(q, k, v, *, causal=True, window=None, q_offset=0, logit_softcap=0.0):
    """q: (B, Sq, H, D); k, v: (B, Sk, Kv, D). Returns (B, Sq, H, Dv)."""
    B, Sq, H, D = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qf = q.astype(jnp.float32).reshape(B, Sq, Kv, G, D) * (D**-0.5)
    s = jnp.einsum("bqkgd,bjkd->bqkgj", qf, k.astype(jnp.float32))
    if logit_softcap > 0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(mask[None, :, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgj,bjkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def decode_attention_ref(q, k_cache, v_cache, pos, *, window=None):
    """q: (B, H, D); caches: (B, S, Kv, D); pos scalar (current token
    index, already written into the cache)."""
    B, H, D = q.shape
    S, Kv = k_cache.shape[1], k_cache.shape[2]
    G = H // Kv
    qf = q.astype(jnp.float32).reshape(B, Kv, G, D) * (D**-0.5)
    s = jnp.einsum("bkgd,bjkd->bkgj", qf, k_cache.astype(jnp.float32))
    j = jnp.arange(S)
    valid = j <= pos
    if window is not None:
        valid &= j > pos - window
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgj,bjkd->bkgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, H, v_cache.shape[-1]).astype(q.dtype)


def rnnt_joint_ref(enc_proj, pred_proj, w_out, bias, labels):
    """Fused joint oracle: materializes (B, T, U1, V) logits.

    enc_proj: (B, T, J); pred_proj: (B, U1, J); w_out: (J, V);
    bias: (V,); labels: (B, U1-? ) — (B, U1) label ids (last unused).
    Returns (blank_lp, label_lp): (B, T, U1).
    """
    h = jnp.tanh(
        enc_proj[:, :, None, :].astype(jnp.float32) + pred_proj[:, None, :, :].astype(jnp.float32)
    )
    logits = h @ w_out.astype(jnp.float32) + bias.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    blank_lp = logits[..., 0] - lse
    lbl = labels[:, None, :, None].astype(jnp.int32)  # (B,1,U1,1)
    lbl = jnp.broadcast_to(lbl, logits.shape[:3] + (1,))
    label_lp = jnp.take_along_axis(logits, lbl, axis=-1)[..., 0] - lse
    return blank_lp, label_lp


def nibble_pack_ref(codes):
    """int4 wire packing oracle: (n,) int8 codes in [-8, 7] -> the
    ((n+1)//2,) int8 nibble-packed payload (element 2i in the low
    nibble, 2i+1 in the high; odd n pads the last high nibble with 0)."""
    n = codes.shape[0]
    c = codes.astype(jnp.int32) & 0xF
    c = jnp.pad(c, (0, n % 2))
    pairs = c.reshape(-1, 2)
    b = pairs[:, 0] | (pairs[:, 1] << 4)
    return (((b & 0xFF) ^ 0x80) - 0x80).astype(jnp.int8)  # two's-complement byte


def nibble_unpack_ref(packed, n: int):
    """Inverse of ``nibble_pack_ref``: sign-extend both nibbles of each
    byte and drop the odd-n pad -> (n,) int8 codes."""
    b = packed.astype(jnp.int32) & 0xFF
    lo = ((b & 0xF) ^ 8) - 8
    hi = (((b >> 4) & 0xF) ^ 8) - 8
    return jnp.stack([lo, hi], axis=-1).reshape(-1)[:n].astype(jnp.int8)


def dequantize_ref(codes, scale):
    """intN codes + fp32 scale -> f32 (the uplink dequantization)."""
    return codes.astype(jnp.float32) * scale


def quantize_codes_with_scale_ref(x, scale, u, levels: float):
    """Stochastic-round/clamp oracle for a *given* scale: (n,) f32 +
    scale () + uniforms (n,) (None = nearest rounding) -> (n,) int8
    codes in [-levels, levels].

    The clamp precedes the rounding draw (the PR 3 ulp regression: f32
    division can land the absmax coordinate one ulp outside the grid,
    and a boundary draw would round to levels+1 and wrap the int8
    cast). ``u < frac`` is exactly ``jax.random.bernoulli``'s
    uniform-threshold draw, so given the same key this matches the
    historical bernoulli-based path bit for bit."""
    y = jnp.clip(x.astype(jnp.float32) / scale, -levels, levels)
    if u is None:
        return jnp.round(y).astype(jnp.int8)
    lo = jnp.floor(y)
    return (lo + (u < (y - lo)).astype(jnp.float32)).astype(jnp.int8)


def quantize_pack_ref(x, scale, u, bits: int):
    """Fused quantize->pack oracle: one tensor's intN wire buffer from
    (x, shared-or-own scale, uniforms). int8 -> the codes themselves;
    int4 -> the nibble-packed bytes (pack_ref of the codes)."""
    levels = 2.0 ** (bits - 1) - 1.0
    codes = quantize_codes_with_scale_ref(x, scale, u, levels)
    return nibble_pack_ref(codes) if bits == 4 else codes


def topk_unpack_ref(values, idx, n: int):
    """Scatter a top-k (value, index) payload into a dense (n,) f32."""
    return jnp.zeros((n,), jnp.float32).at[idx].set(values.astype(jnp.float32))


# ------------------------------------------------- in-kernel PRNG oracle
# jax's threefry2x32 PRNG, restated elementwise so the quantize kernels
# can draw each element's uniform from its flat position alone — no
# (n,)-shaped uniform field ever streams through HBM. Under jax's
# partitionable threefry layout (the default since jax 0.5), the 32-bit
# draw at flat position j hashes the 64-bit counter j as the word pair
# (hi(j), lo(j)) and XORs the two output lanes, so each element's bits
# depend on its position alone, never on the draw size.
# ``threefry_random_bits_at`` computes exactly that for j < 2**32, so it
# equals jax.random.bits / jax.random.uniform bit for bit — the
# tolerance-free parity contract of the keyed quantize kernels
# (tests/test_wire_pack.py sweeps even/odd n).

_THREEFRY_C = 0x1BD11BDA
_THREEFRY_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl32(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32_pair(k0, k1, c0, c1):
    """One threefry2x32 block hash: uint32 key words x uint32 counter
    words -> both uint32 output lanes (jax's 20-round schedule)."""
    ks2 = k0 ^ k1 ^ jnp.uint32(_THREEFRY_C)
    x0 = c0 + k0
    x1 = c1 + k1
    inject = ((k1, ks2), (ks2, k0), (k0, k1), (k1, ks2), (ks2, k0))
    for i, (i0, i1) in enumerate(inject):
        for r in _THREEFRY_ROT[i % 2]:
            x0 = x0 + x1
            x1 = _rotl32(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + i0
        x1 = x1 + i1 + jnp.uint32(i + 1)
    return x0, x1


def threefry_random_bits_at(k0, k1, pos):
    """Random uint32 at flat position(s) ``pos`` (< 2**32) of any draw —
    elementwise jax.random.bits(key, (n,)) for every n > pos."""
    pos = pos.astype(jnp.uint32)
    o0, o1 = threefry2x32_pair(k0, k1, jnp.zeros_like(pos), pos)
    return o0 ^ o1


def bits_to_uniform(bits):
    """uint32 -> [0, 1) f32, jax.random.uniform's exact mantissa fill."""
    f = jax.lax.bitcast_convert_type(
        (bits >> jnp.uint32(9)) | jnp.uint32(0x3F800000), jnp.float32
    )
    return f - 1.0


def threefry_uniform_ref(key_data, n: int):
    """(2,) uint32 key words -> (n,) f32 == jax.random.uniform(key, (n,))
    bit for bit (the streamed-field oracle the in-kernel PRNG must
    reproduce exactly)."""
    k0 = key_data[0].astype(jnp.uint32)
    k1 = key_data[1].astype(jnp.uint32)
    pos = jnp.arange(n, dtype=jnp.uint32)
    return bits_to_uniform(threefry_random_bits_at(k0, k1, pos))


def topk_scatter_add_ref(values, idx, weights, n: int):
    """Weighted scatter-ADD of a stacked top-k payload: values (K, k)
    f32, idx (K, k) int32 flat indices, weights (K,) f32 -> dense (n,)
    f32 sum over clients (duplicate indices accumulate). The code-domain
    aggregation oracle for the top-k plane."""
    flat_vals = (weights[:, None] * values.astype(jnp.float32)).reshape(-1)
    flat_idx = idx.reshape(-1)
    return jnp.zeros((n,), jnp.float32).at[flat_idx].add(flat_vals)


def lstm_gates_ref(gates, c):
    """gates: (B, 4H) preactivation [i|f|g|o]; c: (B, H)."""
    h4 = gates.shape[-1]
    hd = h4 // 4
    gf = gates.astype(jnp.float32)
    i = jax.nn.sigmoid(gf[..., :hd])
    f = jax.nn.sigmoid(gf[..., hd : 2 * hd] + 1.0)
    g = jnp.tanh(gf[..., 2 * hd : 3 * hd])
    o = jax.nn.sigmoid(gf[..., 3 * hd :])
    c_new = f * c.astype(jnp.float32) + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new.astype(gates.dtype), c_new.astype(c.dtype)
