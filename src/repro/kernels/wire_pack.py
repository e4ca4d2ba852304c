"""Packed-wire kernels for the uplink compression plane (Pallas TPU).

The byte formulas in ``repro.core.compression`` price three wire
layouts; these kernels materialize them so the per-client uplink is a
real packed buffer, not an accounting fiction:

- ``nibble_pack`` / ``nibble_unpack``: int4 codes two-per-byte (low
  nibble = even element, high nibble = odd; odd sizes pad one nibble),
  sign-extended back on unpack — a pure VPU bit-twiddle pass.
- ``dequantize``: intN codes x fp32 scale -> f32, fused in one
  VMEM-resident pass (the server-side unpack of every intN payload).
- ``topk_unpack`` / ``topk_scatter_add``: scatter a (value, index)
  payload into the dense tensor. These are XLA scatters on every
  backend, not Pallas kernels: a Pallas scatter needs one-element
  stores at dynamic lane offsets, which Mosaic refuses.
- ``quantize_pack``: the *fused* uplink client kernel — grid-divide by
  the (shared or per-tensor) scale, clamp into the code grid,
  stochastic-round against a uniform field, and (for int4) nibble-pack
  — one VMEM pass per leaf instead of a quantize HLO chain followed by
  a separate pack pass. The absmax reduction stays outside so the
  4-byte scales can be max-reduced across the client axis first
  (shared-scale negotiation: exact code-domain sums).

Each Pallas kernel has a jnp oracle in ``ref.py`` (the parity target,
interpret=True on CPU) and a public auto-dispatch wrapper (Pallas on
TPU, the oracle as the CPU production path — same convention as the
model kernels). Pack->unpack is the identity on codes by construction,
which is what makes the packed compression path bit-exact against the
in-graph quantize->dequantize (tested in tests/test_wire_pack.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import ref

_TILE = 512  # lane-aligned (4 x 128) payload tile


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x, m: int):
    return jnp.pad(x, (0, (-x.shape[0]) % m))


# ------------------------------------------------------------ nibble pack


def _nibble_pack_kernel(ev_ref, od_ref, out_ref):
    ev = ev_ref[...].astype(jnp.int32) & 0xF
    od = od_ref[...].astype(jnp.int32) & 0xF
    b = ev | (od << 4)
    out_ref[...] = (((b & 0xFF) ^ 0x80) - 0x80).astype(jnp.int8)


def nibble_pack_pallas(codes, *, tile: int = _TILE, interpret: bool = False):
    """codes: (n,) int8 in [-8, 7] -> ((n+1)//2,) int8 nibble-packed."""
    n = codes.shape[0]
    nb = (n + 1) // 2
    c = _pad_to(codes, 2 * tile).reshape(-1, 2)  # (nbp, 2) pairs
    ev, od = c[:, 0][None, :], c[:, 1][None, :]  # (1, nbp)
    nbp = ev.shape[1]
    out = pl.pallas_call(
        _nibble_pack_kernel,
        grid=(nbp // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))] * 2,
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nbp), jnp.int8),
        interpret=interpret,
    )(ev, od)
    return out[0, :nb]


def _nibble_unpack_kernel(b_ref, lo_ref, hi_ref):
    b = b_ref[...].astype(jnp.int32) & 0xFF
    lo_ref[...] = (((b & 0xF) ^ 8) - 8).astype(jnp.int8)
    hi_ref[...] = ((((b >> 4) & 0xF) ^ 8) - 8).astype(jnp.int8)


def nibble_unpack_pallas(packed, n: int, *, tile: int = _TILE, interpret: bool = False):
    """packed: ((n+1)//2,) int8 -> (n,) int8 sign-extended codes."""
    b = _pad_to(packed, tile)[None, :]
    nbp = b.shape[1]
    lo, hi = pl.pallas_call(
        _nibble_unpack_kernel,
        grid=(nbp // tile,),
        in_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, tile), lambda i: (0, i))] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, nbp), jnp.int8)] * 2,
        interpret=interpret,
    )(b)
    return jnp.stack([lo[0], hi[0]], axis=-1).reshape(-1)[:n]


# -------------------------------------------------------------- dequantize


def _dequantize_kernel(c_ref, s_ref, out_ref):
    out_ref[...] = c_ref[...].astype(jnp.float32) * s_ref[0, 0]


def dequantize_pallas(codes, scale, *, tile: int = _TILE, interpret: bool = False):
    """codes: (n,) int8 + fp32 scale () -> (n,) f32, one fused pass."""
    n = codes.shape[0]
    c = _pad_to(codes, tile)[None, :]
    npad = c.shape[1]
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(npad // tile,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.float32),
        interpret=interpret,
    )(c, scale.reshape(1, 1))
    return out[0, :n]


# ---------------------------------------------------- fused quantize->pack


def _quantize_kernel(levels: float, x_ref, s_ref, u_ref, out_ref):
    y = jnp.clip(x_ref[...] / s_ref[0, 0], -levels, levels)
    lo = jnp.floor(y)
    out_ref[...] = (lo + (u_ref[...] < (y - lo)).astype(jnp.float32)).astype(jnp.int8)


def _quantize_nearest_kernel(levels: float, x_ref, s_ref, out_ref):
    y = jnp.clip(x_ref[...] / s_ref[0, 0], -levels, levels)
    out_ref[...] = jnp.round(y).astype(jnp.int8)


def _pack_byte(qe, qo):
    b = (qe.astype(jnp.int32) & 0xF) | ((qo.astype(jnp.int32) & 0xF) << 4)
    return (((b & 0xFF) ^ 0x80) - 0x80).astype(jnp.int8)


def _quantize_pack4_kernel(xe_ref, xo_ref, s_ref, ue_ref, uo_ref, out_ref):
    s = s_ref[0, 0]

    def q(x_ref, u_ref):
        y = jnp.clip(x_ref[...] / s, -7.0, 7.0)
        lo = jnp.floor(y)
        return (lo + (u_ref[...] < (y - lo)).astype(jnp.float32)).astype(jnp.int8)

    out_ref[...] = _pack_byte(q(xe_ref, ue_ref), q(xo_ref, uo_ref))


def _quantize_pack4_nearest_kernel(xe_ref, xo_ref, s_ref, out_ref):
    s = s_ref[0, 0]

    def q(x_ref):
        return jnp.round(jnp.clip(x_ref[...] / s, -7.0, 7.0)).astype(jnp.int8)

    out_ref[...] = _pack_byte(q(xe_ref), q(xo_ref))


def quantize_with_scale_pallas(
    x, scale, u, bits: int, *, tile: int = _TILE, interpret: bool = False
):
    """x: (n,) f32 + scale () [+ uniforms u: (n,) f32, None = nearest]
    -> (n,) int8 codes in [-levels, levels]: scale-divide, clamp and
    stochastic-round fused in one VMEM pass (the quantize half of the
    fused uplink kernel, for the unpacked int8/int4 planes)."""
    levels = 2.0 ** (bits - 1) - 1.0
    n = x.shape[0]
    xp = _pad_to(x, tile)[None, :]
    npad = xp.shape[1]
    spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    if u is None:
        out = pl.pallas_call(
            functools.partial(_quantize_nearest_kernel, levels),
            grid=(npad // tile,),
            in_specs=[spec, sspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((1, npad), jnp.int8),
            interpret=interpret,
        )(xp, scale.reshape(1, 1))
    else:
        out = pl.pallas_call(
            functools.partial(_quantize_kernel, levels),
            grid=(npad // tile,),
            in_specs=[spec, sspec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((1, npad), jnp.int8),
            interpret=interpret,
        )(xp, scale.reshape(1, 1), _pad_to(u, tile)[None, :])
    return out[0, :n]


def quantize_pack4_pallas(x, scale, u, *, tile: int = _TILE, interpret: bool = False):
    """Fully fused int4 client kernel: (n,) f32 + scale [+ uniforms]
    -> ((n+1)//2,) int8 nibble-packed wire bytes. Quantization and the
    even/odd nibble interleave happen in the same VMEM pass — the codes
    are never materialized in HBM."""
    n = x.shape[0]
    nb = (n + 1) // 2

    def pairs(a):
        p = _pad_to(a, 2 * tile).reshape(-1, 2)
        return p[:, 0][None, :], p[:, 1][None, :]

    xe, xo = pairs(x)
    nbp = xe.shape[1]
    spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    if u is None:
        out = pl.pallas_call(
            _quantize_pack4_nearest_kernel,
            grid=(nbp // tile,),
            in_specs=[spec, spec, sspec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((1, nbp), jnp.int8),
            interpret=interpret,
        )(xe, xo, scale.reshape(1, 1))
    else:
        ue, uo = pairs(u)
        out = pl.pallas_call(
            _quantize_pack4_kernel,
            grid=(nbp // tile,),
            in_specs=[spec, spec, sspec, spec, spec],
            out_specs=spec,
            out_shape=jax.ShapeDtypeStruct((1, nbp), jnp.int8),
            interpret=interpret,
        )(xe, xo, scale.reshape(1, 1), ue, uo)
    return out[0, :nb]


# ------------------------------------------- keyed (in-kernel PRNG) variants
# Same fused quantize->pack math, but the stochastic-rounding uniforms
# are *generated inside the kernel* from the leaf's threefry key words +
# each element's flat position (repro.kernels.ref.threefry_random_bits_at
# — plain uint32 jnp ops, so the identical 20-round hash runs on every
# backend). The (n,)-sized uniform field never exists in HBM, and the
# draw equals jax.random.uniform(key, (n,)) bit for bit under jax's
# partitionable threefry layout, which keeps the packed plane's
# tolerance-free parity with the streamed-field path.


def _iota_pos(tile: int):
    pid = pl.program_id(0)
    base = (pid * tile).astype(jnp.uint32)
    return base + jax.lax.broadcasted_iota(jnp.uint32, (1, tile), 1)


def _keyed_uniform(k_ref, pos):
    k0 = k_ref[0, 0]
    k1 = k_ref[0, 1]
    return ref.bits_to_uniform(ref.threefry_random_bits_at(k0, k1, pos))


def _quantize_keyed_kernel(levels: float, tile: int, x_ref, s_ref, k_ref, out_ref):
    u = _keyed_uniform(k_ref, _iota_pos(tile))
    y = jnp.clip(x_ref[...] / s_ref[0, 0], -levels, levels)
    lo = jnp.floor(y)
    out_ref[...] = (lo + (u < (y - lo)).astype(jnp.float32)).astype(jnp.int8)


def _quantize_pack4_keyed_kernel(tile: int, xe_ref, xo_ref, s_ref, k_ref, out_ref):
    pair = _iota_pos(tile)
    ue = _keyed_uniform(k_ref, pair * jnp.uint32(2))
    uo = _keyed_uniform(k_ref, pair * jnp.uint32(2) + jnp.uint32(1))
    s = s_ref[0, 0]

    def q(x_ref, u):
        y = jnp.clip(x_ref[...] / s, -7.0, 7.0)
        lo = jnp.floor(y)
        return (lo + (u < (y - lo)).astype(jnp.float32)).astype(jnp.int8)

    out_ref[...] = _pack_byte(q(xe_ref, ue), q(xo_ref, uo))


def quantize_with_scale_keyed_pallas(
    x, scale, key_data, bits: int, *, tile: int = _TILE, interpret: bool = False
):
    """x: (n,) f32 + scale () + key_data (2,) uint32 -> (n,) int8 codes,
    stochastic-rounded against in-kernel threefry draws (positionally
    identical to streaming jax.random.uniform(key, (n,)) in)."""
    levels = 2.0 ** (bits - 1) - 1.0
    n = x.shape[0]
    xp = _pad_to(x, tile)[None, :]
    npad = xp.shape[1]
    spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kspec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_quantize_keyed_kernel, levels, tile),
        grid=(npad // tile,),
        in_specs=[spec, sspec, kspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, npad), jnp.int8),
        interpret=interpret,
    )(xp, scale.reshape(1, 1), key_data.astype(jnp.uint32).reshape(1, 2))
    return out[0, :n]


def quantize_pack4_keyed_pallas(x, scale, key_data, *, tile: int = _TILE, interpret: bool = False):
    """Fully fused keyed int4 client kernel: quantize, stochastic-round
    from in-kernel PRNG, and nibble-pack in one VMEM pass — neither the
    codes nor the uniform field ever land in HBM."""
    n = x.shape[0]
    nb = (n + 1) // 2

    def pairs(a):
        p = _pad_to(a, 2 * tile).reshape(-1, 2)
        return p[:, 0][None, :], p[:, 1][None, :]

    xe, xo = pairs(x)
    nbp = xe.shape[1]
    spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    sspec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    kspec = pl.BlockSpec((1, 2), lambda i: (0, 0))
    out = pl.pallas_call(
        functools.partial(_quantize_pack4_keyed_kernel, tile),
        grid=(nbp // tile,),
        in_specs=[spec, spec, sspec, kspec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, nbp), jnp.int8),
        interpret=interpret,
    )(xe, xo, scale.reshape(1, 1), key_data.astype(jnp.uint32).reshape(1, 2))
    return out[0, :nb]


# ---------------------------------------------------- public auto-dispatch
# Pallas on TPU; the jnp oracle is the CPU production path (interpret
# mode is for tests only — same convention as repro.kernels.ops). The
# top-k scatters are XLA scatters on every backend: Mosaic refuses the
# one-element dynamic lane stores a Pallas scatter needs.


def _dispatch() -> tuple:
    """(use_ref, interpret) for this call site: the jnp oracle on the
    CPU, the Pallas kernel compiled for the device elsewhere."""
    return _on_cpu(), False


def nibble_pack(codes):
    use_ref, interpret = _dispatch()
    if use_ref:
        return ref.nibble_pack_ref(codes)
    return nibble_pack_pallas(codes, interpret=interpret)


def nibble_unpack(packed, n: int):
    use_ref, interpret = _dispatch()
    if use_ref:
        return ref.nibble_unpack_ref(packed, n)
    return nibble_unpack_pallas(packed, n, interpret=interpret)


def dequantize(codes, scale):
    use_ref, interpret = _dispatch()
    if use_ref:
        return ref.dequantize_ref(codes, scale)
    return dequantize_pallas(codes, jnp.asarray(scale, jnp.float32), interpret=interpret)


def topk_unpack(values, idx, n: int):
    """(k,) values + (k,) int32 flat indices -> dense (n,) f32: an XLA
    scatter on every backend."""
    return ref.topk_unpack_ref(values, idx, n)


def quantize_with_scale(x, scale, u, bits: int):
    """Fused scale-divide -> clamp -> (stochastic) round: x (any
    shape) -> int8 codes shaped like x. ``u`` is the uniform rounding
    field (x-shaped; None = nearest). Bit-identical to the historical
    quantize_codes math for the same key — ``u < frac`` IS
    jax.random.bernoulli's draw."""
    use_ref, interpret = _dispatch()
    if use_ref:
        levels = 2.0 ** (bits - 1) - 1.0
        return ref.quantize_codes_with_scale_ref(x, scale, u, levels)
    flat = x.reshape(-1)
    uf = None if u is None else u.reshape(-1)
    out = quantize_with_scale_pallas(
        flat, jnp.asarray(scale, jnp.float32), uf, bits, interpret=interpret
    )
    return out.reshape(jnp.shape(x))


def quantize_pack(x, scale, u, bits: int):
    """Fused uplink client kernel: (n,) f32 -> the intN wire buffer
    (int8: the codes; int4: nibble-packed bytes), quantized against a
    caller-supplied (shared or per-tensor) scale in one pass."""
    use_ref, interpret = _dispatch()
    if use_ref:
        return ref.quantize_pack_ref(x, scale, u, bits)
    if bits == 4:
        return quantize_pack4_pallas(x, jnp.asarray(scale, jnp.float32), u, interpret=interpret)
    return quantize_with_scale_pallas(
        x, jnp.asarray(scale, jnp.float32), u, bits, interpret=interpret
    )


def quantize_with_scale_keyed(x, scale, key_data, bits: int):
    """Keyed twin of ``quantize_with_scale``: the rounding uniforms come
    from the in-kernel threefry hash of ``key_data`` ((2,) uint32 words,
    i.e. the per-leaf fold_in key) instead of a streamed field. Codes
    are bit-identical to quantize_with_scale(x, scale,
    jax.random.uniform(key, x.shape), bits) on every backend."""
    use_ref, interpret = _dispatch()
    n = int(jnp.size(x))
    if use_ref:
        levels = 2.0 ** (bits - 1) - 1.0
        u = ref.threefry_uniform_ref(key_data, n).reshape(jnp.shape(x))
        return ref.quantize_codes_with_scale_ref(x, scale, u, levels)
    out = quantize_with_scale_keyed_pallas(
        x.reshape(-1), jnp.asarray(scale, jnp.float32), key_data, bits, interpret=interpret
    )
    return out.reshape(jnp.shape(x))


def quantize_pack_keyed(x, scale, key_data, bits: int):
    """Keyed twin of ``quantize_pack``: fused quantize -> stochastic
    round (in-kernel PRNG) -> pack. Neither the uniform field nor (for
    int4) the codes touch HBM; the wire bytes equal quantize_pack with
    the streamed jax.random.uniform(key, (n,)) field bit for bit."""
    use_ref, interpret = _dispatch()
    n = x.shape[0]
    if use_ref:
        u = ref.threefry_uniform_ref(key_data, n)
        return ref.quantize_pack_ref(x, scale, u, bits)
    if bits == 4:
        return quantize_pack4_keyed_pallas(
            x, jnp.asarray(scale, jnp.float32), key_data, interpret=interpret
        )
    return quantize_with_scale_keyed_pallas(
        x, jnp.asarray(scale, jnp.float32), key_data, bits, interpret=interpret
    )


def topk_scatter_add(values, idx, weights, n: int):
    """Aggregate stacked top-k payloads in the code domain: values
    (K, k) f32, idx (K, k) int32, weights (K,) -> dense (n,) f32
    weighted sum. Duplicate coordinates accumulate. An XLA scatter-add
    on every backend, like ``topk_unpack``."""
    return ref.topk_scatter_add_ref(values, idx, weights, n)
