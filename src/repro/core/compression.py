"""Uplink delta compression — the wire side of the CFMQ cost axis.

The paper approximates the round-trip payload as ``2 x model_bytes``
(§4.3.1); production cross-device FL compresses the *uplink* (client
-> server) aggressively because client bandwidth dominates. This
module provides the compressors for the per-client deltas so the round
step both (a) trains through the real quantization error and (b)
reports the *exact* bytes each client would put on the wire:

- ``int8`` / ``int4``: per-tensor absmax stochastic quantization.
  Stochastic rounding keeps the dequantized delta unbiased
  (E[Q(x)] = x), which is what lets the example-weighted mean still
  converge; a 4-byte fp32 scale per tensor rides along.
- ``topk``: per-tensor magnitude sparsification; only ``k = ceil(frac
  * size)`` (value, index) pairs travel (4 + 4 bytes each).
- ``none``: identity, fp32 on the wire (the paper/parity path).

The implementation is layered so the byte formulas are backed by real
buffers, not just arithmetic:

1. a *codes* layer (``quantize_codes`` / ``dequantize_codes`` /
   ``topk_select``) that maps tensors to the integer codes and
   (value, index) pairs a client would actually transmit;
2. an in-graph quantize->dequantize path (``make_compressor`` with
   ``packed=False``) that composes the codes layer without ever
   leaving fp32 — the cheap simulation path;
3. a *packed-wire* path (``packed=True``) that materializes the int8
   buffer / int4 nibble-packed buffer / top-k (value, index) payload
   via the ``repro.kernels.wire_pack`` kernels and round-trips it.
   Pack->unpack is bit-exact against path 2 by construction: both
   consume the same codes, so the dequantized deltas are identical
   while the payload's materialized byte size equals
   ``leaf_wire_bytes`` for every kind (property-tested);
4. a *code-domain fast path* (``code_domain_aggregate``; the round
   engine selects it statically for quantizing planes under the
   paper's weighted mean) that never rematerializes per-client fp32
   deltas: scales are negotiated cohort-wide by a max-reduce over the
   client axis (so the integer code sums are exact), each client runs
   the fused ``wire_pack.quantize_pack`` kernel, ``sum_packed_codes``
   reduces in int32, and the server dequantizes ONCE. Same wire bytes,
   same payload buffers — only the compute drops.

``error_feedback`` turns on EF21-style residual accumulation in the
round engine (see ``repro.core.fedavg``): each client compresses
``delta + residual`` and keeps the compression error as next round's
residual, which recovers the quality that plain top-k loses at
aggressive sparsity. It changes no wire bytes — only what travels in
them.

Kind and fractions are *static* (compile-time structure — they change
wire layout and graph shape); the RNG key is traced. Byte accounting
is pure Python over leaf shapes (``client_wire_bytes``) so CFMQ and
the round metrics agree to the byte by construction.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

PyTree = Any

KINDS = ("none", "int8", "int4", "topk")

# fp32 scalar (scale) / value / index — all 4 bytes on the wire.
_WORD = 4

_BITS = {"int8": 8, "int4": 4}


@dataclasses.dataclass(frozen=True)
class CompressionConfig:
    """Static uplink compression spec (part of the jit cache key)."""

    kind: str = "none"  # none | int8 | int4 | topk
    topk_frac: float = 0.05  # fraction of coordinates kept per tensor
    stochastic: bool = True  # stochastic (unbiased) vs nearest rounding
    packed: bool = False  # materialize + round-trip the wire payload
    error_feedback: bool = False  # EF21 per-client residual accumulation

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown compression kind {self.kind!r}; available: {KINDS}")
        # only validate the knob that is actually in use, so callers can
        # pass an inert topk_frac (e.g. a CLI default) with other kinds
        if self.kind == "topk" and not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], got {self.topk_frac}")
        if self.kind == "none" and self.packed:
            raise ValueError(
                "packed=True materializes a quantized wire payload; "
                "kind='none' ships raw fp32 and has nothing to pack"
            )
        if self.kind == "none" and self.error_feedback:
            raise ValueError(
                "error_feedback compensates compression error; with "
                "kind='none' there is no error to feed back"
            )


def _topk_count(frac: float, size: int) -> int:
    return max(1, min(size, int(math.ceil(frac * size))))


def leaf_wire_bytes(cfg: CompressionConfig, size: int) -> int:
    """Exact uplink bytes for one tensor of ``size`` elements."""
    if cfg.kind == "none":
        return _WORD * size
    if cfg.kind == "int8":
        return size + _WORD  # 1 B/elt + fp32 scale
    if cfg.kind == "int4":
        return (size + 1) // 2 + _WORD  # two elts per byte + scale
    if cfg.kind == "topk":
        return 2 * _WORD * _topk_count(cfg.topk_frac, size)
    raise ValueError(cfg.kind)


def client_wire_bytes(cfg: CompressionConfig, tree: PyTree) -> int:
    """Exact per-client uplink bytes for one delta pytree."""
    return sum(leaf_wire_bytes(cfg, int(l.size)) for l in jax.tree.leaves(tree))


def tree_param_bytes(tree: PyTree) -> int:
    """Downlink bytes: the server broadcasts the full model."""
    return sum(int(l.size) * jnp.dtype(l.dtype).itemsize for l in jax.tree.leaves(tree))


# ----------------------------------------------------------------------
# Codes layer: tensors <-> the integers / (value, index) pairs that a
# client actually transmits. Both the in-graph and the packed path are
# built on these, which is what makes them bit-exact to each other.
# ----------------------------------------------------------------------


def leaf_scale(x, bits: int):
    """Per-tensor absmax scale: max|x| / levels, guarded against the
    all-zero tensor (scale 1.0 keeps the codes at exactly 0)."""
    levels = 2.0 ** (bits - 1) - 1.0  # 127 (int8) / 7 (int4)
    scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / levels
    return jnp.where(scale > 0, scale, 1.0)


def _rounding_field(key, shape, stochastic: bool):
    """The stochastic-rounding uniforms (None = nearest). ``u < frac``
    is jax.random.bernoulli's own draw. Since PR 10 the production
    kernels generate this field *in-kernel* (threefry hashed from the
    key words + each element's flat position — never materialized in
    HBM); this streamed form remains the oracle the bit-parity tests
    check the in-kernel draw against."""
    return jax.random.uniform(key, shape) if stochastic else None


def _key_words(key):
    """The raw (2,) uint32 threefry words of ``key`` (typed or raw
    PRNG key) — what the in-kernel PRNG hashes."""
    key = jnp.asarray(key)
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(key)
    return key


def quantize_codes_with_scale(x, key, scale, bits: int, stochastic: bool = True):
    """intN codes of ``x`` against a *given* scale — the cohort-shared
    entry point of the code-domain fast path (every client quantizing
    on one negotiated grid is what makes code sums exact).

    ``y`` is clamped into the grid *before* the rounding draw: f32
    division can land the absmax coordinate one ulp outside the grid
    (|x| / (|x|/levels) > levels), and a boundary draw would round up
    to levels+1 and get clipped back — biasing E[Q(x)] *below* x
    exactly at the max-magnitude coordinate. Clamped, the boundary is
    deterministic and the documented unbiasedness holds on the whole
    grid.
    """
    from repro.kernels import wire_pack

    xf = x.astype(jnp.float32)
    if stochastic:
        return wire_pack.quantize_with_scale_keyed(xf, scale, _key_words(key), bits)
    return wire_pack.quantize_with_scale(xf, scale, None, bits)


def quantize_codes(x, key, bits: int, stochastic: bool = True):
    """Per-tensor absmax intN codes: -> (int8 codes shaped like x, fp32
    scale scalar), with codes in [-levels, levels]."""
    scale = leaf_scale(x, bits)
    return quantize_codes_with_scale(x, key, scale, bits, stochastic), scale


def dequantize_codes(codes, scale, dtype=jnp.float32):
    """codes * scale; int8 codes are exact in f32, so this reproduces
    the in-graph quantize->dequantize value bit-for-bit."""
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def topk_select(x, frac: float):
    """The top-k wire payload of one tensor: -> (fp32 values (k,),
    int32 flat indices (k,)), k = ceil(frac * size)."""
    flat = x.reshape(-1)
    k = _topk_count(frac, flat.size)
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    return flat[idx].astype(jnp.float32), idx.astype(jnp.int32)


def _quantize_leaf(x, key, bits: int, stochastic: bool):
    """Per-tensor absmax intN quantize->dequantize (symmetric grid)."""
    codes, scale = quantize_codes(x, key, bits, stochastic)
    return dequantize_codes(codes, scale, x.dtype)


def _topk_leaf(x, frac: float):
    """Keep the k largest-|x| coordinates, zero the rest (exact k)."""
    vals, idx = topk_select(x, frac)
    out = jnp.zeros((x.size,), x.dtype).at[idx].set(vals.astype(x.dtype))
    return out.reshape(x.shape)


# ----------------------------------------------------------------------
# Packed-wire payloads: the materialized buffers behind the formulas.
# ----------------------------------------------------------------------


def pack_leaf(cfg: CompressionConfig, x, key):
    """Materialize one tensor's uplink payload as a tuple of arrays
    whose total byte size equals ``leaf_wire_bytes`` exactly:

    - int8: (int8 codes (n,), fp32 scale ())          -> n + 4 bytes
    - int4: (int8 nibble bytes ((n+1)//2,), scale ()) -> (n+1)//2 + 4
    - topk: (fp32 values (k,), int32 indices (k,))    -> 8k bytes
    """
    from repro.kernels import wire_pack

    if cfg.kind == "topk":
        return topk_select(x, cfg.topk_frac)
    bits = _BITS[cfg.kind]
    scale = leaf_scale(x, bits)
    flat = x.astype(jnp.float32).reshape(-1)
    # stochastic rounding draws in-kernel (threefry of the key words +
    # flat position — bit-identical to the historical streamed
    # jax.random.uniform field, which never touches HBM anymore)
    if cfg.stochastic:
        payload = wire_pack.quantize_pack_keyed(flat, scale, _key_words(key), bits)
    else:
        payload = wire_pack.quantize_pack(flat, scale, None, bits)
    return payload, scale


def unpack_leaf(cfg: CompressionConfig, payload, shape, dtype=jnp.float32):
    """Reverse of ``pack_leaf``: payload -> dequantized tensor. Equals
    the in-graph quantize->dequantize of the same tensor bit-exactly
    (same codes, same dequant arithmetic)."""
    from repro.kernels import wire_pack

    size = int(math.prod(shape)) if shape else 1
    if cfg.kind == "topk":
        vals, idx = payload
        return wire_pack.topk_unpack(vals, idx, size).reshape(shape).astype(dtype)
    data, scale = payload
    codes = wire_pack.nibble_unpack(data, size) if cfg.kind == "int4" else data
    return wire_pack.dequantize(codes.reshape(-1), scale).reshape(shape).astype(dtype)


def packed_leaf_bytes(payload) -> int:
    """Byte size of a materialized payload (host-side Python int) —
    property-tested equal to ``leaf_wire_bytes`` for every kind."""
    return sum(int(a.size) * jnp.dtype(a.dtype).itemsize for a in payload)


def sum_packed_codes(cfg: CompressionConfig, data, size: int, weights=None, axis=None):
    """All-reduce a stack of intN payload buffers *in the code domain*:
    (K, nbytes) payload -> (size,) int32 code sums. ``data`` is the
    wire buffer of ``cfg`` — nibble-packed bytes for a packed int4
    plane, raw int8 codes otherwise.

    This is the packed-form all-reduce of the uplink: int8/int4 codes
    widen to int32, so the server can ``psum`` the widened codes across
    the client mesh axis and dequantize ONCE — valid whenever the
    cohort shares one scale (the per-tensor scales are 4-byte scalars,
    cheap to max-reduce first; see ``shared_leaf_scale``). With
    ``weights`` (int32 per-client example counts n_k — integral by
    data-plane construction, the weight leaves are 0/1 masks) the
    reduction is the example-weighted code sum the paper's aggregator
    needs, still in exact integer arithmetic.

    int32 overflow bound (property-tested in tests/test_code_fastpath.py):
    |sum| <= levels * sum(w_k) (or levels * K unweighted), so int8
    accumulation is exact up to sum(n_k) < 2**31 / 127 = 16,909,320
    examples (clients) per round, int4 up to 2**31 / 7 ~= 306M — far
    above any real cohort; past that, widen to int64 before the psum.

    With ``axis`` (a named mesh axis inside ``shard_map``) the local
    per-shard code sum is followed by a literal ``jax.lax.psum`` over
    that axis — int32 addition is associative and commutative, so the
    sharded total is bit-identical to the single-device reduction and
    the overflow bound above applies to the *global* cohort unchanged.
    """
    from repro.kernels import wire_pack

    if cfg.kind not in _BITS:
        raise ValueError(
            f"sum_packed_codes is the intN code-domain reduction; a "
            f"{cfg.kind!r} payload carries fp32 values, not codes"
        )
    if cfg.kind == "int4" and cfg.packed:
        codes = jax.vmap(lambda b: wire_pack.nibble_unpack(b, size))(data)
    else:
        codes = data
    wide = codes.astype(jnp.int32)
    if weights is None:
        total = wide.sum(axis=0)
    else:
        total = jnp.tensordot(weights.astype(jnp.int32), wide, axes=(0, 0))
    if axis is not None:
        total = jax.lax.psum(total, axis)
    return total


# ----------------------------------------------------------------------
# Code-domain fast path: shared-scale negotiation + in-graph code-sum
# aggregation. Clients never rematerialize fp32 deltas — the round
# engine calls this INSTEAD of compress-then-aggregate whenever the
# plane quantizes under the paper's weighted mean (selected statically
# in repro.core.fedavg, so the fp32 parity graph is untouched).
# ----------------------------------------------------------------------


def shared_leaf_scale(d, pmask, bits: int, axis=None):
    """Negotiate one scale for a (K, ...) client-stacked leaf: each
    client's absmax (masked by participation — dropped clients transmit
    nothing, so they must not coarsen the grid), max-reduced over the
    client axis. With ``axis`` (a named mesh axis inside ``shard_map``,
    where ``d``/``pmask`` hold only this shard's clients) the local max
    is followed by ``jax.lax.pmax`` over that axis — an all-reduce over
    a 4-byte scalar, the cheap half of the negotiation that makes the
    code sums exact. max is associative/commutative and exact in f32,
    so the sharded scale is bit-identical to the single-device one."""
    levels = 2.0 ** (bits - 1) - 1.0
    am = jnp.max(jnp.abs(d.astype(jnp.float32).reshape(d.shape[0], -1)), axis=1)
    m = jnp.max(am * (pmask > 0))
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    scale = m / levels
    return jnp.where(scale > 0, scale, 1.0)


def fastpath_leaf_keys(ckeys, leaf_idx: int):
    """Per-client rounding keys for one leaf: the round's cached client
    key fan-out (one fold_in per client per round, hoisted in the round
    engine) folded with the leaf index."""
    return jax.vmap(lambda ck: jax.random.fold_in(ck, leaf_idx))(ckeys)


def code_domain_aggregate(
    cfg: CompressionConfig, deltas: PyTree, n_k, pmask, ckeys, axis=None
) -> PyTree:
    """Example-weighted mean of K quantized client deltas without ever
    rematerializing fp32 per-client tensors:

        per leaf:  absmax_k --max-reduce--> shared scale s
                   fused quantize(+pack) per client  -> intN payload
                   sum_packed_codes (int32, weighted by n_k)  -> csum
                   wbar = csum * (s / n)          [ONE dequant, server]

    vs the slow path's K dequantized fp32 trees reduced by an fp32
    tensordot. With the shared scale the integer code sum is *exact*,
    so this equals dequantize-then-weighted-mean up to one final f32
    rounding (bit-exact for equal weights on power-of-two scales;
    property-tested in tests/test_code_fastpath.py). Wire accounting is
    untouched: the payload per client is byte-identical to
    ``pack_leaf`` (codes against a shared scale instead of its own —
    same buffer shapes, same ``leaf_wire_bytes``).

    ``topk`` planes aggregate in the payload domain instead: each
    client's (value, index) pairs — exactly the wire payload — go
    through one weighted XLA scatter-add
    (``wire_pack.topk_scatter_add``) into the dense mean, so the slow
    path's K rematerialized dense fp32 trees (and their K-deep
    tensordot) never exist. Dropped clients carry weight n_k = 0, so
    their payloads cancel exactly as in the slow path.

    With ``axis`` (called inside ``shard_map`` where ``deltas``/``n_k``/
    ``pmask``/``ckeys`` hold only this shard's slice of the cohort) the
    scale negotiation pmax-es, the code sum psum-s, and ``n`` psum-s
    over that axis — each reduction is exact (f32 max; int32 add; f32
    add of integer-valued example counts, exact below 2**24), so the
    sharded aggregate is bit-identical to the single-device one and
    every shard returns the same replicated ``wbar``. The topk dense
    sums psum in f32 (bit-identical on a 1-device mesh, tolerance-level
    elsewhere — same contract as the fp32 slow path's reduction order).
    """
    from repro.kernels import wire_pack

    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    n_total = n_k.sum()
    if axis is not None:
        n_total = jax.lax.psum(n_total, axis)
    n = jnp.maximum(n_total, 1.0)
    out = []
    if cfg.kind == "topk":
        for d in leaves:
            K = d.shape[0]
            flat = d.astype(jnp.float32).reshape(K, -1)
            size = flat.shape[1]
            vals, idx = jax.vmap(lambda x: topk_select(x, cfg.topk_frac))(flat)
            dsum = wire_pack.topk_scatter_add(vals, idx, n_k.astype(jnp.float32), size)
            if axis is not None:
                dsum = jax.lax.psum(dsum, axis)
            out.append((dsum / n).reshape(d.shape[1:]))
        return jax.tree_util.tree_unflatten(treedef, out)
    bits = _BITS[cfg.kind]
    w_int = jnp.round(n_k).astype(jnp.int32)
    for li, d in enumerate(leaves):
        K = d.shape[0]
        flat = d.astype(jnp.float32).reshape(K, -1)
        size = flat.shape[1]
        scale = shared_leaf_scale(d, pmask, bits, axis=axis)
        lkeys = fastpath_leaf_keys(ckeys, li)

        def client(x, k, scale=scale):
            if cfg.stochastic:
                kw = _key_words(k)
                if cfg.packed:
                    return wire_pack.quantize_pack_keyed(x, scale, kw, bits)
                return wire_pack.quantize_with_scale_keyed(x, scale, kw, bits)
            if cfg.packed:
                return wire_pack.quantize_pack(x, scale, None, bits)
            return wire_pack.quantize_with_scale(x, scale, None, bits)

        payload = jax.vmap(client)(flat, lkeys)
        csum = sum_packed_codes(cfg, payload, size, weights=w_int, axis=axis)
        out.append((csum.astype(jnp.float32) * (scale / n)).reshape(d.shape[1:]))
    return jax.tree_util.tree_unflatten(treedef, out)


def code_domain_aggregate_ef(
    cfg: CompressionConfig, deltas: PyTree, n_k, pmask, ckeys, ef: PyTree, axis=None
) -> tuple[PyTree, PyTree]:
    """Error-feedback twin of ``code_domain_aggregate``: compresses each
    client's ``delta + residual``, aggregates in the code/payload
    domain, and returns ``(wbar, new_ef)`` with the EF21 residual
    update computed from the *transmitted codes' dequant* — never from
    a separately compressed fp32 tree, so what feeds the residual is
    bit-identical to what went on the wire.

    - intN: new_ef = target - codes * shared_scale for participants
      (codes from the same fused keyed kernel whose int32 sum builds
      wbar); dropped clients keep their old residual untouched.
    - topk: the transmitted coordinates are sent *exactly*, so the
      residual is just the target with its selected coordinates zeroed
      (one in-place scatter per client — no dense subtraction).

    Aggregation and scale negotiation shard over ``axis`` exactly as in
    ``code_domain_aggregate``; the residual update is purely local to
    each shard's clients (ef is sharded along the client axis), so no
    extra collectives appear.
    """
    from repro.kernels import wire_pack

    leaves, treedef = jax.tree_util.tree_flatten(deltas)
    ef_leaves = jax.tree_util.tree_flatten(ef)[0]
    n_total = n_k.sum()
    if axis is not None:
        n_total = jax.lax.psum(n_total, axis)
    n = jnp.maximum(n_total, 1.0)
    out, ef_out = [], []
    if cfg.kind == "topk":
        for d, e in zip(leaves, ef_leaves):
            K = d.shape[0]
            target = d.astype(jnp.float32) + e.astype(jnp.float32)
            flat = target.reshape(K, -1)
            size = flat.shape[1]
            vals, idx = jax.vmap(lambda x: topk_select(x, cfg.topk_frac))(flat)
            dsum = wire_pack.topk_scatter_add(vals, idx, n_k.astype(jnp.float32), size)
            if axis is not None:
                dsum = jax.lax.psum(dsum, axis)
            out.append((dsum / n).reshape(d.shape[1:]))
            resid = jax.vmap(lambda t, i: t.at[i].set(0.0))(flat, idx).reshape(d.shape)
            sel = pmask.reshape((K,) + (1,) * (d.ndim - 1)) > 0
            ef_out.append(jnp.where(sel, resid, e).astype(e.dtype))
        return (jax.tree_util.tree_unflatten(treedef, out),
                jax.tree_util.tree_unflatten(treedef, ef_out))
    bits = _BITS[cfg.kind]
    w_int = jnp.round(n_k).astype(jnp.int32)
    for li, (d, e) in enumerate(zip(leaves, ef_leaves)):
        K = d.shape[0]
        target = d.astype(jnp.float32) + e.astype(jnp.float32)
        flat = target.reshape(K, -1)
        size = flat.shape[1]
        scale = shared_leaf_scale(target, pmask, bits, axis=axis)
        lkeys = fastpath_leaf_keys(ckeys, li)

        def client(x, k, scale=scale):
            if cfg.stochastic:
                return wire_pack.quantize_with_scale_keyed(x, scale, _key_words(k), bits)
            return wire_pack.quantize_with_scale(x, scale, None, bits)

        codes = jax.vmap(client)(flat, lkeys)
        if cfg.packed and bits == 4:
            # materialize the nibble-packed wire buffer (byte accounting's
            # payload) and reduce through it — pack->unpack is the
            # identity on codes, so csum is unchanged
            payload = jax.vmap(wire_pack.nibble_pack)(codes)
            csum = sum_packed_codes(cfg, payload, size, weights=w_int, axis=axis)
        else:
            csum = sum_packed_codes(cfg, codes, size, weights=w_int, axis=axis)
        out.append((csum.astype(jnp.float32) * (scale / n)).reshape(d.shape[1:]))
        resid = (flat - codes.astype(jnp.float32) * scale).reshape(d.shape)
        sel = pmask.reshape((K,) + (1,) * (d.ndim - 1)) > 0
        ef_out.append(jnp.where(sel, resid, e).astype(e.dtype))
    return (jax.tree_util.tree_unflatten(treedef, out),
            jax.tree_util.tree_unflatten(treedef, ef_out))


# ----------------------------------------------------------------------
# In-graph compressors: delta -> dequantized delta (same shape/dtype).
# ----------------------------------------------------------------------


def make_compressor(cfg: CompressionConfig):
    """Returns compress(delta_tree, key) -> delta_tree (dequantized).

    One independent RNG key per leaf; the caller supplies a per-client
    key (vmapped over the K axis), so every client quantizes its own
    delta with its own noise — exactly the production wire protocol.
    With ``cfg.packed`` the payload is additionally materialized and
    round-tripped through the wire_pack kernels (bit-identical output,
    but the packed buffer the byte formulas price actually exists in
    the graph and is what a deployment would all-reduce).
    """
    if cfg.kind == "none":
        return lambda tree, key: tree
    if cfg.kind == "topk" and not cfg.packed:
        return lambda tree, key: jax.tree.map(lambda x: _topk_leaf(x, cfg.topk_frac), tree)

    if cfg.packed:

        def leaf_fn(x, k):
            return unpack_leaf(cfg, pack_leaf(cfg, x, k), x.shape, x.dtype)

    else:
        bits = _BITS[cfg.kind]

        def leaf_fn(x, k):
            return _quantize_leaf(x, k, bits, cfg.stochastic)

    def compress(tree, key):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        keys = jax.random.split(key, len(leaves))
        out = [leaf_fn(x, k) for x, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return compress
