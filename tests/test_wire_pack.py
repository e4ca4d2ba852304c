"""Packed-wire plane: Pallas kernels vs jnp oracles, byte-exact payload
sizes vs the Python formulas, and bit-exact round-trips against the
in-graph quantize->dequantize path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import (
    CompressionConfig,
    leaf_wire_bytes,
    make_compressor,
    pack_leaf,
    packed_leaf_bytes,
    quantize_codes,
    sum_packed_codes,
    unpack_leaf,
)
from repro.kernels import ref, wire_pack

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - deterministic fallback below
    HAVE_HYPOTHESIS = False


def _codes(n, lo=-7, hi=7, seed=0):
    return jnp.asarray(np.random.default_rng(seed).integers(lo, hi + 1, n),
                       jnp.int8)


# ------------------------------------------------------- kernel parity

@pytest.mark.parametrize("n", [1, 2, 3, 101, 512, 1025, 2048])
def test_nibble_pack_kernel_matches_ref(n):
    codes = _codes(n, seed=n)
    out = wire_pack.nibble_pack_pallas(codes, interpret=True)
    expect = ref.nibble_pack_ref(codes)
    assert out.shape == ((n + 1) // 2,)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@pytest.mark.parametrize("n", [1, 2, 3, 101, 512, 1025, 2048])
def test_nibble_unpack_kernel_matches_ref_and_roundtrips(n):
    codes = _codes(n, seed=1000 + n)
    packed = ref.nibble_pack_ref(codes)
    out = wire_pack.nibble_unpack_pallas(packed, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.nibble_unpack_ref(packed, n)))
    # pack -> unpack is the identity on int4 codes
    np.testing.assert_array_equal(np.asarray(out), np.asarray(codes))


@pytest.mark.parametrize("n", [1, 7, 300, 1024])
def test_dequantize_kernel_matches_ref(n):
    codes = _codes(n, lo=-127, hi=127, seed=n)
    scale = jnp.float32(0.0173)
    out = wire_pack.dequantize_pallas(codes, scale, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(ref.dequantize_ref(codes, scale)))


def _dense_scatter(vals, idx, n):
    dense = np.zeros((n,), np.float32)
    dense[np.asarray(idx)] = np.asarray(vals)
    return dense


@pytest.mark.parametrize("n,k", [(8, 1), (64, 5), (256, 32), (1, 1), (100, 11),
                                 (4096, 200), (16, 16), (2048, 64), (513, 7),
                                 (65536, 3277)])
def test_topk_unpack_matches_manual(n, k):
    """The public top-k unpack (an XLA scatter on every backend) equals
    a host-side dense scatter — including a full payload (k == n), the
    size-1 degenerate and a wide leaf."""
    rng = np.random.default_rng(k * 100 + n)
    vals = jnp.asarray(rng.normal(size=(k,)), jnp.float32)
    idx = jnp.asarray(rng.choice(n, size=k, replace=False), jnp.int32)
    out = wire_pack.topk_unpack(vals, idx, n)
    assert out.shape == (n,)
    np.testing.assert_array_equal(np.asarray(out), _dense_scatter(vals, idx, n))


def test_topk_unpack_boundary_indices():
    """Entries at the first and last positions and across 32-wide
    boundaries land where they belong."""
    n = 128
    idx = jnp.asarray([0, 31, 32, 63, 64, 127], jnp.int32)
    vals = jnp.arange(1.0, 7.0, dtype=jnp.float32)
    out = wire_pack.topk_unpack(vals, idx, n)
    np.testing.assert_array_equal(np.asarray(out), _dense_scatter(vals, idx, n))


# ----------------------------------------------- fused quantize -> pack

# the PR 3 ulp regression values: |x| / (|x| / levels) > levels in f32
_BOUNDARY = {8: 2.770888566970825, 4: 7.646292686462402}


def _fused_case(n, bits, seed, boundary=False, stochastic=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n,)).astype(np.float32)
    if boundary:
        x[0] = _BOUNDARY[bits]
        x[1:] = x[1:] * 0.1
    return jnp.asarray(x)


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("n", [1, 2, 3, 33, 101, 512, 1025])
@pytest.mark.parametrize("stochastic", [True, False])
def test_fused_quantize_pack_matches_composition(bits, n, stochastic):
    """The fused kernel == quantize_codes + pack_leaf's historical
    composition, code for code and byte for byte — odd sizes, size-1,
    both rounding modes."""
    from repro.core.compression import leaf_scale, _rounding_field

    x = _fused_case(n, bits, seed=n * bits)
    key = jax.random.PRNGKey(n + bits)
    scale = leaf_scale(x, bits)
    u = _rounding_field(key, x.shape, stochastic)
    codes_ref = ref.quantize_codes_with_scale_ref(
        x, scale, u, 2.0 ** (bits - 1) - 1.0)
    # dispatch wrapper (oracle on CPU)
    codes = wire_pack.quantize_with_scale(x, scale, u, bits)
    np.testing.assert_array_equal(np.asarray(codes), np.asarray(codes_ref))
    # pallas kernels in interpret mode
    k_codes = wire_pack.quantize_with_scale_pallas(x, scale, u, bits,
                                                   interpret=True)
    np.testing.assert_array_equal(np.asarray(k_codes), np.asarray(codes_ref))
    payload_ref = (ref.nibble_pack_ref(codes_ref) if bits == 4 else codes_ref)
    payload = wire_pack.quantize_pack(x, scale, u, bits)
    np.testing.assert_array_equal(np.asarray(payload), np.asarray(payload_ref))
    if bits == 4:
        k_payload = wire_pack.quantize_pack4_pallas(x, scale, u,
                                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(k_payload),
                                      np.asarray(payload_ref))


@pytest.mark.parametrize("bits", [8, 4])
def test_fused_quantize_pack_absmax_boundary_never_wraps(bits):
    """The PR 3 ulp regression case through the FUSED kernel: the
    absmax coordinate must clamp before the rounding draw, or a
    boundary draw quantizes to levels+1 and the int8/nibble cast wraps
    the sign inside the packed buffer."""
    from repro.core.compression import leaf_scale, _rounding_field

    levels = 2 ** (bits - 1) - 1
    x = _fused_case(64, bits, seed=0, boundary=True)
    scale = leaf_scale(x, bits)
    for i in range(20):
        u = _rounding_field(jax.random.PRNGKey(i), x.shape, True)
        codes = np.asarray(wire_pack.quantize_with_scale_pallas(
            x, scale, u, bits, interpret=True))
        assert codes.min() >= -levels and codes.max() <= levels
        assert codes[0] == levels
        if bits == 4:
            packed = wire_pack.quantize_pack4_pallas(x, scale, u,
                                                     interpret=True)
            unpacked = np.asarray(ref.nibble_unpack_ref(packed, 64))
            np.testing.assert_array_equal(unpacked, codes)


# --------------------------------------- payload size == byte formula

_KIND_CFGS = [
    CompressionConfig(kind="int8", packed=True),
    CompressionConfig(kind="int4", packed=True),
    CompressionConfig(kind="topk", topk_frac=0.05, packed=True),
    CompressionConfig(kind="topk", topk_frac=1e-9, packed=True),  # k -> 1
    CompressionConfig(kind="topk", topk_frac=1.0, packed=True),
]


def _assert_payload_bytes(cfg, n, seed=0):
    x = jnp.asarray(np.random.default_rng(seed).normal(size=(n,)), jnp.float32)
    payload = pack_leaf(cfg, x, jax.random.PRNGKey(seed))
    assert packed_leaf_bytes(payload) == leaf_wire_bytes(cfg, n), (cfg.kind, n)


@pytest.mark.parametrize("cfg", _KIND_CFGS, ids=lambda c: f"{c.kind}-{c.topk_frac}")
@pytest.mark.parametrize("n", [1, 2, 3, 33, 101, 4096])
def test_packed_payload_size_equals_formula(cfg, n):
    """The Python byte formula equals the materialized buffer size for
    every kind — including odd-size int4 nibble padding, topk_frac -> 0
    (k floors at 1) and size-1 tensors."""
    _assert_payload_bytes(cfg, n)


if HAVE_HYPOTHESIS:

    @settings(max_examples=40, deadline=None)
    @given(n=st.integers(1, 5000),
           kind=st.sampled_from(["int8", "int4", "topk"]),
           frac=st.floats(1e-9, 1.0))
    def test_packed_payload_size_property(n, kind, frac):
        cfg = CompressionConfig(kind=kind, topk_frac=frac, packed=True)
        _assert_payload_bytes(cfg, n, seed=n % 17)

else:  # deterministic fallback sweep

    @pytest.mark.parametrize("n", [1, 5, 17, 999, 5000])
    @pytest.mark.parametrize("kind,frac", [("int8", 0.05), ("int4", 0.05),
                                           ("topk", 1e-9), ("topk", 0.37),
                                           ("topk", 1.0)])
    def test_packed_payload_size_property(n, kind, frac):
        cfg = CompressionConfig(kind=kind, topk_frac=frac, packed=True)
        _assert_payload_bytes(cfg, n, seed=n % 17)


# ------------------------------------------------- bit-exact roundtrip

TREE = {
    "a": jnp.asarray(np.random.default_rng(0).normal(size=(16, 8)), jnp.float32),
    "b": {"c": jnp.asarray(np.random.default_rng(1).normal(size=(33,)), jnp.float32)},
    "s": jnp.asarray(np.random.default_rng(2).normal(size=(1,)), jnp.float32),
}


@pytest.mark.parametrize("kind,frac", [("int8", 0.05), ("int4", 0.05),
                                       ("topk", 0.05), ("topk", 0.25)])
def test_packed_roundtrip_bit_exact_vs_in_graph(kind, frac):
    """pack -> unpack == in-graph quantize -> dequantize, bit for bit:
    both consume the same codes, so the wire format is a pure re-layout."""
    key = jax.random.PRNGKey(42)
    plain = make_compressor(CompressionConfig(kind=kind, topk_frac=frac))(TREE, key)
    packed = make_compressor(
        CompressionConfig(kind=kind, topk_frac=frac, packed=True))(TREE, key)
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(packed)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_packed_roundtrip_under_jit_and_vmap():
    """The round engine vmaps the compressor over clients; the packed
    path must survive jit+vmap unchanged."""
    X = jnp.asarray(np.random.default_rng(3).normal(size=(4, 16, 8)), jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    compress = make_compressor(CompressionConfig(kind="int4", packed=True))
    plain = make_compressor(CompressionConfig(kind="int4"))
    # both sides jit+vmap so the comparison isolates the wire re-layout
    # (jit-vs-eager would differ by fusion/FMA ulps unrelated to packing)
    out_p = jax.jit(jax.vmap(lambda x, k: compress({"w": x}, k)))(X, keys)
    out_q = jax.jit(jax.vmap(lambda x, k: plain({"w": x}, k)))(X, keys)
    np.testing.assert_array_equal(np.asarray(out_p["w"]), np.asarray(out_q["w"]))


def test_unpack_leaf_restores_shape_and_dtype():
    cfg = CompressionConfig(kind="int4", packed=True)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(7, 3)), jnp.float32)
    payload = pack_leaf(cfg, x, jax.random.PRNGKey(1))
    out = unpack_leaf(cfg, payload, x.shape, x.dtype)
    assert out.shape == x.shape and out.dtype == x.dtype


# ------------------------------------------------ packed-form allreduce

def test_sum_packed_codes_matches_dequantized_sum():
    """With a shared scale, summing the *packed* codes (widened to
    int32) then dequantizing once equals summing the dequantized
    tensors — the packed-form all-reduce of the uplink."""
    rng = np.random.default_rng(9)
    K, n = 6, 64
    # same absmax for every client => identical scales
    X = rng.normal(size=(K, n)).astype(np.float32)
    X[:, 0] = 10.0
    X = jnp.asarray(X)
    keys = jax.random.split(jax.random.PRNGKey(11), K)
    for kind in ("int8", "int4"):
        cfg = CompressionConfig(kind=kind, packed=True)
        payloads = [pack_leaf(cfg, X[i], keys[i]) for i in range(K)]
        scales = np.asarray([p[1] for p in payloads])
        np.testing.assert_allclose(scales, scales[0])
        code_sum = sum_packed_codes(cfg, jnp.stack([p[0] for p in payloads]), n)
        packed_reduce = np.asarray(code_sum, np.float32) * scales[0]
        dense_reduce = sum(
            np.asarray(unpack_leaf(cfg, p, (n,))) for p in payloads)
        np.testing.assert_allclose(packed_reduce, dense_reduce, atol=1e-5)


def test_quantize_codes_range_never_wraps():
    """Codes live in [-levels, levels]: the pre-draw clamp keeps the
    int8 cast from wrapping (an unclamped boundary draw could yield
    levels+1, which int8-wraps to a sign flip in the packed buffer)."""
    rng = np.random.default_rng(13)
    # absmax values chosen so f32 division overshoots the grid boundary
    for a, bits in [(2.770888566970825, 8), (0.26362359523773193, 8),
                    (7.646292686462402, 4), (3.625833749771118, 4)]:
        x = jnp.asarray(np.concatenate([[a], rng.normal(size=63)]), jnp.float32)
        levels = 2 ** (bits - 1) - 1
        for i in range(20):
            codes, _ = quantize_codes(x, jax.random.PRNGKey(i), bits)
            c = np.asarray(codes)
            assert c.min() >= -levels and c.max() <= levels


def test_sum_packed_codes_rejects_topk():
    cfg = CompressionConfig(kind="topk", topk_frac=0.05, packed=True)
    with pytest.raises(ValueError, match="code-domain"):
        sum_packed_codes(cfg, jnp.zeros((2, 3), jnp.float32), 3)


# --------------------------------------------- in-kernel keyed PRNG (PR 10)
# The fast-path client kernels draw their stochastic-rounding uniforms
# from an in-kernel threefry hash of (key words, flat position) — the
# field never exists in HBM. The contract is BIT-parity with streaming
# jax.random.uniform(key, (n,)) in, which these tests pin on the jnp
# oracle, the Pallas kernels (interpret), and the public dispatchers.


@pytest.mark.parametrize("seed", [0, 42, 123456])
@pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 512, 513, 4097])
def test_threefry_uniform_oracle_bit_parity(seed, n):
    key = jax.random.PRNGKey(seed)
    mine = ref.threefry_uniform_ref(key, n)
    theirs = jax.random.uniform(key, (n,))
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))


@pytest.mark.parametrize("n", [1, 2, 65, 513, 4097])
def test_threefry_bits_oracle_matches_jax_random_bits(n):
    """The elementwise hash equals jax.random.bits under the installed
    jax's threefry layout, and a position's bits do not depend on the
    draw size (a size-n draw is a prefix of a larger one)."""
    key = jax.random.PRNGKey(7 * n)
    k0, k1 = key[0].astype(jnp.uint32), key[1].astype(jnp.uint32)
    mine = ref.threefry_random_bits_at(k0, k1, jnp.arange(n, dtype=jnp.uint32))
    theirs = jax.random.bits(key, (n,), jnp.uint32)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    longer = jax.random.bits(key, (n + 3,), jnp.uint32)
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(longer[:n]))


def test_threefry_uniform_oracle_bit_parity_after_fold_in():
    """The production keys are per-client/per-leaf fold_in derivations —
    the oracle must track the full key-derivation chain."""
    key = jax.random.PRNGKey(3)
    for tag in (0, 1, 0x636D70, 917):
        key = jax.random.fold_in(key, tag)
        np.testing.assert_array_equal(
            np.asarray(ref.threefry_uniform_ref(key, 257)),
            np.asarray(jax.random.uniform(key, (257,))))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("n", [1, 64, 513, 1025, 2048])
def test_keyed_quantize_bits_match_streamed_field(bits, n):
    """quantize_with_scale_keyed == quantize_with_scale fed the streamed
    uniform field, code for code — oracle path and Pallas kernel."""
    rng = np.random.default_rng(n + bits)
    x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    key = jax.random.PRNGKey(100 + n)
    levels = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / levels, 1e-8)
    u = jax.random.uniform(key, (n,))
    want = wire_pack.quantize_with_scale(x, scale, u, bits)
    got = wire_pack.quantize_with_scale_keyed(x, scale, key, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    got_k = wire_pack.quantize_with_scale_keyed_pallas(x, scale, key, bits,
                                                       interpret=True)
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("n", [1, 65, 513, 2048])
def test_keyed_pack_matches_streamed_pack(bits, n):
    """The fused keyed pack kernels produce the byte-identical wire
    buffer to the historical streamed-field quantize_pack."""
    rng = np.random.default_rng(7 * n + bits)
    x = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    key = jax.random.PRNGKey(n)
    levels = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(x)) / levels, 1e-8)
    u = jax.random.uniform(key, (n,))
    want = wire_pack.quantize_pack(x, scale, u, bits)
    got = wire_pack.quantize_pack_keyed(x, scale, key, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if bits == 4:
        got_k = wire_pack.quantize_pack4_keyed_pallas(x, scale, key,
                                                      interpret=True)
        np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want))


# ------------------------------------------------- topk scatter-add (PR 10)


@pytest.mark.parametrize("K,k,n", [(1, 1, 8), (3, 5, 64), (4, 16, 1000),
                                   (2, 7, 4096)])
def test_topk_scatter_add_matches_manual(K, k, n):
    """Weighted payload scatter-add: duplicates accumulate, weights
    scale per client — checked against a host-side loop, on the jnp
    oracle and the public dispatcher."""
    rng = np.random.default_rng(K * n + k)
    vals = jnp.asarray(rng.normal(size=(K, k)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (K, k)), jnp.int32)
    w = jnp.asarray(rng.integers(0, 5, (K,)), jnp.float32)
    manual = np.zeros((n,), np.float64)
    for ci in range(K):
        for j in range(k):
            manual[int(idx[ci, j])] += float(w[ci]) * float(vals[ci, j])
    got = ref.topk_scatter_add_ref(vals, idx, w, n)
    np.testing.assert_allclose(np.asarray(got), manual.astype(np.float32),
                               rtol=1e-6, atol=1e-6)
    got_d = wire_pack.topk_scatter_add(vals, idx, w, n)
    np.testing.assert_allclose(np.asarray(got_d), manual.astype(np.float32),
                               rtol=1e-6, atol=1e-6)


def test_topk_scatter_add_dispatcher_zero_weight_cancels():
    """A dropped client (weight 0) contributes nothing even though its
    payload is present — the fast path's cohort-drop contract."""
    vals = jnp.asarray([[5.0, 5.0], [1.0, 2.0]], jnp.float32)
    idx = jnp.asarray([[0, 1], [1, 2]], jnp.int32)
    w = jnp.asarray([0.0, 3.0], jnp.float32)
    out = np.asarray(wire_pack.topk_scatter_add(vals, idx, w, 4))
    np.testing.assert_allclose(out, [0.0, 3.0, 6.0, 0.0])
