"""Binary hyperdimensional-computing algebra.

Hypervectors (HVs) are d-dimensional pseudo-random binary vectors (d >= 512 in this
paper; classically d ~ 10,000). We keep two representations:

* **unpacked**: ``uint8`` arrays of {0, 1} — convenient for algebra and majority.
* **packed**: ``uint32`` arrays of d/32 words — used by the Pallas Hamming kernel,
  mirroring how an IMC macro would store a row.

All ops are pure jnp and jit-friendly. Bipolar view {-1,+1} = 2*hv-1 is used where a
matmul (MXU) formulation is preferable.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

WORD = 32


def random_hv(key: jax.Array, num: int, dim: int) -> jax.Array:
    """`num` i.i.d. random binary hypervectors of dimension `dim` (uint8 {0,1})."""
    return jax.random.bernoulli(key, 0.5, (num, dim)).astype(jnp.uint8)


def bind(a: jax.Array, b: jax.Array) -> jax.Array:
    """Binding = elementwise XOR. Involutive, similarity-preserving."""
    return jnp.bitwise_xor(a, b)


def permute(hv: jax.Array, shift: int | jax.Array) -> jax.Array:
    """Cyclic permutation rho^shift along the last (dimension) axis."""
    return jnp.roll(hv, shift, axis=-1)


def permute_batch(hvs: jax.Array, shifts: jax.Array) -> jax.Array:
    """Apply per-row cyclic shifts: hvs [M, d], shifts [M] -> [M, d].

    Used for the paper's *permuted bundling*: transmitter m applies rho^m so each
    TX has a distinguishable signature and the shared codebook decorrelates.
    """
    d = hvs.shape[-1]
    idx = (jnp.arange(d)[None, :] - shifts[:, None]) % d
    return jnp.take_along_axis(hvs, idx.astype(jnp.int32), axis=-1)


def majority(hvs: jax.Array, key: jax.Array | None = None) -> jax.Array:
    """Bit-wise logical majority (the HDC *bundling* op) over axis 0.

    hvs: [M, ..., d] uint8 in {0,1}.  Even-M tie convention (repo-wide): ties
    resolve to 0, i.e. strict majority ``count*2 > M`` — the same rule as the
    scale-out ``tally > 0`` psum path, `kernels.majority`, and
    `majority_packed`.  Passing `key` opts into the classical randomized
    tie-break (a random hypervector decides ties); that variant never runs on
    the distributed serve path.
    """
    m = hvs.shape[0]
    counts = jnp.sum(hvs.astype(jnp.int32), axis=0)
    if m % 2 == 1 or key is None:
        return (counts * 2 > m).astype(jnp.uint8)
    tie = jax.random.bernoulli(key, 0.5, counts.shape)
    return jnp.where(counts * 2 == m, tie, counts * 2 > m).astype(jnp.uint8)


def hamming_similarity(q: jax.Array, protos: jax.Array) -> jax.Array:
    """Normalized similarity in [0,1]: 1 - hamming/d.

    q: [..., d]; protos: [C, d] -> [..., C].
    Implemented as a bipolar dot product so that on TPU it maps to the MXU —
    the direct analogue of the IMC crossbar MVM of the paper (Fig. 2).
    """
    d = q.shape[-1]
    qb = (2.0 * q.astype(jnp.float32) - 1.0)
    pb = (2.0 * protos.astype(jnp.float32) - 1.0)
    dots = qb @ pb.T  # in [-d, d]; = d - 2*hamming
    return (dots + d) / (2.0 * d)


def flip_bits(key: jax.Array, hv: jax.Array, ber: jax.Array | float) -> jax.Array:
    """Binary symmetric channel: flip each bit independently w.p. `ber`.

    This is how the paper injects the wireless OTA error figures into the HDC
    chain ("errors ... are modeled as uncorrelated bit flips over the query
    hypervectors").
    """
    flips = jax.random.bernoulli(key, ber, hv.shape)
    return jnp.bitwise_xor(hv, flips.astype(jnp.uint8))


def flip_bits_per_rx(key: jax.Array, hv: jax.Array, ber_per_rx: jax.Array) -> jax.Array:
    """Per-receiver BSC: hv [..., d] broadcast against ber_per_rx [N] -> [N, ..., d]."""
    n = ber_per_rx.shape[0]
    p = ber_per_rx.reshape((n,) + (1,) * hv.ndim)
    flips = jax.random.bernoulli(key, p, (n,) + hv.shape)
    return jnp.bitwise_xor(hv[None], flips.astype(jnp.uint8))


# ---------------------------------------------------------------------------
# packed representation
# ---------------------------------------------------------------------------

def pack(hv: jax.Array) -> jax.Array:
    """Pack uint8 {0,1} [..., d] -> uint32 [..., d//32] (little-endian bit order)."""
    d = hv.shape[-1]
    assert d % WORD == 0, f"dim {d} must be a multiple of {WORD}"
    w = hv.reshape(hv.shape[:-1] + (d // WORD, WORD)).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32))
    return jnp.sum(w * weights, axis=-1).astype(jnp.uint32)


def unpack(packed: jax.Array, dim: int) -> jax.Array:
    """Inverse of `pack`."""
    shifts = jnp.arange(WORD, dtype=jnp.uint32)
    bits = (packed[..., None] >> shifts) & jnp.uint32(1)
    return bits.reshape(packed.shape[:-1] + (dim,)).astype(jnp.uint8)


def hamming_distance_packed(q: jax.Array, protos: jax.Array) -> jax.Array:
    """Packed-word Hamming distance via XOR + popcount.

    q: [..., W] uint32, protos: [C, W] uint32 -> int32 [..., C].
    The pure-jnp oracle for kernels/hamming.
    """
    x = jnp.bitwise_xor(q[..., None, :], protos)  # [..., C, W]
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


# ---------------------------------------------------------------------------
# packed algebra — the production fast path
#
# Every op below is bit-exact against its unpacked counterpart on the same PRNG
# stream (property-tested in tests/test_hdc_core.py): the packed serve pipeline
# can therefore be verified prediction-identical to the unpacked one while
# moving d/8 bytes per hypervector instead of d (uint8) or 4d (fp32 bipolar).
# ---------------------------------------------------------------------------

_FULL = jnp.uint32(0xFFFFFFFF)


def random_hv_packed(key: jax.Array, num: int, dim: int) -> jax.Array:
    """`num` i.i.d. random hypervectors drawn directly as uint32 words.

    [num, dim//32] — each of the d bits is an independent fair coin, exactly as
    `random_hv`, but the PRNG emits 32 bits per word instead of one uint8 per
    bit (no unpacked intermediate; a *different* stream than pack(random_hv)).
    """
    assert dim % WORD == 0, f"dim {dim} must be a multiple of {WORD}"
    return jax.random.bits(key, (num, dim // WORD), dtype=jnp.uint32)


def bind_packed(a: jax.Array, b: jax.Array) -> jax.Array:
    """Packed binding: word-wise XOR (identical to `bind`; packing commutes)."""
    return jnp.bitwise_xor(a, b)


def permute_packed(hvp: jax.Array, shift: int | jax.Array) -> jax.Array:
    """Cyclic permutation rho^shift on packed words [..., W].

    Equals pack(permute(unpack(hvp))): a word-level roll by shift//32 plus a
    bit-level shift by shift%32 with cross-word carry from the previous word
    (little-endian bit order, so `<<` moves bits toward higher dim indices).
    Accepts traced shifts (the per-TX signatures inside shard_map bodies).
    """
    w = hvp.shape[-1]
    d = w * WORD
    s = jnp.asarray(shift) % d
    ws = (s // WORD).astype(jnp.int32)
    bs = (s % WORD).astype(jnp.uint32)
    rolled = jnp.roll(hvp, ws, axis=-1)
    prev = jnp.roll(rolled, 1, axis=-1)
    # (WORD - bs) % WORD keeps the shift amount in [0, 31] even at bs == 0
    # (a >> 32 is undefined); the where() discards the bogus bs == 0 lane.
    carry = jnp.where(bs == 0, jnp.uint32(0), prev >> ((WORD - bs) % WORD))
    return ((rolled << bs) | carry).astype(jnp.uint32)


def permute_batch_packed(hvps: jax.Array, shifts: jax.Array) -> jax.Array:
    """Per-row cyclic shifts on packed rows: hvps [M, W], shifts [M] -> [M, W]."""
    return jax.vmap(permute_packed)(hvps, shifts)


def _bitsliced_counts(hvs: jax.Array) -> list[jax.Array]:
    """Bit-planes (LSB first) of the per-bit-lane popcount over axis 0.

    hvs: [M, ..., W] uint32. A carry-save ripple adder: each transmitter's word
    is added into a bit-sliced binary counter, so counting M inputs costs
    O(M log M) word ops with no unpacking — every one of the 32 lanes of a word
    is counted in parallel.
    """
    planes: list[jax.Array] = []
    for k in range(hvs.shape[0]):
        carry = hvs[k]
        for i in range(len(planes)):
            planes[i], carry = planes[i] ^ carry, planes[i] & carry
        if len(planes) < (k + 1).bit_length():  # else carry is provably 0
            planes.append(carry)
    return planes


def _bitsliced_gt(planes: list[jax.Array], t: int) -> tuple[jax.Array, jax.Array]:
    """(count > t, count == t) per bit lane, from LSB-first count planes."""
    gt = jnp.zeros_like(planes[0])
    eq = jnp.full_like(planes[0], _FULL)
    for i in reversed(range(len(planes))):
        tb = _FULL if (t >> i) & 1 else jnp.uint32(0)
        gt = gt | (eq & planes[i] & ~tb)
        eq = eq & ~(planes[i] ^ tb)
    return gt, eq


def majority_packed(hvs: jax.Array, key: jax.Array | None = None) -> jax.Array:
    """Packed majority bundling over axis 0: [M, ..., W] uint32 -> [..., W].

    Bit-sliced carry-save adder + bitwise comparator — no unpacking. Same tie
    convention as `majority` (even-M ties -> 0; `key` opts into the randomized
    tie-break, bit-exact against `majority(key=...)` on the same stream).
    """
    m = hvs.shape[0]
    planes = _bitsliced_counts(hvs)
    gt, eq = _bitsliced_gt(planes, m // 2)
    if m % 2 == 1 or key is None:
        return gt
    d = hvs.shape[-1] * WORD
    tie = pack(jax.random.bernoulli(key, 0.5, hvs.shape[1:-1] + (d,)).astype(jnp.uint8))
    return gt | (eq & tie)


def _bitsliced_gt_traced(planes: list[jax.Array], t: jax.Array) -> jax.Array:
    """(count > t) per bit lane for a TRACED uint32 threshold `t`.

    The comparator of `_bitsliced_gt` with the threshold bits materialized as
    0/all-ones lane masks (the `bernoulli_words` trick), so the threshold may
    depend on traced data — e.g. the live member count of a masked majority.
    `t` must broadcast against the planes and satisfy t < 2^len(planes).
    """
    gt = jnp.zeros_like(planes[0])
    eq = jnp.full_like(planes[0], _FULL)
    for i in reversed(range(len(planes))):
        tb = jnp.uint32(0) - ((t >> jnp.uint32(i)) & jnp.uint32(1))  # 0 or all-ones
        gt = gt | (eq & planes[i] & ~tb)
        eq = eq & ~(planes[i] ^ tb)
    return gt


def majority_packed_masked(hvs: jax.Array, mask: jax.Array) -> jax.Array:
    """Strict packed majority over the MASKED subset of axis 0.

    hvs: [M, ..., W] uint32; mask: [M] (or any prefix of hvs' leading dims)
    bool -> [..., W]. A masked-out member contributes exact zero words to the
    carry-save counter, and the strict-majority threshold compares against the
    *traced* live count n = sum(mask): ``count*2 > n  <=>  count > n//2`` for
    either parity, so even-n ties resolve to 0 exactly like `majority_packed`.
    An empty selection returns all-zero words. jit-safe for traced masks — the
    multi-centroid k-means update recomputes every centroid from its current
    assignment without a recompile per iteration.
    """
    m = hvs.shape[0]
    assert m >= 1 and mask.shape[0] == m, (hvs.shape, mask.shape)
    mask = jnp.broadcast_to(
        mask.reshape(mask.shape + (1,) * (hvs.ndim - mask.ndim)),
        hvs.shape[:-1] + (1,),
    )
    mw = jnp.uint32(0) - mask.astype(jnp.uint32)  # 0 or all-ones per member
    planes = _bitsliced_counts(hvs & mw)
    n = jnp.sum(mask.astype(jnp.int32), axis=0)   # [..., 1] live count
    # n//2 <= M//2 < 2^len(planes) == 2^bit_length(M): threshold always fits
    return _bitsliced_gt_traced(planes, (n // 2).astype(jnp.uint32))


def bernoulli_threshold(p: jax.Array | float, precision: int = 16) -> jax.Array:
    """The `precision`-bit uniform's threshold of `bernoulli_words`:
    round(p * 2^precision) as uint32, clipped to [0, 2^precision - 1]."""
    return jnp.clip(
        jnp.round(jnp.asarray(p, jnp.float32) * (2**precision)), 0, 2**precision - 1
    ).astype(jnp.uint32)


def bernoulli_words(
    key: jax.Array, p: jax.Array | float, shape: tuple[int, ...], precision: int = 16
) -> jax.Array:
    """Bernoulli(p) bit masks drawn directly as packed uint32 words.

    Draws `precision` fair bit-planes and compares the per-lane `precision`-bit
    uniform against round(p * 2^precision) with a bit-sliced comparator: the
    whole mask costs `precision` random bits per output bit instead of the 32
    the unpacked bernoulli (uint32 -> f32 uniform -> compare) pays, and never
    materializes an unpacked intermediate. p is quantized to 2^-precision —
    the packed serve path's "bitplane" noise mode (NOT bit-exact against
    `flip_bits`; use `flip_bits_packed` when identity matters).
    """
    planes = jax.random.bits(key, (precision,) + tuple(shape), dtype=jnp.uint32)
    t = bernoulli_threshold(p, precision)
    lt = jnp.zeros(shape, jnp.uint32)
    eq = jnp.full(shape, _FULL, jnp.uint32)
    for i in reversed(range(precision)):
        tb = jnp.uint32(0) - ((t >> jnp.uint32(i)) & jnp.uint32(1))  # 0 or all-ones
        lt = lt | (eq & ~planes[i] & tb)
        eq = eq & ~(planes[i] ^ tb)
    return lt


def flip_bits_packed(key: jax.Array, hvp: jax.Array, ber: jax.Array | float) -> jax.Array:
    """Packed BSC, bit-exact against `flip_bits` on the same key.

    The Bernoulli mask is generated per 32-lane block in the unpacked layout
    (the same draw `flip_bits` makes) and packed before the XOR, so
    unpack(flip_bits_packed(k, pack(x), p)) == flip_bits(k, x, p) exactly.
    """
    d = hvp.shape[-1] * WORD
    flips = jax.random.bernoulli(key, ber, hvp.shape[:-1] + (d,))
    return jnp.bitwise_xor(hvp, pack(flips.astype(jnp.uint8)))


def flip_bits_per_rx_packed(
    key: jax.Array, hvp: jax.Array, ber_per_rx: jax.Array
) -> jax.Array:
    """Per-receiver packed BSC: hvp [..., W] x ber [N] -> [N, ..., W].

    Bit-exact against `flip_bits_per_rx` on the same key (same mask draw,
    packed before the XOR).
    """
    n = ber_per_rx.shape[0]
    d = hvp.shape[-1] * WORD
    p = ber_per_rx.reshape((n,) + (1,) * hvp.ndim)
    flips = jax.random.bernoulli(key, p, (n,) + hvp.shape[:-1] + (d,))
    return jnp.bitwise_xor(hvp[None], pack(flips.astype(jnp.uint8)))
