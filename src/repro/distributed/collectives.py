"""Collectives implementing the paper's OTA majority as mesh operations.

The paper's observation, transplanted to a TPU pod: *a reduce-then-broadcast of
binary data is one collective, and it may be lossy*. On the wireless chip the
superposition happens in the channel; on a pod the same semantics is an all-reduce
whose payload is 1 bit/element (sent as ±1) followed by a sign, with an optional
per-receiver binary-symmetric channel modelling the measured OTA BER.

These run inside ``compat.shard_map`` bodies (manual axes). The float variant
(``sign_allreduce``) is the majority-vote signSGD aggregation used by the
``sign_majority`` gradient-compression mode of the trainer — the beyond-paper
application of the same collective to data-parallel LM training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hypervector as hv


def ota_noise(key: jax.Array, bits: jax.Array, ber, axis_name: str | None = None) -> jax.Array:
    """Binary symmetric channel at rate `ber` on uint8 {0,1} bits.

    When `axis_name` is given, the key is folded with this device's index along
    that axis so every receiver sees an *independent* noisy copy — the paper's
    "each IMC core receives a slightly different version of Q".
    """
    if axis_name is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    flips = jax.random.bernoulli(key, ber, bits.shape)
    return jnp.bitwise_xor(bits, flips.astype(bits.dtype))


def ota_noise_packed(
    key: jax.Array,
    words: jax.Array,
    ber,
    axis_name: str | None = None,
    mode: str = "exact",
    planes: int = 16,
) -> jax.Array:
    """BSC on bit-packed uint32 words [..., W] — the packed serve path's channel.

    mode "exact": the flip mask is the same Bernoulli draw `ota_noise` makes
    (generated per 32-lane block, then packed), so the packed pipeline is
    bit-identical to the unpacked one on the same key. mode "bitplane": the
    mask is drawn directly as uint32 words via a bit-sliced `planes`-plane
    comparator (`hv.bernoulli_words`) — `planes` random bits per mask bit
    instead of 32, and no unpacked intermediate, at 2^-planes BER quantization;
    the production choice when replaying the unpacked stream doesn't matter.
    """
    if axis_name is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    if mode == "exact":
        return hv.flip_bits_packed(key, words, ber)
    if mode == "bitplane":
        return jnp.bitwise_xor(
            words, hv.bernoulli_words(key, ber, words.shape, precision=planes)
        )
    raise ValueError(f"unknown packed noise mode {mode!r}")


# ---------------------------------------------------------------------------
# guard-bit packed vote all-reduce
#
# The int8 vote psum of the OTA serve path sends 1 byte per hypervector
# dimension even though the tally only spans [-S*e_per, S*e_per]. Packing
# several votes per uint32 lane with guard bits makes the SAME reduction cost
# 32/(8*k) of the wire bytes: bias each vote to non-negative, give every field
# ceil(log2(2*S*e_per + 1)) bits so the summed field can never overflow into
# its neighbour, run ONE uint32 psum, unpack, un-bias. The tally is
# bit-identical to the int8 psum by construction (psum of packed fields ==
# packed psum of fields; property-tested in tests/test_distributed.py).
# ---------------------------------------------------------------------------


def vote_field_spec(
    group_size: int, e_per: int = 1, pow2_fields: bool = False,
    n_active: int | None = None,
) -> tuple[int, int]:
    """(field_bits, fields_per_lane) for guard-bit packed vote reduction.

    Each participant contributes a vote in [-e_per, e_per]; `group_size`
    participants sum over the reduce axis, so the biased per-field tally spans
    [0, 2*group_size*e_per] and needs ``field_bits = ceil(log2(span + 1))``
    bits. ``k = 32 // field_bits`` fields fit one uint32 lane. With
    `pow2_fields` k is rounded down to a power of two (the reduce-scatter leg
    needs the lane count to tile evenly over the mesh axis).

    `n_active` opts into **active-slot-aware** fields: when only M of the
    group's slots actually vote (the OTA serve's abstaining encoder slots),
    the tally spans [-M, M] regardless of how wide the mesh axis is, so the
    field only needs ``ceil(log2(2*M + 1))`` bits. At S=16/e_per=1/M=3 that is
    3-bit fields (k=10, a ~2.5x wire cut over int8) where S-sized guards gave
    6-bit fields (k=5, 1.25x). Callers must then bias each column by its OWN
    active count (`local_active` in the collectives below), not by e_per.
    """
    span = 2 * (group_size * e_per if n_active is None else n_active)
    fbits = max(1, span.bit_length())
    k = 32 // fbits
    assert k >= 1, f"vote span {span} does not fit a uint32 lane"
    if pow2_fields:
        k = 1 << (k.bit_length() - 1)
    return fbits, k


def _pack_vote_fields(votes: jax.Array, bias, fbits: int, k: int) -> jax.Array:
    """Bias int votes [..., d] by `bias` (non-negative) and pack k fields per
    uint32 lane.

    `bias` is this column's per-field offset: e_per for slot-blind packing, or
    the column's active-voter count (possibly traced) for slot-aware packing.
    d is padded to a multiple of k with zero votes (which bias to `bias` and
    stay within the field's guard bits; sliced away after unpacking). Field i
    of a lane holds element lane*k + i at bit offset i*fbits.
    """
    d = votes.shape[-1]
    pad = (-d) % k
    bias = jnp.asarray(bias, jnp.int32)
    biased = (votes.astype(jnp.int32) + bias).astype(jnp.uint32)
    if pad:
        fill = jnp.broadcast_to(
            bias.astype(jnp.uint32), votes.shape[:-1] + (pad,)
        )
        biased = jnp.concatenate([biased, fill], axis=-1)
    blocks = biased.reshape(biased.shape[:-1] + (-1, k))
    shifts = (jnp.arange(k, dtype=jnp.uint32) * jnp.uint32(fbits))
    return jnp.sum(blocks << shifts, axis=-1, dtype=jnp.uint32)


def _unpack_vote_fields(
    lanes: jax.Array, d: int, bias: int, fbits: int, k: int
) -> jax.Array:
    """Inverse of `_pack_vote_fields` after the reduction: int32 tally [..., d].

    `bias` is the accumulated per-field offset (group_size * e_per after a full
    all-reduce or reduce-scatter over the group).
    """
    shifts = (jnp.arange(k, dtype=jnp.uint32) * jnp.uint32(fbits))
    mask = jnp.uint32((1 << fbits) - 1)
    fields = (lanes[..., None] >> shifts) & mask
    flat = fields.reshape(lanes.shape[:-1] + (lanes.shape[-1] * k,))
    return flat[..., :d].astype(jnp.int32) - bias


def packed_vote_allreduce(
    votes: jax.Array, axis_name: str, *, group_size: int, e_per: int = 1,
    n_active: int | None = None, local_active=None, total_active=None,
) -> jax.Array:
    """Guard-bit packed vote all-reduce: int votes [..., d] -> int32 tally [..., d].

    Bit-identical to ``psum(votes, axis_name)`` (no field can overflow by
    construction) while sending ``ceil(d/k)`` uint32 lanes instead of d int8
    bytes — a 2x wire-byte cut at the paper's M=3 operating point on a 4-wide
    model axis (4-bit fields, k=8). This is the OTA majority collective of
    the OTA serve builder at ``collective="psum_packed"``
    (`make_mt_ota_serve` and its one-slot view `make_ota_serve`).

    **Active-slot-aware mode** (`n_active` + `local_active`): when only
    `n_active` voters across the whole group are live (every other slot votes
    exactly 0), fields shrink to the [-n_active, n_active] tally span —
    3-bit fields / k=10 / ~2.5x at S=16, M=3, where slot-blind guards give
    6-bit / k=5 / 1.25x. `local_active` is this column's own live-voter count
    (traced is fine; it becomes the column's bias so the biased fields sum to
    exactly n_active + tally). Caller contract: ``|votes| <= local_active``
    element-wise and ``psum(local_active) == n_active`` — both hold for the
    serve body's abstaining-slot votes by construction.

    ``total_active`` (traced is fine) overrides the accumulated bias the
    unpack subtracts when the LIVE voter count differs from the static
    ``n_active`` — the erasure-aware mode (`repro.faults`): dead or dropped
    slots vote exact 0 with `local_active` excluding them, and the caller
    passes the group-wide live total (``psum(local_active)``, computed
    locally from the replicated fault masks — no extra collective). Field
    sizing stays ``n_active`` (a valid span upper bound: live <= n_active),
    so erasures never change the compiled wire format.
    """
    fbits, k = vote_field_spec(group_size, e_per, n_active=n_active)
    if n_active is None:
        bias, total_bias = e_per, group_size * e_per
    else:
        assert local_active is not None, "slot-aware packing needs local_active"
        bias = local_active
        total_bias = n_active if total_active is None else total_active
    lanes = _pack_vote_fields(votes, bias, fbits, k)
    lanes = jax.lax.psum(lanes, axis_name)
    return _unpack_vote_fields(lanes, votes.shape[-1], total_bias, fbits, k)


def packed_vote_psum_scatter(
    votes: jax.Array, axis_name: str, *, group_size: int, e_per: int = 1,
    n_active: int | None = None, local_active=None, total_active=None,
) -> jax.Array:
    """Guard-bit packed reduce-scatter of votes along their last dimension.

    Returns this device's contiguous tally shard [..., d // group_size] int32,
    bit-identical to ``psum_scatter(votes, tiled=True)`` on the same shard.
    Fields per lane are rounded down to a power of two so whole lanes tile
    evenly over the axis; if d doesn't divide into lanes x group_size the
    plain scatter is used unchanged (int8 on the wire whenever the tally span
    fits int8, so no saving but also no regression). `n_active`/`local_active`
    select the active-slot-aware field sizing exactly as in
    `packed_vote_allreduce`; ``total_active`` is the same erasure-aware
    live-total override (ignored by the plain-scatter fallback, which sums
    the raw votes and needs no bias at all).
    """
    d = votes.shape[-1]
    fbits, k = vote_field_spec(group_size, e_per, pow2_fields=True,
                               n_active=n_active)
    if d % (k * group_size) != 0:
        wire = votes if group_size * e_per <= 127 else votes.astype(jnp.int32)
        part = jax.lax.psum_scatter(
            wire, axis_name, scatter_dimension=votes.ndim - 1, tiled=True
        )
        return part.astype(jnp.int32)
    if n_active is None:
        bias, total_bias = e_per, group_size * e_per
    else:
        assert local_active is not None, "slot-aware packing needs local_active"
        bias = local_active
        total_bias = n_active if total_active is None else total_active
    lanes = _pack_vote_fields(votes, bias, fbits, k)
    part = jax.lax.psum_scatter(
        lanes, axis_name, scatter_dimension=votes.ndim - 1, tiled=True
    )
    return _unpack_vote_fields(part, d // group_size, total_bias, fbits, k)


def sparse_index_allgather(idx: jax.Array, axis_name: str) -> jax.Array:
    """All-gather sparse index lists over `axis_name`, slot-flattened.

    idx: int32 [..., e, k_max] (this shard's `e` encoder slots as sorted
    sentinel-padded index lists) -> [..., S*e, k_max] with the slot axis in
    global-encoder order (shard-major: slot s*e + j is shard s's slot j —
    the `gids = tx*e_per + arange(e_per)` convention of the serve body).

    This is the sparse wire format of the OTA majority: each TX ships its
    k_max·32 bits of indices instead of the d field-packed vote bits of
    `packed_vote_allreduce`, and the majority is taken locally over the
    gathered union (`sparse.bundle`). Crossover vs the guard-bit psum is at
    k_max ~ d/field_bits·... — measured, fitted, and gated by
    benchmarks/sparse.py; `ScaleOutConfig.representation="auto"` picks per
    workload from that fit.
    """
    g = jax.lax.all_gather(idx, axis_name)  # [S, ..., e, k_max]
    g = jnp.moveaxis(g, 0, -3)              # [..., S, e, k_max]
    return g.reshape(g.shape[:-3] + (g.shape[-3] * g.shape[-2], g.shape[-1]))


def majority_allreduce(
    bits: jax.Array,
    axis_name: str,
    *,
    key: jax.Array | None = None,
    ber=None,
    rx_axis_name: str | None = None,
) -> jax.Array:
    """OTA majority bundling across `axis_name`: uint8 {0,1} shards -> majority bits.

    Equivalent to the paper's over-the-air computation: every device along
    `axis_name` contributes its hypervector; all devices receive maj(·) in a single
    all-reduce. Ties on even group size resolve to 0 (`tally > 0`) — the repo-wide
    convention shared by `hv.majority`/`hv.majority_packed` (without a key) and
    the `kernels.majority` oracle, asserted in tests/test_hdc_core.py.
    Optional (key, ber): apply the OTA error channel to the *received* copy,
    independently per device along `rx_axis_name` (default: the reduce axis).
    """
    bipolar = 2 * bits.astype(jnp.int32) - 1
    votes = jax.lax.psum(bipolar, axis_name)
    out = (votes > 0).astype(jnp.uint8)
    if ber is not None:
        assert key is not None, "OTA noise needs a PRNG key"
        out = ota_noise(key, out, ber, rx_axis_name or axis_name)
    return out


def sign_allreduce(
    x: jax.Array, axis_name: str, *, key=None, ber=None
) -> jax.Array:
    """Majority-vote sign aggregation (1-bit compressed all-reduce) for floats.

    Payload on the wire is sign(x) (1 bit/element vs 32): the majority-vote
    signSGD aggregation [Bernstein et al.] — structurally identical to the
    paper's OTA bundling with gradients in place of query hypervectors. Optional
    BER applies the OTA channel to the result (sign flips), which HDC-style error
    tolerance (and signSGD's) absorbs; each receiver folds its index along
    the reduce axes into the key, so the flips are independent per device.
    """
    votes = jax.lax.psum(jnp.sign(x).astype(jnp.float32), axis_name)
    out = jnp.sign(votes)
    if ber is not None:
        assert key is not None, "OTA noise needs a PRNG key"
        axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        for ax in axes:
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        flips = jax.random.bernoulli(key, ber, out.shape)
        out = jnp.where(flips, -out, out)
    return out.astype(x.dtype)
