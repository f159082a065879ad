"""Pluggable PHY channel models for the over-the-air serve path.

The paper's whole argument hangs on one abstraction: the OTA majority channel
can be summarized as a per-RX bit-error rate (Eq. 1) without changing what the
classifier sees. This module makes that abstraction a *swappable layer* of the
serve step instead of a baked-in assumption. Three fidelity tiers implement
one `Channel` interface:

* ``ideal``  — error-free: every IMC core receives the exact majority bundle.
* ``bsc``    — the paper's methodology (and the previous hard-coded behavior):
  each core decodes a binary-symmetric-channel copy at its pre-characterized
  BER. Bit-identical to the old inline ``_core_noise`` path on the same RNG
  stream — the tier every prediction-identity guarantee is pinned to.
* ``symbol`` — the actual physics, fully batched and in-graph: per dimension,
  the M transmitters' phase-encoded symbols superpose in the channel
  (`ota.rx_constellations`), each receiver adds complex AWGN and decodes via
  its majority decision regions (`ota.majority_centroids`) — a vectorized
  re-hosting of ``ota.simulate_ota_bundle`` inside the ``shard_map`` serve
  body. This is the tier that *verifies* "BER 0.01 with no accuracy impact"
  end-to-end instead of assuming it.

The precharacterization outputs travel as a :class:`ChannelState` pytree
(channel matrix ``h``, chosen ``phase_idx``, constellation ``symbols``,
decision centroids ``c0``/``c1``, noise density ``n0``, per-RX ``ber`` +
``valid``) threaded through the OTA serve builder (``make_mt_ota_serve`` and
its one-slot view ``make_ota_serve``) and ``make_wired_serve`` in place
of the bare BER array; every leaf with a leading RX axis shards over the
``model`` mesh axis exactly like the prototype memory it sits next to.

Distribution note — the ``symbol`` tier's wire payload: the received symbol of
RX r at dimension j depends on the TX bits only through the combo index
``b = sum_m bit_mj * 2^m`` (``y[r, b] = sum_m H[r, m] * exp(i*phi_m(bit_mj))``
is precomputed per combo in ``symbols``). Since the combo index is itself a
weighted *sum* of per-TX contributions, the analog field superposition
re-hosts exactly as ONE int32 psum over the model axis — the same
single-collective shape as the paper's OTA reduction — followed by a purely
local constellation lookup + AWGN + decision at each core. No approximation:
indexing the precomputed constellation by the summed combo equals summing the
per-TX complex fields.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import hypervector as hv, ota


@dataclasses.dataclass(frozen=True)
class ChannelState:
    """Precharacterized channel state (one pytree, [N] = RX cores leading).

    Produced offline by the EM + constellation pipeline (the paper's CST +
    MATLAB step) via `state_from_ota`, or synthesized from a bare BER table
    via `state_from_ber` for the ``ideal``/``bsc`` tiers that never touch the
    physical fields.
    """

    ber: jax.Array        # [N] f32 — Eq. (1) per-RX BER (the bsc abstraction)
    valid: jax.Array      # [N] bool — majority decision regions are a 2-means fit
    h: jax.Array          # [N, M] c64 — channel matrix (quasi-static, known a priori)
    phase_idx: jax.Array  # [M, 2] i32 — jointly optimized TX phase pairs
    symbols: jax.Array    # [N, 2^M] c64 — noiseless received constellation per combo
    c0: jax.Array         # [N] c64 — maj=0 decision-region centroid
    c1: jax.Array         # [N] c64 — maj=1 decision-region centroid
    n0: jax.Array         # [] f32 — AWGN noise density (per-component var n0/2)

    @property
    def n_rx(self) -> int:
        return self.ber.shape[0]

    @property
    def m_tx(self) -> int:
        return self.h.shape[1]


jax.tree_util.register_pytree_node(
    ChannelState,
    lambda s: ((s.ber, s.valid, s.h, s.phase_idx, s.symbols, s.c0, s.c1, s.n0), None),
    lambda _, leaves: ChannelState(*leaves),
)


def state_from_ota(res: "ota.OTAResult", h: jax.Array) -> ChannelState:
    """Package an `ota.OTAResult` + its channel matrix as a ChannelState."""
    m = h.shape[1]
    maj = ota.majority_labels(m)
    c0, c1 = ota.majority_centroids(res.symbols, maj)
    return ChannelState(
        ber=jnp.asarray(res.ber_per_rx, jnp.float32),
        valid=jnp.asarray(res.valid_per_rx, bool),
        h=jnp.asarray(h, jnp.complex64),
        phase_idx=jnp.asarray(res.phase_idx, jnp.int32),
        symbols=jnp.asarray(res.symbols, jnp.complex64),
        c0=jnp.asarray(c0, jnp.complex64),
        c1=jnp.asarray(c1, jnp.complex64),
        n0=jnp.asarray(res.n0, jnp.float32),
    )


def state_from_ber(ber: jax.Array, m_tx: int) -> ChannelState:
    """Minimal state for the ``ideal``/``bsc`` tiers from a bare BER table.

    The physical fields are zero placeholders with the correct shapes (they
    are inputs of the compiled serve program either way, and a few KB at
    most), and ``valid`` is all-False: these rows carry NO usable decision
    regions.  Every tier treats invalid rows as "trust the analytic BER,
    not the physics": ``bsc`` flips at ``ber`` (its only model anyway),
    ``ideal`` ignores the state, and the ``symbol`` tier falls back to
    majority + BSC flips at ``ber`` for such rows instead of silently
    decoding the all-zero constellation (which would return constant bits
    and poison the vote).  Build real physics with `state_from_ota` /
    `scaleout.precharacterize_state`.
    """
    ber = jnp.asarray(ber, jnp.float32)
    n = ber.shape[0]
    b = 2 ** m_tx
    return ChannelState(
        ber=ber,
        valid=jnp.zeros((n,), bool),
        h=jnp.zeros((n, m_tx), jnp.complex64),
        phase_idx=jnp.zeros((m_tx, 2), jnp.int32),
        symbols=jnp.zeros((n, b), jnp.complex64),
        c0=jnp.zeros((n,), jnp.complex64),
        c1=jnp.zeros((n,), jnp.complex64),
        n0=jnp.ones((), jnp.float32),
    )


def state_spec(rx_axis: str | None = "model") -> ChannelState:
    """PartitionSpec tree for a ChannelState: RX-leading leaves shard over
    `rx_axis` (aligned with the prototype/core sharding), the rest replicate.
    Feed directly to `compat.shard_map`'s in_specs."""
    from jax.sharding import PartitionSpec as P

    rx = P(rx_axis)
    rx2 = P(rx_axis, None)
    return ChannelState(ber=rx, valid=rx, h=rx2, phase_idx=P(), symbols=rx2,
                        c0=rx, c1=rx, n0=P())


def state_shape_structs(n_rx: int, m_tx: int) -> ChannelState:
    """ShapeDtypeStruct tree matching `state_from_ber`/`state_from_ota` output
    — for AOT lowering (the dry-run cells) without running the EM pipeline."""
    s = jax.ShapeDtypeStruct
    b = 2 ** m_tx
    return ChannelState(
        ber=s((n_rx,), jnp.float32), valid=s((n_rx,), bool),
        h=s((n_rx, m_tx), jnp.complex64), phase_idx=s((m_tx, 2), jnp.int32),
        symbols=s((n_rx, b), jnp.complex64), c0=s((n_rx,), jnp.complex64),
        c1=s((n_rx,), jnp.complex64), n0=s((), jnp.float32),
    )


def combo_index(bits: jax.Array, axis: int = 0) -> jax.Array:
    """TX bit combo index along `axis`: bits [.., M, ..] {0,1} -> int32 [..].

    The LSB-first weighting matches `ota.bit_combos` (TX 0 = bit 0), so
    ``symbols[:, combo_index(q)]`` is the noiseless received field of the
    transmission — the per-dimension column of `ota.rx_constellations`.
    """
    m = bits.shape[axis]
    shape = [1] * bits.ndim
    shape[axis] = m
    weights = (jnp.int32(1) << jnp.arange(m, dtype=jnp.int32)).reshape(shape)
    return jnp.sum(bits.astype(jnp.int32) * weights, axis=axis)


# the ONE physical decode definition, shared with `ota.simulate_ota_bundle`
awgn_decide = ota.awgn_decide


# ---------------------------------------------------------------------------
# the Channel interface + tiers
# ---------------------------------------------------------------------------

class Channel:
    """One fidelity tier of the OTA link inside the serve step.

    ``wire`` names what the TX columns reduce over the mesh axis:

    * ``"votes"`` — bipolar majority votes; the serve step keeps its existing
      collective realizations (psum / psum_packed / rs_ag) and hands the
      thresholded bundle to `rx_copies`.
    * ``"combo"`` — the int32 TX bit-combo index (ONE psum); `rx_copies` gets
      the summed combo and performs the physical per-core decode.

    `rx_copies` produces every local core's received copy of the query:
    [n_cores, B, d] uint8 bits, or [n_cores, B, d/32] uint32 words when
    ``packed`` (the symbol tier decodes bits, then packs — the IMC macro
    stores bits either way). ``rx_base + i`` indexes the global RX core for
    the PRNG fold, the SAME schedule for every tier so swapping tiers never
    perturbs an unrelated stream.
    """

    name: str = "?"
    wire: str = "votes"

    def rx_copies(self, key, reduced, state: ChannelState, rx_base, n_cores: int,
                  *, packed: bool, dim: int, noise: str, planes: int) -> jax.Array:
        raise NotImplementedError

    def rx_copies_slots(self, keys, reduced, state: ChannelState, rx_base,
                        n_cores: int, *, packed: bool, dim: int, noise: str,
                        planes: int, use_kernel: bool) -> jax.Array:
        """`rx_copies` for N slots, each with its own key: keys [N, 2],
        reduced [N, ...] -> [N, n_cores, ...]. A tier with a kernel for the
        whole slot batch overrides this; the default maps over the slots."""
        return jax.vmap(
            lambda k, r: self.rx_copies(k, r, state, rx_base, n_cores,
                                        packed=packed, dim=dim, noise=noise,
                                        planes=planes)
        )(keys, reduced)


class IdealChannel(Channel):
    """Error-free link: every core receives the exact majority bundle."""

    name = "ideal"
    wire = "votes"

    def rx_copies(self, key, reduced, state, rx_base, n_cores,
                  *, packed, dim, noise, planes):
        return jnp.broadcast_to(reduced[None], (n_cores,) + reduced.shape)


class BSCChannel(Channel):
    """Per-RX binary symmetric channel at the precharacterized BER (Eq. 1).

    The paper's abstraction and the repo default — bit-identical to the
    pre-phy inline serve noise on the same RNG stream: core i folds
    ``rx_base + i`` into the key and flips at ``state.ber[i]``. The packed
    representation honors the ``exact``/``bitplane`` mask modes.
    """

    name = "bsc"
    wire = "votes"

    def rx_copies(self, key, reduced, state, rx_base, n_cores,
                  *, packed, dim, noise, planes):
        from repro.distributed import collectives

        def one(i, ber):
            k = jax.random.fold_in(key, rx_base + i)
            if packed:
                return collectives.ota_noise_packed(k, reduced, ber,
                                                    mode=noise, planes=planes)
            return collectives.ota_noise(k, reduced, ber)

        return jax.vmap(one)(jnp.arange(n_cores), state.ber)

    def rx_copies_slots(self, keys, reduced, state, rx_base, n_cores,
                        *, packed, dim, noise, planes, use_kernel):
        """Packed bitplane copies of every slot run as one Pallas kernel
        (`kernels.bsc`), bit for bit `rx_copies` mapped over the slots: the
        same per-core keys and thresholds, the 16 random planes kept in
        vregs. The other modes map `rx_copies` over the slots."""
        if not (packed and noise == "bitplane" and use_kernel):
            return super().rx_copies_slots(
                keys, reduced, state, rx_base, n_cores, packed=packed,
                dim=dim, noise=noise, planes=planes, use_kernel=use_kernel)
        from repro.kernels.bsc import bsc_bitplane_copies

        cores = rx_base + jnp.arange(n_cores)
        core_keys = jax.vmap(
            lambda k: jax.vmap(lambda i: jax.random.fold_in(k, i))(cores)
        )(keys)  # [N, n_cores, 2]
        return bsc_bitplane_copies(core_keys, reduced, state.ber,
                                   precision=planes)


class SymbolChannel(Channel):
    """Physical OTA: constellation superposition + AWGN + decision regions.

    ``reduced`` is the psum'd combo index [B, d] int32 (see module docstring:
    the combo psum IS the field superposition, re-hosted losslessly). Each
    local core looks up its noiseless received symbol ``symbols[i][combo]``,
    adds complex AWGN at ``n0`` and decides against its (c0, c1) centroids —
    `ota.simulate_ota_bundle` vectorized over cores x batch x dimensions.
    Decodes bits, then packs when the serve representation is packed.
    """

    name = "symbol"
    wire = "combo"

    def rx_copies(self, key, reduced, state, rx_base, n_cores,
                  *, packed, dim, noise, planes):
        def one(i, sym_row, c0, c1):
            k = jax.random.fold_in(key, rx_base + i)
            return awgn_decide(k, sym_row[reduced], c0, c1, state.n0)

        bits = jax.vmap(one)(jnp.arange(n_cores), state.symbols, state.c0,
                             state.c1)  # [n_cores, B, d]

        m = state.m_tx

        def with_fallback(b):
            # rows with valid=False carry no usable decision regions — either
            # the 2-means constraint failed at characterization (their
            # analytic BER is pinned to 0.5) or the state is a
            # `state_from_ber` synthesis with zero physics. Decoding the raw
            # constellation there returns constant garbage that poisons the
            # vote; fall back to the analytic-BER abstraction instead:
            # exact majority + BSC flips at `state.ber`. The fallback stream
            # is a fold_in(., 1) off the per-core key, so VALID rows' RNG
            # (consumed inside awgn_decide off the un-suffixed key) is
            # untouched — all-valid states stay bit-identical.
            exact = ota.majority_labels(m)[reduced]  # [.., d] true majority

            def flips(i, ber):
                k = jax.random.fold_in(jax.random.fold_in(key, rx_base + i), 1)
                f = jax.random.bernoulli(k, ber, exact.shape)
                return jnp.logical_xor(exact.astype(bool), f).astype(jnp.uint8)

            fb = jax.vmap(flips)(jnp.arange(n_cores), state.ber)
            return jnp.where(
                state.valid.reshape((n_cores,) + (1,) * (b.ndim - 1)), b, fb
            )

        # all-valid states (every real characterization in the repo) skip the
        # fallback branch at runtime — lax.cond, not select: the predicate is
        # unbatched even under the multi-tenant slot vmap
        bits = jax.lax.cond(jnp.all(state.valid), lambda b: b, with_fallback,
                            bits)
        return hv.pack(bits) if packed else bits


CHANNELS: dict[str, Channel] = {}


def register_channel(channel: Channel, *, override: bool = False) -> Channel:
    """Register a `Channel` tier under ``channel.name`` for `get_channel`.

    The extension seam for out-of-tree fidelity tiers (and the process
    subsystem's derived channels): implement the `Channel` interface, register
    an instance, and ``ScaleOutConfig(channel=<name>)`` picks it up without
    editing this module. Re-registering a taken name raises unless
    ``override=True`` (deliberate replacement, e.g. an instrumented tier in a
    test).  Returns the instance so it can be used as a decorator-ish one-liner.
    """
    name = getattr(channel, "name", None)
    if not isinstance(name, str) or not name or name == "?":
        raise ValueError(f"channel must define a non-empty .name, got {name!r}")
    if not callable(getattr(channel, "rx_copies", None)):
        raise TypeError(f"channel {name!r} does not implement rx_copies()")
    if name in CHANNELS and not override:
        raise ValueError(
            f"channel tier {name!r} already registered; pass override=True "
            "to replace it"
        )
    CHANNELS[name] = channel
    return channel


for _tier in (IdealChannel(), BSCChannel(), SymbolChannel()):
    register_channel(_tier)
del _tier


def get_channel(name: str) -> Channel:
    try:
        return CHANNELS[name]
    except KeyError:
        raise ValueError(
            f"unknown channel tier {name!r}; available: {sorted(CHANNELS)}"
        ) from None
