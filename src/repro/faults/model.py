"""Hard-fault injection for the OTA serve path: the chaos layer.

The PHY subsystem (`repro.phy`) models *soft* degradation — drifting phases,
fading amplitudes, a rising BER the closed loop can re-characterize away.
This module models the failures no re-fit recovers: PCM crossbar cells stuck
at a conductance rail, whole IMC cores (or their RX front-ends) going dark,
and encoder votes erased from the over-the-air superposition. At WHYPE scale
(1024 RX cores) these are a statistical certainty, and a serve path that
ignores them silently misclassifies every query whose class lives on a dead
core.

Everything rides in ONE `FaultState` pytree threaded through the one OTA
serve builder (`core.scaleout.make_mt_ota_serve` and its one-slot view
`make_ota_serve`, with a ``faults=`` model), split into three fault surfaces:

* **wire faults** — ``dead_tx`` (permanent) and ``vote_drop`` (per-step,
  refreshed by the fault process) erase encoder slots from the bundle. On the
  vote wire an erased slot votes exact 0 — the same abstention mechanism as
  the unused mesh slots — and the tally threshold ``tally > 0`` is
  automatically the majority of the LIVE voters; the guard-bit packed
  collectives re-bias by the traced live counts
  (`collectives.packed_vote_allreduce(total_active=...)`) so the packed
  tally stays bit-identical to the int8 psum of the erased votes. On the
  combo (symbol) wire an erased encoder is modeled as a *stuck carrier*:
  it keeps radiating its bit-0 phase, so the received field is exactly the
  full constellation row with that bit forced 0 — `live_combo_mask` /
  `recenter_state` refit the decision centroids over the occurring
  sub-constellation (the mask extension of `ota.majority_centroids`).
* **node faults** — ``dead_rx`` marks IMC cores that answer no similarity
  query: their received copy is zeroed in-graph. Tolerance is the
  ``serve_rows`` failover indirection (`plan_failover`): each *bank* of
  classes is served by the query copy of a healthy same-shard core — the
  query-side dual of the `hamming_topk_banked` ``bank_rows`` prototype
  indirection, and like it a traced gather, so remapping never recompiles.
  Banks with no healthy server left are excluded from the top-1 via
  ``rx_mask`` (the same pre-reduction masking as the PHY quarantine).
* **memory faults** — ``stuck0`` / ``stuck1`` are per-core packed column
  masks forcing stored prototype bits to 0/1 (applied in-graph to the
  stored — post-permutation — rows, i.e. the physical crossbar columns);
  word-dropout is a whole word stuck at 0 (`sample_word_dropout`).

Key invariant (pinned in tests/test_faults.py): with the all-healthy
`healthy_state` every application is a value identity — zero masks, identity
gather — so the fault-aware serve is **bit-identical** to the fault-free
build across every representation x collective x channel combination.

Fault models evolve the state between steps through the same registry
pattern as `phy.PROCESSES` (`FAULTS` / `register_fault_model` /
`get_fault_model`) and the same RNG discipline (`phy.row_keys`:
``fold_in(fold_in(key, t), rx_base + row)``, no data-position fold), with
their own key — the serve RNG stream is untouched. TX-side leaves evolve
from the ``t`` fold alone so every model shard derives the identical
replicated update.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hypervector as hv, ota
from repro.phy.channel import ChannelState
from repro.phy.process import row_keys

_FULL_WORD = jnp.uint32(0xFFFFFFFF)

# per-row RNG sub-streams (suffix folds, disjoint from phy.process's 0..2)
_WIRE = 3
_WEAR = 4


@dataclasses.dataclass(frozen=True)
class FaultState:
    """One pytree carrying every injected hard fault, [N] RX leading.

    ``m_slots = model_size * e_per`` covers every encoder slot the serve body
    can address (``gids``); slots past ``m_tx`` never vote, so their fault
    bits are inert. ``serve_rows`` holds GLOBAL core ids constrained to the
    owning shard (bank i is served by the query copy of core
    ``serve_rows[i]``; identity = no remap); the serve body converts to
    shard-local indices, so the leaf shards over ``model`` like the rest of
    the RX-leading leaves (`fstate_spec`).
    """

    dead_tx: jax.Array    # [m_slots] bool — permanently dark encoder slots
    vote_drop: jax.Array  # [m_slots] bool — THIS step's transient erasures
    dead_rx: jax.Array    # [N] bool — dark IMC cores (answer no query)
    stuck0: jax.Array     # [N, W] u32 — prototype bits stuck at 0
    stuck1: jax.Array     # [N, W] u32 — prototype bits stuck at 1
    serve_rows: jax.Array  # [N] i32 — failover: bank i served by this core
    rx_mask: jax.Array    # [N] bool — banks with no healthy server
    t: jax.Array          # [] i32 — fault-process time

    @property
    def n_rx(self) -> int:
        return self.dead_rx.shape[0]

    @property
    def m_slots(self) -> int:
        return self.dead_tx.shape[0]


jax.tree_util.register_pytree_node(
    FaultState,
    lambda f: ((f.dead_tx, f.vote_drop, f.dead_rx, f.stuck0, f.stuck1,
                f.serve_rows, f.rx_mask, f.t), None),
    lambda _, leaves: FaultState(*leaves),
)


def fstate_spec(rx_axis: str | None = "model") -> FaultState:
    """PartitionSpec tree for a FaultState (RX-leading over `rx_axis`; the
    TX-side erasure masks and ``t`` replicate — every column needs the global
    view to derive the live-voter total without an extra collective)."""
    from jax.sharding import PartitionSpec as P

    rx = P(rx_axis)
    return FaultState(dead_tx=P(), vote_drop=P(), dead_rx=rx,
                      stuck0=P(rx_axis, None), stuck1=P(rx_axis, None),
                      serve_rows=rx, rx_mask=rx, t=P())


def fstate_shape_structs(n_rx: int, m_slots: int, words: int) -> FaultState:
    """ShapeDtypeStruct tree matching `healthy_state` — for AOT lowering
    (the dry-run ``serve_faulty`` cells) without materializing the arrays."""
    s = jax.ShapeDtypeStruct
    return FaultState(
        dead_tx=s((m_slots,), bool),
        vote_drop=s((m_slots,), bool),
        dead_rx=s((n_rx,), bool),
        stuck0=s((n_rx, words), jnp.uint32),
        stuck1=s((n_rx, words), jnp.uint32),
        serve_rows=s((n_rx,), jnp.int32),
        rx_mask=s((n_rx,), bool),
        t=s((), jnp.int32),
    )


def healthy_state(n_rx: int, m_slots: int, words: int) -> FaultState:
    """The all-healthy FaultState: every application is a value identity, so
    serving through it is bit-identical to the fault-free serve build."""
    return FaultState(
        dead_tx=jnp.zeros((m_slots,), bool),
        vote_drop=jnp.zeros((m_slots,), bool),
        dead_rx=jnp.zeros((n_rx,), bool),
        stuck0=jnp.zeros((n_rx, words), jnp.uint32),
        stuck1=jnp.zeros((n_rx, words), jnp.uint32),
        serve_rows=jnp.arange(n_rx, dtype=jnp.int32),
        rx_mask=jnp.zeros((n_rx,), bool),
        t=jnp.zeros((), jnp.int32),
    )


def healthy_for(cfg, model_size: int) -> FaultState:
    """`healthy_state` sized for a `ScaleOutConfig` on a given model-axis
    width (m_slots = model_size * e_per, matching the serve body's gids)."""
    e_per = -(-cfg.m_tx // model_size)
    return healthy_state(cfg.n_rx_cores, model_size * e_per, cfg.words)


def inject(fstate: FaultState, **leaves) -> FaultState:
    """Replace fault leaves host-side, coercing to the pytree dtypes.

    ``inject(f, dead_rx=[0, 3], ...)`` accepts index lists for the bool
    masks (dead_tx / vote_drop / dead_rx / rx_mask) or full arrays for any
    leaf; shapes must match the state (the serve step is compiled for them).
    """
    coerced = {}
    for name, val in leaves.items():
        ref = getattr(fstate, name)
        if ref.dtype == jnp.bool_ and not isinstance(val, jax.Array):
            arr = np.asarray(val)
            if arr.dtype != np.bool_ or arr.shape != ref.shape:
                mask = np.zeros(ref.shape, bool)
                mask[arr.astype(np.int64)] = True
                arr = mask
            val = arr
        val = jnp.asarray(val, ref.dtype)
        assert val.shape == ref.shape, (name, val.shape, ref.shape)
        coerced[name] = val
    return dataclasses.replace(fstate, **coerced)


# ---------------------------------------------------------------------------
# memory-fault samplers
# ---------------------------------------------------------------------------

def sample_stuck_cells(
    key: jax.Array, n_rx: int, words: int, density: float
) -> tuple[jax.Array, jax.Array]:
    """(stuck0, stuck1) [N, W] u32 masks at total cell density `density`,
    split evenly between the two rails and kept disjoint (a cell has one
    conductance). The Karunaratne et al. stuck-at abstraction of PCM
    device failures."""
    k0, k1 = jax.random.split(key)
    s0 = hv.bernoulli_words(k0, density / 2.0, (n_rx, words))
    s1 = hv.bernoulli_words(k1, density / 2.0, (n_rx, words)) & ~s0
    return s0, s1


def sample_word_dropout(
    key: jax.Array, n_rx: int, words: int, p_word: float
) -> jax.Array:
    """Whole-word dropout as a stuck-at-0 mask: each of the N*W stored words
    is lost (all 32 bits forced 0 — a dead word line) w.p. `p_word`.
    OR the result into ``stuck0``."""
    drop = jax.random.bernoulli(key, p_word, (n_rx, words))
    return jnp.where(drop, _FULL_WORD, jnp.uint32(0))


# ---------------------------------------------------------------------------
# failover planning (host-side; the FaultController's remap action)
# ---------------------------------------------------------------------------

def plan_failover(fstate: FaultState, cores_per_shard: int) -> FaultState:
    """Remap every dead core's class bank onto healthy same-shard cores.

    Dead banks are dealt round-robin over the shard's healthy cores (each
    healthy core already serves its own bank; failover adds the dead ones on
    top — the kernel's G axis covers both). Failover never crosses a shard
    boundary: the query copies live per-shard, and a cross-shard remap would
    need a query exchange the wire path doesn't have. A shard with no
    healthy core left gets its banks ``rx_mask``-ed out of the top-1
    instead. Pure host-side planning — the result feeds the SAME compiled
    serve (``serve_rows``/``rx_mask`` are traced inputs)."""
    dead = np.asarray(fstate.dead_rx)
    n = dead.shape[0]
    assert n % cores_per_shard == 0, (n, cores_per_shard)
    rows = np.arange(n, dtype=np.int32)
    mask = np.zeros(n, bool)
    for lo in range(0, n, cores_per_shard):
        sl = slice(lo, lo + cores_per_shard)
        healthy = np.flatnonzero(~dead[sl])
        if healthy.size == 0:
            mask[sl] = True
            continue
        for j, i in enumerate(np.flatnonzero(dead[sl])):
            rows[lo + i] = lo + healthy[j % healthy.size]
    return dataclasses.replace(
        fstate,
        serve_rows=jnp.asarray(rows),
        rx_mask=jnp.asarray(mask),
    )


# ---------------------------------------------------------------------------
# combo-wire (symbol tier) erasure support
# ---------------------------------------------------------------------------

def live_combo_mask(dead_slots, m_tx: int) -> jax.Array:
    """[2^M] bool — the combos that can occur on the wire when the erased
    encoders are stuck radiating their bit-0 phase (combo bit forced 0)."""
    combos = ota.bit_combos(m_tx).astype(bool)          # [B, M]
    dead = jnp.asarray(dead_slots, bool)[:m_tx]
    return ~jnp.any(combos & dead[None, :], axis=-1)


def live_majority_labels(dead_slots, m_tx: int) -> jax.Array:
    """maj(b) over the LIVE encoder bits only, [2^M] uint8 — what the
    erasure-aware receiver should decode (even live counts tie to 0, the
    repo-wide convention)."""
    combos = ota.bit_combos(m_tx).astype(jnp.int32)     # [B, M]
    live = ~jnp.asarray(dead_slots, bool)[:m_tx]
    counts = jnp.sum(combos * live.astype(jnp.int32)[None, :], axis=-1)
    n_live = jnp.sum(live.astype(jnp.int32))
    return (2 * counts > n_live).astype(jnp.uint8)


def recenter_state(state: ChannelState, dead_slots) -> ChannelState:
    """Erasure-aware re-fit of the symbol-tier decision regions.

    With encoders erased, only the `live_combo_mask` sub-constellation
    occurs; the stale all-M centroids straddle the wrong partition. This
    refits ``c0/c1`` via the masked `ota.majority_centroids` over the
    occurring combos labelled by the LIVE majority — the erasure analogue of
    `phy.recharacterize`."""
    maj = live_majority_labels(dead_slots, state.m_tx)
    mask = live_combo_mask(dead_slots, state.m_tx)
    c0, c1 = ota.majority_centroids(state.symbols, maj, mask=mask)
    return dataclasses.replace(
        state,
        c0=c0.astype(jnp.complex64),
        c1=c1.astype(jnp.complex64),
    )


# ---------------------------------------------------------------------------
# fault models (the evolution laws) + registry, mirroring phy.PROCESSES
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FaultModel:
    """One stochastic evolution law for the injected faults between steps.

    ``step`` advances the FaultState one serve step with the per-row RNG
    discipline of `phy.row_keys` (RX-side leaves) and plain ``t`` folds
    (TX-side leaves, so the replicated update is identical on every model
    shard). The serve integration calls it once per step with its OWN key —
    fault evolution never consumes the serve stream.
    """

    name = "?"

    def init(self, n_rx: int, m_slots: int, words: int) -> FaultState:
        return healthy_state(n_rx, m_slots, words)

    def step(self, key: jax.Array, f: FaultState, *, rx_base=0) -> FaultState:
        return dataclasses.replace(f, t=f.t + 1)


@dataclasses.dataclass(frozen=True)
class StaticFaults(FaultModel):
    """Frozen faults: `step` only advances ``t``. The bit-identity anchor —
    injected faults persist unchanged, and through `healthy_state` the serve
    is bit-identical to the fault-free build (same discipline as
    `phy.StaticProcess`)."""

    name = "static"


@dataclasses.dataclass(frozen=True)
class TransientVoteFaults(StaticFaults):
    """Per-step wire erasures: each encoder slot's vote is dropped from this
    step's superposition w.p. ``p_drop`` (redrawn every step — a glinting
    interconnect, not a dead node). Node/memory leaves pass through."""

    name = "transient_votes"
    p_drop: float = 0.05

    def step(self, key, f, *, rx_base=0):
        kt = jax.random.fold_in(jax.random.fold_in(key, f.t), _WIRE)
        drop = jax.random.bernoulli(kt, self.p_drop, f.vote_drop.shape)
        return dataclasses.replace(f, vote_drop=drop, t=f.t + 1)


@dataclasses.dataclass(frozen=True)
class WearoutFaults(FaultModel):
    """Permanent accumulation: each live core dies w.p. ``p_die`` per step
    and each stored cell sticks w.p. ``stuck_rate`` per step (split evenly
    between the rails, monotone — faults only accrue). The controller's
    remap action, not this model, updates ``serve_rows``/``rx_mask``:
    physics breaks hardware, the serving layer routes around it."""

    name = "wearout"
    p_die: float = 0.001
    stuck_rate: float = 1e-4

    def step(self, key, f, *, rx_base=0):
        n = f.dead_rx.shape[0]
        words = f.stuck0.shape[-1]
        kr = row_keys(key, f.t, rx_base, n)

        def one(k):
            kd, k0, k1 = jax.random.split(jax.random.fold_in(k, _WEAR), 3)
            die = jax.random.bernoulli(kd, self.p_die)
            s0 = hv.bernoulli_words(k0, self.stuck_rate / 2.0, (words,))
            s1 = hv.bernoulli_words(k1, self.stuck_rate / 2.0, (words,))
            return die, s0, s1

        die, s0, s1 = jax.vmap(one)(kr)
        stuck0 = f.stuck0 | s0
        return dataclasses.replace(
            f,
            dead_rx=f.dead_rx | die,
            stuck0=stuck0,
            stuck1=(f.stuck1 | s1) & ~stuck0,
            t=f.t + 1,
        )


FAULTS: dict[str, type] = {}


def register_fault_model(cls: type, *, override: bool = False) -> type:
    """Register a `FaultModel` subclass under ``cls.name`` for
    `get_fault_model` — the same open-registry contract as
    `phy.register_process`; usable as a class decorator."""
    name = getattr(cls, "name", None)
    if not isinstance(name, str) or not name or name == "?":
        raise ValueError(f"fault model must define a non-empty .name, got {name!r}")
    if not callable(getattr(cls, "step", None)):
        raise TypeError(f"fault model {name!r} does not implement step()")
    if name in FAULTS and not override:
        raise ValueError(
            f"fault model {name!r} already registered; pass override=True "
            "to replace it"
        )
    FAULTS[name] = cls
    return cls


for _f in (StaticFaults, TransientVoteFaults, WearoutFaults):
    register_fault_model(_f)
del _f


def get_fault_model(name: str, **kwargs) -> FaultModel:
    """Instantiate a registered fault model by name (kwargs -> constructor)."""
    try:
        cls = FAULTS[name]
    except KeyError:
        raise ValueError(
            f"unknown fault model {name!r}; available: {sorted(FAULTS)}"
        ) from None
    return cls(**kwargs)
