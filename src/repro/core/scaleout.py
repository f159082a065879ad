"""Distributed scale-out of IMC-based HDC similarity search (paper Fig. 3b).

Mapping of the paper's architecture onto the production TPU mesh:

* **encoders (TXs)** — the ``model`` mesh axis carries the encoder slots; encoder
  *g* lives co-located with model column ``g // e_per`` (``e_per = ceil(m_tx /
  model_size)`` encoders per column, so any M up to the paper's 11 TXs fits any
  mesh). Unoccupied slots abstain (vote 0).
* **OTA majority bundling** — one ``psum`` of int8 bipolar votes over the ``model``
  axis (`distributed.collectives.majority_allreduce`): the all-to-one reduction and
  one-to-all broadcast collapse into a single collective, exactly the paper's
  over-the-air computation. Payload is 1 byte/element (conceptually 1 bit);
  ``collective="psum_packed"`` shrinks it further with guard-bit field packing
  (`collectives.packed_vote_allreduce` — several votes per uint32 lane, ONE
  uint32 psum, bit-identical tally).
* **N IMC cores (RXs)** — the associative memory (C prototype hypervectors) is
  sharded over ``model``; each shard subdivides its classes among
  ``cores_per_shard`` IMC cores, and *each core decodes its own noisy copy* of the
  bundled query through the pluggable PHY tier (``repro.phy``): ``bsc`` flips at
  the pre-characterized BER of the EM + constellation pipeline (``core.em`` /
  ``core.ota`` — the paper's Eq. 1 abstraction, the default), ``symbol`` runs the
  actual constellation + AWGN + decision-region physics in-graph, ``ideal`` is
  error-free — "each RX receives a slightly different version of Q". The
  precharacterization travels as a ``phy.ChannelState`` pytree sharded with the
  cores.
* **similarity search** — local bipolar dot products (the IMC crossbar MVM;
  Pallas ``assoc_matmul`` on TPU) + a tiny all-gather of per-shard (value, index)
  pairs for the global top-1.
* trials are batched over the ``data`` (and ``pod``) axes.

``make_wired_serve`` implements the *wired-baseline* dataflow the paper argues
against: queries are all-gathered to every core (the NoC broadcast), then bundled
locally — same math, M·(model_size)× the collective bytes. The roofline benchmark
contrasts the two HLOs.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat, phy
from repro import faults as faultlib
from repro.core import em, hypervector as hv, ota, sparse
from repro.distributed import collectives
from repro.kernels.assoc_matmul import assoc_matmul
from repro.kernels.hamming import hamming_search, hamming_topk_banked
from repro.kernels.majority import majority_bundle
from repro.kernels.sparse import sparse_topk_banked


@dataclasses.dataclass(frozen=True)
class ScaleOutConfig:
    n_classes: int = 6400        # total classes across all IMC cores
    dim: int = 512               # hypervector dimensionality
    m_tx: int = 3                # simultaneous transmitters (<= model mesh size)
    n_rx_cores: int = 64         # physical IMC cores (multiple of model mesh size)
    snr_db: float = 7.0          # OTA operating point (see ota.default_n0)
    permuted: bool = False       # permuted bundling (per-TX cyclic signature)
    use_kernels: bool = True     # Pallas fast path (interpret on CPU)
    batch: int = 256             # global trial batch
    collective: str = "psum"     # OTA realization: "psum" (paper-faithful single
    #   fused collective, int8 all-reduce) | "psum_packed" (same single
    #   all-reduce with guard-bit field packing: votes biased non-negative,
    #   k = 32 // ceil(log2(2*S*e_per + 1)) per uint32 lane, ONE uint32 psum —
    #   bit-identical tally, ~2x less wire traffic at M=3 on a 4-wide model
    #   axis) | "rs_ag" (beyond-paper: reduce-scatter the votes (guard-bit
    #   packed when d tiles into lanes), threshold the local d/S shard,
    #   bit-pack to uint8, all-gather d/8 bytes; see EXPERIMENTS.md §Perf)
    representation: str = "unpacked"  # HV storage on the serve path: "unpacked"
    #   (uint8 {0,1}, fp32 bipolar MXU similarity) | "packed" (uint32 words,
    #   XOR+popcount similarity — how the IMC macro actually stores a row; d/8
    #   bytes per HV, prediction-identical to unpacked on the same RNG stream)
    #   | "sparse" (ultra-sparse index lists, `core.sparse`: queries travel as
    #   k_max sorted int32 bit indices — 4*k_max bytes per HV regardless of d,
    #   the regime d ~ 10^6 at ~0.1% density where dense words blow VMEM and
    #   wire; prototypes stay packed words and the top-1 is the gather-overlap
    #   kernels/sparse family, distance-identical to the packed scan) | "auto"
    #   (resolve_representation picks sparse vs packed per (dim, k_max) from
    #   the measured density crossover, cached per workload)
    noise: str = "exact"         # packed-path BSC mask source: "exact" (pack the
    #   same Bernoulli draw as the unpacked path — bit-identical, used for the
    #   parity tests) | "bitplane" (draw uint32 mask words directly via a
    #   bit-sliced comparator — `noise_planes` random bits per mask bit instead
    #   of the 32 the unpacked Bernoulli pays). Unpacked representation always
    #   draws the plain Bernoulli mask.
    noise_planes: int = 16       # bitplane-mode mask precision: BER quantized to
    #   2^-planes. 8 is plenty for the paper's operating points (BER 1e-2..1e-1
    #   against an accuracy curve that is flat out to BER 0.26, Fig. 10) and
    #   halves the mask-generation traffic again; 16 is the conservative default.
    channel: str = "bsc"         # PHY fidelity tier (repro.phy): "ideal" (error-
    #   free link) | "bsc" (default: per-core BSC at the precharacterized Eq. 1
    #   BER — the paper's abstraction, bit-identical to the historical serve
    #   noise on the same RNG stream) | "symbol" (full physics in-graph: ONE
    #   int32 psum of the per-dimension TX bit-combo == the constellation
    #   superposition, then per-core AWGN + decision-region decode; requires a
    #   real ChannelState from `precharacterize_state` and collective="psum")
    coarse_group: int = 0        # two-level coarse-to-fine search (0 = flat
    #   scan). >0 groups each core's class rows into contiguous blocks of
    #   `coarse_group` and summarizes every block with its strict-majority
    #   bundle; the serve screens the C_core/coarse_group summaries first
    #   (fused top-k kernel / lax.top_k), keeps the best `coarse_keep` groups
    #   per (core, query), and runs the exact scan ONLY on the survivors —
    #   the per-core class-axis work drops from C_core to
    #   C_core/coarse_group + coarse_keep*coarse_group. Summaries are
    #   recomputed in-graph from the (post-stuck-mask) resident rows each
    #   step (C x W word-ops, negligible against the B x C x W search), so
    #   the coarse path composes with faults/tenant onboarding with no new
    #   serve inputs and no recompile. Baseline bundling only (permuted banks
    #   would need one summary set per TX signature); must divide
    #   n_classes/n_rx_cores.
    coarse_keep: int = 8         # surviving groups per (core, query) — the
    #   screen's recall knob (clamped to the group count; keep == group count
    #   is bit-identical to the flat scan). Survivors are rescored in
    #   ascending class order, so whenever the flat winner survives the screen
    #   the prediction AND maxsim are bit-identical to the flat scan.
    k_max: int = 0               # sparse index-list capacity (sparse/auto
    #   representations only): each HV carries at most k_max set-bit indices
    #   (sorted int32, SENTINEL-padded — `core.sparse`). Pick k_max with
    #   headroom over density*dim (the bundle of M sparse HVs can hold up to
    #   the union of their indices before majority thresholding); results
    #   saturate to the k_max smallest indices, deterministically.
    m_active: int | None = None  # link-adaptation M-drop: only the first
    #   m_active TXs transmit (others abstain); None = all m_tx. Must be odd
    #   (majority ties) and needs a vote-wire tier — the symbol tier's
    #   constellation assumes all M TXs superpose. A single-TX bundle (M=1)
    #   IS the class hypervector: maximum per-bit noise margin, the
    #   controller's deepest fallback under a degraded link. Query/prediction
    #   SHAPES are unchanged (compile-once across M switches); in permuted
    #   mode only the first m_active prediction columns are meaningful.

    @property
    def packed(self) -> bool:
        return self.representation == "packed"

    @property
    def sparse(self) -> bool:
        return self.representation == "sparse"

    @property
    def m_act(self) -> int:
        return self.m_tx if self.m_active is None else self.m_active

    @property
    def words(self) -> int:
        assert self.dim % hv.WORD == 0, (self.dim, hv.WORD)
        return self.dim // hv.WORD

    def __post_init__(self):
        # unsupported combos fail HERE with a clear message, not deep inside a
        # kernel trace (mirrors the coarse-vs-permuted rejection)
        if self.representation in ("sparse", "auto"):
            if self.k_max <= 0:
                raise ValueError(
                    f"representation={self.representation!r} needs k_max > 0 "
                    "(the sparse index-list capacity); got "
                    f"k_max={self.k_max}"
                )
            if self.permuted:
                raise ValueError(
                    "representation='sparse' requires baseline bundling "
                    "(permuted TX signatures would need per-bank sparse "
                    "searches); set permuted=False"
                )
            if self.coarse_group:
                raise ValueError(
                    "representation='sparse' does not compose with the "
                    "coarse-to-fine screen (group summaries are dense "
                    "majority bundles); set coarse_group=0"
                )
            if self.collective not in ("index_ag", "psum", "psum_packed"):
                raise ValueError(
                    f"collective={self.collective!r} has no sparse wire "
                    "format; sparse serves use 'index_ag' (index-coded "
                    "all-gather) or the dense fallbacks 'psum'/'psum_packed'"
                )
            if self.channel not in ("ideal", "bsc"):
                raise ValueError(
                    f"channel={self.channel!r} is not available for the "
                    "sparse representation (the symbol tier decodes dense "
                    "per-dimension fields); use 'ideal' or 'bsc'"
                )
        elif self.collective == "index_ag":
            raise ValueError(
                "collective='index_ag' is the sparse index-list wire; "
                f"representation={self.representation!r} has no index lists "
                "to gather (use representation='sparse' or a vote collective)"
            )


# ---------------------------------------------------------------------------
# density-crossover autotuner (representation="auto")
# ---------------------------------------------------------------------------

# Built-in sparse-vs-packed crossover: sparse wins below this query density
# (k_max / dim). The analytic wire-parity point is density 1/32 (k_max int32
# indices == d/32 packed words == the guard-bit field); the MEASURED compute
# crossover from benchmarks/sparse.py (EXPERIMENTS.md §Sparse-crossover) sits
# at the same order, so the shipped default is the conservative wire-parity
# density. `set_crossover_table` installs a freshly fitted table.
DEFAULT_CROSSOVER = {"density": 1.0 / 32.0}
_crossover_table = dict(DEFAULT_CROSSOVER)
_AUTO_CACHE: dict[tuple[int, int], str] = {}


def set_crossover_table(table: dict | None) -> None:
    """Install a measured crossover fit ({"density": float}); None restores
    the built-in DEFAULT_CROSSOVER. Clears the per-workload cache."""
    global _crossover_table
    _crossover_table = dict(DEFAULT_CROSSOVER if table is None else table)
    _AUTO_CACHE.clear()


def resolve_representation(cfg: ScaleOutConfig) -> ScaleOutConfig:
    """Materialize ``representation="auto"`` into "sparse" or "packed".

    Decision rule: sparse wins when the query density ceiling ``k_max / dim``
    is below the fitted crossover density; cached per (dim, k_max) so repeat
    builds of the same workload never re-decide. The resolved config also
    carries the representation's native wire — ``index_ag`` (4*k_max bytes/HV)
    for sparse, ``psum_packed`` (guard-bit field) for packed. Non-auto configs
    pass through untouched.
    """
    if cfg.representation != "auto":
        return cfg
    key = (cfg.dim, cfg.k_max)
    rep = _AUTO_CACHE.get(key)
    if rep is None:
        rep = ("sparse" if cfg.k_max / cfg.dim < _crossover_table["density"]
               else "packed")
        _AUTO_CACHE[key] = rep
    coll = "index_ag" if rep == "sparse" else "psum_packed"
    return dataclasses.replace(cfg, representation=rep, collective=coll)


def precharacterize_state(
    cfg: ScaleOutConfig, geom: em.PackageGeometry | None = None
) -> phy.ChannelState:
    """Full channel precharacterization -> `phy.ChannelState` pytree.

    This is the paper's offline CST + MATLAB step: deterministic given the
    package geometry ("quasi-static and known a priori"). The returned state
    carries everything every PHY tier needs — Eq. 1 per-RX BER + validity for
    ``bsc``, the channel matrix / phase assignment / constellation / decision
    centroids / N0 for ``symbol``.
    """
    geom = geom or em.PackageGeometry()
    h = em.channel_matrix(geom, cfg.m_tx, cfg.n_rx_cores)
    n0 = ota.default_n0(h, cfg.snr_db)
    if cfg.m_tx <= 3:
        res = ota.optimize_phases_exhaustive(h, n0)
    else:
        res = ota.optimize_phases_coordinate(h, n0, jax.random.PRNGKey(0))
    return phy.state_from_ota(res, h)


def precharacterize(cfg: ScaleOutConfig) -> jnp.ndarray:
    """Per-IMC-core BER [n_rx_cores] — the Eq. 1 summary of
    `precharacterize_state` (kept for BER-only consumers; the serve steps take
    the full ChannelState)."""
    return precharacterize_state(cfg).ber


# ---------------------------------------------------------------------------
# mesh-level serve steps
# ---------------------------------------------------------------------------

def _dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _local_search(q: jax.Array, protos: jax.Array, use_kernels: bool) -> jax.Array:
    """Bipolar similarity dots [B_l, C_l] — the IMC crossbar MVM."""
    return assoc_matmul(q, protos, use_kernel=use_kernels, bm=8)


# ---------------------------------------------------------------------------
# serve-step stages (shared by the standalone and multi-tenant serves)
#
# Each stage runs INSIDE the shard_map body on one model shard. They are the
# verbatim standalone dataflow, generalized to arbitrary leading row dims
# (axis=-2 encoder sums, shape[:-1] reshapes) so the multi-tenant serve can
# flatten its [N_slots, B] rows through the same collectives — elementwise
# over rows, hence bit-identical per row to a standalone serve of that row.
# The OTA bundle, the RX fan-out, the shard search and the global top-1 each
# run under a `jax.named_scope` (ota_bundle, rx_copies, search, top1_gather):
# the compiled step's ops carry it in their op_name metadata, so device time
# is attributed to the stage it belongs to; values are unchanged. Inside
# ota_bundle, the cross-chip calls alone (the vote all-reduce, reduce-scatter
# and all-gather, the combo psum) run under a nested `vote_exchange` scope.
# ---------------------------------------------------------------------------

def _tx_ids(cfg: ScaleOutConfig, e_per: int):
    """This column's encoder slots: (column index, global encoder ids [e_per],
    live-voter count — slots with gid >= m_act abstain, which folds the
    link-adaptation M-drop into the same abstention mechanism as the unused
    mesh slots)."""
    tx = jax.lax.axis_index("model")
    gids = tx * e_per + jnp.arange(e_per)
    n_act_local = jnp.clip(cfg.m_act - tx * e_per, 0, e_per)
    return tx, gids, n_act_local


def _dpos(mesh: Mesh, dp: tuple[str, ...]):
    """Flat data-parallel position (pod-major) — the per-shard RNG fold."""
    if not dp:
        return jnp.int32(0)
    if len(dp) == 1:
        return jax.lax.axis_index(dp[0])
    return (
        jax.lax.axis_index(dp[0]) * mesh.axis_sizes[mesh.axis_names.index(dp[1])]
        + jax.lax.axis_index(dp[1])
    )


@jax.named_scope("ota_bundle")
def _ota_bundle(cfg: ScaleOutConfig, chan, model_size: int, e_per: int,
                q_mine, gids, n_act_local, fstate=None):
    """The OTA collective over the encoder/model axis.

    q_mine [..., e_per, d|W] (any leading row dims) -> bundled query
    [..., d|W] (or [..., d] int32 combo index for wire == "combo"). Elementwise
    over the leading rows, so flattened multi-slot batches tally bit-identically
    to per-row standalone calls.

    ``fstate`` (a `faults.FaultState`, TX-side leaves replicated) erases dead
    or dropped encoder slots from the superposition. Vote wire: the erased
    slot votes exact 0 (the abstention mechanism), the live local/total voter
    counts become traced (`total_active` re-bias of the guard-bit
    collectives), and ``tally > 0`` is automatically the live majority.
    Combo wire: the erased encoder is a stuck carrier radiating its bit-0
    phase, so its combo bit is forced 0 — the received symbol is still an
    exact constellation row (see `faults.recenter_state` for the decoder-side
    refit). With the all-healthy state every adjustment is a value identity.
    """
    d = cfg.dim
    packed = cfg.packed
    active = (gids < cfg.m_act)[:, None]
    q_bits = hv.unpack(q_mine, d) if packed else q_mine
    total_active = None
    if fstate is not None:
        erased = (fstate.dead_tx | fstate.vote_drop)[gids]      # [e_per]
        if chan.wire == "combo":
            q_bits = jnp.where(erased[:, None], jnp.uint8(0), q_bits)
        else:
            live = (gids < cfg.m_act) & ~erased
            active = active & ~erased[:, None]
            n_act_local = jnp.sum(live.astype(jnp.int32))
            slots = jnp.arange(fstate.m_slots)
            live_all = (slots < cfg.m_act) & ~(fstate.dead_tx | fstate.vote_drop)
            total_active = jnp.sum(live_all.astype(jnp.int32))
    if chan.wire == "combo":
        # physical superposition: the summed combo index IS the received
        # field (phy.channel module docstring) — ONE psum, the same
        # single-collective shape as the paper's OTA reduction. Columns
        # contribute disjoint bit ranges, so the sum stays < 2^M and the
        # wire dtype is the smallest int that fits it: at the paper's
        # M <= 7 the combo psum costs the SAME bytes as the int8 votes.
        weights = jnp.where(
            gids < cfg.m_tx, jnp.int32(1) << jnp.minimum(gids, 30), 0
        )
        partial = jnp.sum(
            q_bits.astype(jnp.int32) * weights[:, None], axis=-2
        )
        cdt = (jnp.int8 if cfg.m_tx <= 7
               else jnp.int16 if cfg.m_tx <= 15 else jnp.int32)
        with jax.named_scope("vote_exchange"):
            combo = jax.lax.psum(partial.astype(cdt), "model")
        return combo.astype(jnp.int32)  # [..., d] combo index
    # bipolar majority votes; abstaining slots (g >= m_tx) vote exact 0
    votes = jnp.sum(
        jnp.where(active, 2 * q_bits.astype(jnp.int8) - 1, 0), axis=-2
    ).astype(jnp.int8)
    if cfg.collective in ("psum", "psum_packed"):
        with jax.named_scope("vote_exchange"):
            if cfg.collective == "psum":  # paper-faithful: ONE all-reduce
                tally = jax.lax.psum(votes, "model")
            else:  # guard-bit packed votes sized by the M live voters:
                # ONE uint32 psum, bit-identical tally
                tally = collectives.packed_vote_allreduce(
                    votes, "model", group_size=model_size, e_per=e_per,
                    n_active=cfg.m_act, local_active=n_act_local,
                    total_active=total_active,
                )
        bundled_bits = (tally > 0).astype(jnp.uint8)  # even-M ties -> 0
        return hv.pack(bundled_bits) if packed else bundled_bits
    elif cfg.collective == "rs_ag":
        # reduce-scatter the votes (guard-bit packed lanes when d tiles
        # evenly — each core tallies a d/S shard), threshold locally,
        # bit-pack, all-gather d/8 packed bytes.
        if packed:
            # the gathered uint32 words ARE the bundled packed query —
            # no unpack/repack round-trip after the collective.
            assert d % (model_size * hv.WORD) == 0, (d, model_size)
            with jax.named_scope("vote_exchange"):
                part = collectives.packed_vote_psum_scatter(
                    votes, "model", group_size=model_size, e_per=e_per,
                    n_active=cfg.m_act, local_active=n_act_local,
                    total_active=total_active,
                )
            words = hv.pack((part > 0).astype(jnp.uint8))  # [..., W/S]
            with jax.named_scope("vote_exchange"):
                return jax.lax.all_gather(
                    words, "model", axis=words.ndim - 1, tiled=True
                )
        assert d % (model_size * 8) == 0, (d, model_size)
        with jax.named_scope("vote_exchange"):
            part = collectives.packed_vote_psum_scatter(
                votes, "model", group_size=model_size, e_per=e_per,
                n_active=cfg.m_act, local_active=n_act_local,
                total_active=total_active,
            )
        bits = (part > 0).astype(jnp.uint8)          # [..., d/S]
        w = bits.reshape(bits.shape[:-1] + (-1, 8))
        packed8 = jnp.sum(w << jnp.arange(8, dtype=jnp.uint8), axis=-1).astype(jnp.uint8)
        with jax.named_scope("vote_exchange"):
            allbytes = jax.lax.all_gather(
                packed8, "model", axis=packed8.ndim - 1, tiled=True
            )
        return (
            (allbytes[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
        ).reshape(bits.shape[:-1] + (d,)).astype(jnp.uint8)
    raise ValueError(cfg.collective)


@jax.named_scope("rx_copies")
def _rx_fanout(cfg: ScaleOutConfig, chan, cores_per_shard: int, tx,
               q_bundled, state, kqs):
    """Per-core decode through the PHY tier: each of this shard's IMC cores
    receives its own noisy copy of each slot's bundled query.
    q_bundled [N, B, d|W], kqs [N, 2] (a slot's key) -> [N, n_core, B, d|W]."""
    return chan.rx_copies_slots(
        kqs, q_bundled, state, rx_base=tx * cores_per_shard,
        n_cores=cores_per_shard, packed=cfg.packed, dim=cfg.dim,
        noise=cfg.noise, planes=cfg.noise_planes, use_kernel=cfg.use_kernels,
    )


def _sparse_bundle(cfg: ScaleOutConfig, chan, model_size: int, e_per: int,
                   q_mine, gids, n_act_local):
    """The OTA collective for sparse index-list queries.

    q_mine [..., e_per, k_max] int32 -> bundled [..., k_max] int32.

    ``index_ag``: each column all-gathers its slots' raw index lists
    (`collectives.sparse_index_allgather` — 4*k_max bytes per slot per HV,
    independent of d, the whole point at d ~ 10^6), then every shard runs the
    identical O(k log k) sparse majority locally. Abstaining slots (gid >=
    m_act and the sentinel-padded mesh slots) are emptied to all-SENTINEL —
    exactly a dense all-zero vote — and the strict threshold runs at
    m = m_act, so the surviving index set equals the dense ``tally > 0``
    majority wherever the union fits k_max (saturation keeps the k_max
    smallest, the canonical rule).

    ``psum``/``psum_packed``: dense fallback for the crossover benchmark —
    densify, run the verbatim `_ota_bundle` vote wire, re-sparsify.
    """
    if cfg.collective == "index_ag":
        stack = collectives.sparse_index_allgather(q_mine, "model")
        # [..., S*e_per, k_max]; slot s holds global encoder id s's list
        n_slots = model_size * e_per
        active = (jnp.arange(n_slots) < cfg.m_act)[:, None]
        stack = jnp.where(active, stack, jnp.int32(sparse.SENTINEL))
        return sparse.bundle(stack, m=cfg.m_act)
    q_bits = sparse.densify(q_mine, cfg.dim)
    bits = _ota_bundle(cfg, chan, model_size, e_per, q_bits, gids,
                       n_act_local, None)
    return sparse.sparsify(bits, cfg.k_max)


def _sparse_rx_fanout(cfg: ScaleOutConfig, cores_per_shard: int, tx,
                      q_bundled, state, kq):
    """Per-core sparse decode — the index-list analogue of `_rx_fanout`.

    ``ideal`` broadcasts the bundled list; ``bsc`` applies the O(k)
    drop+insert channel (`sparse.flip_bits_sparse`) at each core's
    precharacterized Eq. 1 BER, on the SAME per-core key schedule as
    `phy.BSCChannel.rx_copies` (``fold_in(kq, rx_base + i)``) — so switching
    a workload between dense and sparse never perturbs any OTHER core's RNG
    stream.
    """
    if cfg.channel == "ideal":
        return jnp.broadcast_to(
            q_bundled[None], (cores_per_shard,) + q_bundled.shape)
    rx_base = tx * cores_per_shard

    def one(i, ber):
        k = jax.random.fold_in(kq, rx_base + i)
        return sparse.flip_bits_sparse(k, q_bundled, ber, cfg.dim)

    return jax.vmap(one)(jnp.arange(cores_per_shard), state.ber)


def _apply_stuck(rows_arr, stuck, d: int, packed: bool, core_axis: int):
    """Force stuck prototype bits to their rail, per physical core.

    rows_arr: stored rows with the core axis at ``core_axis`` and the
    dimension words/bits last; stuck = (stuck0, stuck1) [n_core, W] packed
    column masks (a stuck crossbar column hits every row the core stores —
    including all permuted banks, which is why callers apply this AFTER
    permuting: the masks live in physical array coordinates). Zero masks are
    a value identity, preserving the zero-fault bit-identity invariant.
    """
    if stuck is None:
        return rows_arr
    s0, s1 = stuck
    shape = [1] * rows_arr.ndim
    shape[core_axis] = s0.shape[0]
    shape[-1] = s0.shape[-1]
    if packed:
        return (rows_arr & ~s0.reshape(shape)) | s1.reshape(shape)
    shape[-1] = d
    m0 = hv.unpack(s0, d).astype(bool).reshape(shape)
    m1 = hv.unpack(s1, d).astype(bool).reshape(shape)
    return jnp.where(m1, jnp.uint8(1), jnp.where(m0, jnp.uint8(0), rows_arr))


def _apply_rx_faults(fstate, tx, cores_per_shard: int, q_rx, qmask,
                     core_axis: int):
    """Dead-RX zeroing + failover query remap + fault bank masking.

    A dead core's received copy is zeroed (it answers nothing), then bank i's
    search query is gathered from physical core ``serve_rows[i]`` (global ids,
    same-shard by the `faults.plan_failover` contract; identity = no remap) —
    the query-side dual of the ``bank_rows`` prototype indirection, equally
    recompile-free. ``rx_mask`` joins the PHY quarantine mask so banks with
    no healthy server can never win the top-1. All-healthy state: zero mask,
    identity gather, all-False qmask — value-identical to no faults at all.
    """
    shape = [1] * q_rx.ndim
    shape[core_axis] = cores_per_shard
    q_rx = jnp.where(fstate.dead_rx.reshape(shape),
                     jnp.zeros((), q_rx.dtype), q_rx)
    srl = fstate.serve_rows - tx * cores_per_shard
    q_rx = jnp.take(q_rx, srl, axis=core_axis)
    qmask = fstate.rx_mask if qmask is None else (qmask | fstate.rx_mask)
    return q_rx, qmask


def _group_summaries(cfg: ScaleOutConfig, banks: jax.Array) -> jax.Array:
    """Per-bank coarse summaries: banks [T, C_core, d|W] -> [T, n_grp, d|W].

    Each contiguous `coarse_group`-row block collapses to its strict-majority
    bundle — the block's centroid in Hamming space. Computed in-graph from the
    resident rows (after stuck-at masks / tenant onboarding), so the screen
    always sees what the exact scan sees.
    """
    gs = cfg.coarse_group
    t, c_core, last = banks.shape
    grp = banks.reshape(t, c_core // gs, gs, last)
    members = jnp.moveaxis(grp, 2, 0)                 # [gs, T, n_grp, last]
    return hv.majority_packed(members) if cfg.packed else hv.majority(members)


def _coarse_fine_packed(cfg: ScaleOutConfig, banks, q, bank_rows=None):
    """Two-level packed search: coarse top-keep screen over the group
    summaries (ONE fused top-k launch), exact rescore over only the
    surviving rows. banks [T, C_core, W] (T == G when ``bank_rows`` is None),
    q [G, B, W] -> (dist, row) of each bank's winner, both [G, B] int32.

    Survivor groups are re-sorted ascending and the rescore minimizes ONE
    ``dist*c_core + row`` int32 key, so ties break toward the lowest class
    row exactly like the flat scan — predictions match the flat path whenever
    the screen recalls the true winner, and keep == n_grp is bit-identical.
    With ``bank_rows`` the survivor rows are gathered straight from the bank
    table (advanced indexing), so the expanded [G, C_core, W] view never
    materializes — the same indirection contract as `hamming_topk_banked`.
    """
    gs = cfg.coarse_group
    t, c_core, w = banks.shape
    g, b_l = q.shape[0], q.shape[1]
    n_grp = c_core // gs
    keep = min(cfg.coarse_keep, n_grp)
    summ = _group_summaries(cfg, banks)               # [T, n_grp, W]
    _, gidx = hamming_topk_banked(
        q, summ, k=keep, bank_rows=bank_rows, use_kernel=cfg.use_kernels
    )                                                 # [G, B, keep]
    gidx = jnp.sort(gidx, axis=-1)
    rows = (
        gidx[..., None] * gs + jnp.arange(gs, dtype=jnp.int32)
    ).reshape(g, b_l, keep * gs)
    bidx = jnp.arange(g, dtype=jnp.int32) if bank_rows is None else bank_rows
    cand = banks[bidx[:, None, None], rows]           # [G, B, keep*gs, W]
    x = jnp.bitwise_xor(q[:, :, None, :], cand)
    dist = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
    key = jnp.min(dist * c_core + rows, axis=-1)      # single-key first-min
    return key // c_core, key % c_core


def _coarse_fine_unpacked(cfg: ScaleOutConfig, banks, q, bank_rows=None):
    """Unpacked (fp32 bipolar MXU) coarse-to-fine: screen via the summary
    dots, rescore only the surviving rows. banks [T, C_core, d] uint8,
    q [G, B, d] -> (val f32, row i32) of each bank's winner, both [G, B].

    `lax.top_k` is stable (ties keep the lower group) and survivors are
    rescored in ascending row order through the same integer-valued fp32
    bipolar dots as the flat scan, so the (max, argmax) tail reproduces the
    flat first-maximum tie order whenever the winner survives the screen;
    keep == n_grp is bit-identical.
    """
    gs = cfg.coarse_group
    t, c_core, d = banks.shape
    g, b_l = q.shape[0], q.shape[1]
    n_grp = c_core // gs
    keep = min(cfg.coarse_keep, n_grp)
    summ = _group_summaries(cfg, banks)               # [T, n_grp, d]
    summ_g = summ if bank_rows is None else jnp.take(summ, bank_rows, axis=0)
    csims = jax.vmap(
        lambda qc, sc: _local_search(qc, sc, cfg.use_kernels)
    )(q, summ_g)                                      # [G, B, n_grp]
    gidx = jnp.sort(jax.lax.top_k(csims, keep)[1].astype(jnp.int32), axis=-1)
    rows = (
        gidx[..., None] * gs + jnp.arange(gs, dtype=jnp.int32)
    ).reshape(g, b_l, keep * gs)
    bidx = jnp.arange(g, dtype=jnp.int32) if bank_rows is None else bank_rows
    cand = banks[bidx[:, None, None], rows]           # [G, B, keep*gs, d]
    qb = 2.0 * q.astype(jnp.float32) - 1.0
    cb = 2.0 * cand.astype(jnp.float32) - 1.0
    sims = jnp.einsum("gbd,gbrd->gbr", qb, cb)        # integer-valued f32
    val = jnp.max(sims, -1)
    star = jnp.argmax(sims, -1)                       # first max among survivors
    row = jnp.take_along_axis(rows, star[..., None], -1)[..., 0]
    return val, row.astype(jnp.int32)


@jax.named_scope("search")
def _shard_top1(cfg: ScaleOutConfig, cores_per_shard: int, tx, q_rx, protos,
                qmask=None, stuck=None):
    """This shard's local top-1: each core searches its class sub-shard (with
    the M permuted banks when cfg.permuted). Returns (val, idx) — similarity
    value and GLOBAL class index of the shard winner, [B_l] or [B_l, M].

    ``qmask`` [cores_per_shard] bool quarantines cores (True = excluded): a
    quarantined core's candidates are masked BEFORE the core reduction
    (distance -> d + 1 / similarity -> -2d), so a degraded receiver can never
    win the vote for its own classes. An all-False mask is value-identical to
    qmask=None — the controller's release action costs nothing.

    ``stuck`` = (stuck0, stuck1) [cores_per_shard, W] packed column masks:
    stored bits forced to their rail in physical array coordinates
    (`_apply_stuck` — after permuting, so every bank a core stores shares
    its column faults)."""
    c_l = protos.shape[0]
    d = cfg.dim
    b_l = q_rx.shape[1]
    packed = cfg.packed
    assert c_l % cores_per_shard == 0
    c_core = c_l // cores_per_shard
    protos_c = protos.reshape(cores_per_shard, c_core, protos.shape[-1])

    if cfg.permuted:
        # expand each core's memory with the M permuted banks (paper Sec. IV)
        if packed:
            # fused top-1 over all (core, bank) pairs: the grid reduces the
            # class axis in VMEM (and spans the M bank axis too) — the
            # [G, B_l, c_core] distances never reach HBM; the in-memory
            # argmax of the IMC macro. argmin == first-max of sims exactly.
            banks = jnp.stack(
                [hv.permute_packed(protos_c, m) for m in range(cfg.m_tx)], 1
            )  # [n_core, M, c_core, W]
            banks = _apply_stuck(banks, stuck, d, True, 0)
            g = cores_per_shard * cfg.m_tx
            q_rep = jnp.broadcast_to(
                q_rx[:, None], (cores_per_shard, cfg.m_tx) + q_rx.shape[1:]
            ).reshape(g, b_l, -1)
            dmin, amin = hamming_topk_banked(
                q_rep, banks.reshape(g, c_core, -1), use_kernel=cfg.use_kernels
            )  # each [g, B_l]
            dmin = jnp.moveaxis(
                dmin.reshape(cores_per_shard, cfg.m_tx, b_l), 2, 0
            )  # [B_l, n_core, M]
            amin = jnp.moveaxis(
                amin.reshape(cores_per_shard, cfg.m_tx, b_l), 2, 0
            )
            if qmask is not None:
                dmin = jnp.where(qmask[None, :, None], d + 1, dmin)
            val = d - 2 * jnp.min(dmin, 1)                # [B_l, M]
            core_star = jnp.argmin(dmin, 1)               # [B_l, M]
            idx_in_core = jnp.take_along_axis(amin, core_star[:, None, :], 1)[:, 0, :]
        else:
            banks = jnp.stack([hv.permute(protos_c, m) for m in range(cfg.m_tx)], 1)
            # banks: [n_core, M, c_core, d]
            banks = _apply_stuck(banks, stuck, d, False, 0)
            sims = jax.vmap(
                lambda qc, pc: jax.vmap(
                    lambda bank: _local_search(qc, bank, cfg.use_kernels)
                )(pc)
            )(q_rx, banks)  # [n_core, M, B_l, c_core]
            sims = jnp.moveaxis(sims, 2, 0)  # [B_l, n_core, M, c_core]
            val_c = jnp.max(sims, -1)
            idx_c = jnp.argmax(sims, -1).astype(jnp.int32)
            if qmask is not None:
                val_c = jnp.where(qmask[None, :, None], -2.0 * d, val_c)
            val = jnp.max(val_c, 1)                       # [B_l, M]
            core_star = jnp.argmax(val_c, 1)              # [B_l, M]
            idx_in_core = jnp.take_along_axis(idx_c, core_star[:, None, :], 1)[:, 0, :]
        idx = (tx * c_l + core_star * c_core + idx_in_core).astype(jnp.int32)
    else:
        protos_c = _apply_stuck(protos_c, stuck, d, packed, 0)
        if packed or cfg.sparse:
            if cfg.sparse:
                # gather-overlap kernel on the raw index lists — integer- and
                # tie-identical to hamming_topk_banked on the densified
                # queries, so the packed downstream below is shared verbatim
                dmin, amin = sparse_topk_banked(
                    q_rx, protos_c, use_kernel=cfg.use_kernels
                )
            elif cfg.coarse_group:
                dmin, amin = _coarse_fine_packed(cfg, protos_c, q_rx)
            else:
                dmin, amin = hamming_topk_banked(
                    q_rx, protos_c, use_kernel=cfg.use_kernels
                )  # each [n_core, B_l] — distances reduced in VMEM, not HBM
            dmin = jnp.moveaxis(dmin, 1, 0)               # [B_l, n_core]
            amin = jnp.moveaxis(amin, 1, 0)
            if qmask is not None:
                dmin = jnp.where(qmask[None, :], d + 1, dmin)
            val = d - 2 * jnp.min(dmin, -1)               # [B_l]
            core_star = jnp.argmin(dmin, -1)
            idx_in_core = jnp.take_along_axis(amin, core_star[:, None], 1)[:, 0]
        else:
            if cfg.coarse_group:
                vg, rg = _coarse_fine_unpacked(cfg, protos_c, q_rx)
                val_c = jnp.moveaxis(vg, 1, 0)            # [B_l, n_core]
                idx_c = jnp.moveaxis(rg, 1, 0)
            else:
                sims = jax.vmap(
                    lambda qc, pc: _local_search(qc, pc, cfg.use_kernels)
                )(q_rx, protos_c)  # [n_core, B_l, c_core]
                sims = jnp.moveaxis(sims, 1, 0)  # [B_l, n_core, c_core]
                val_c = jnp.max(sims, -1)
                idx_c = jnp.argmax(sims, -1).astype(jnp.int32)
            if qmask is not None:
                val_c = jnp.where(qmask[None, :], -2.0 * d, val_c)
            val = jnp.max(val_c, -1)                      # [B_l]
            core_star = jnp.argmax(val_c, -1)
            idx_in_core = jnp.take_along_axis(idx_c, core_star[:, None], 1)[:, 0]
        idx = (tx * c_l + core_star * c_core + idx_in_core).astype(jnp.int32)
    return val, idx


@jax.named_scope("top1_gather")
def _gather_top1(cfg: ScaleOutConfig, val, idx):
    """Global top-1: tiny (value, index) all-gather over the cores."""
    vals = jax.lax.all_gather(val, "model")           # [S_tx, ...]
    idxs = jax.lax.all_gather(idx, "model")
    shard_star = jnp.argmax(vals, 0)
    pred = jnp.take_along_axis(idxs, shard_star[None], 0)[0]
    maxsim = jnp.max(vals, 0) / (2.0 * cfg.dim) + 0.5  # normalize to [0,1]
    return pred, maxsim


def _validate_channel(cfg: ScaleOutConfig, chan) -> None:
    """Shared serve-build validation: combo-wire and M-drop constraints."""
    if chan.wire == "combo":
        if cfg.collective != "psum":
            raise ValueError(
                f"channel={cfg.channel!r} replaces the vote reduction with the "
                f"combo-index psum; collective={cfg.collective!r} does not "
                "apply (use collective='psum')"
            )
        assert cfg.m_tx <= 16, (cfg.m_tx, "constellation table is [N, 2^M]")
    if cfg.m_act != cfg.m_tx:
        if chan.wire == "combo":
            raise ValueError(
                f"m_active={cfg.m_act} needs a vote-wire tier; "
                f"channel={cfg.channel!r} transmits the full {cfg.m_tx}-TX "
                "combo field (its constellation assumes every TX superposes)"
            )
        if not 1 <= cfg.m_act <= cfg.m_tx:
            raise ValueError(f"m_active={cfg.m_act} outside [1, {cfg.m_tx}]")
        if cfg.m_act % 2 == 0:
            raise ValueError(
                f"m_active={cfg.m_act} must be odd (majority votes tie)"
            )


def _validate_coarse(cfg: ScaleOutConfig) -> None:
    """Serve-build validation for the two-level coarse-to-fine search."""
    if not cfg.coarse_group:
        return
    if cfg.permuted:
        raise ValueError(
            "coarse_group requires baseline bundling (permuted banks would "
            "need one summary set per TX signature)"
        )
    if cfg.n_classes % cfg.n_rx_cores:
        raise ValueError(
            f"coarse search needs n_classes ({cfg.n_classes}) divisible by "
            f"n_rx_cores ({cfg.n_rx_cores})"
        )
    c_core = cfg.n_classes // cfg.n_rx_cores
    if cfg.coarse_group < 2 or c_core % cfg.coarse_group:
        raise ValueError(
            f"coarse_group={cfg.coarse_group} must be >= 2 and divide the "
            f"per-core class count {c_core}"
        )
    if cfg.coarse_keep < 1:
        raise ValueError(f"coarse_keep={cfg.coarse_keep} must be >= 1")
    if (cfg.dim + 1) * c_core >= 2**31:
        raise ValueError(
            f"rescore key (dim+1)*c_core = {(cfg.dim + 1) * c_core} would "
            "overflow int32 — shard wider (more RX cores) or shrink dim"
        )


def make_ota_serve(
    mesh: Mesh, cfg: ScaleOutConfig, process=None, faults=None
) -> Callable[..., tuple[jax.Array, ...]]:
    """Build the jitted OTA serve step.

    fn(protos [C, dim] u8, queries [B, S_tx, e_per, dim] u8,
       state phy.ChannelState, key)
      -> (pred, maxsim); pred [B] int32 (baseline) or [B, m_tx] (permuted).
    S_tx = model mesh size; e_per = ceil(m_tx / S_tx) encoders per column; global
    encoder g = column * e_per + j; slots with g >= cfg.m_tx abstain.

    The OTA link itself is the pluggable PHY tier ``cfg.channel``
    (`repro.phy`): ``bsc`` (default) keeps the historical dataflow — vote
    tally over the model axis (psum / guard-bit psum_packed / rs_ag), then a
    per-core BSC at ``state.ber`` — bit-identical to pre-phy serves on the
    same RNG stream; ``ideal`` skips the noise; ``symbol`` replaces the
    psum+BSC pair with the physical channel: ONE int32 psum of the
    per-dimension TX bit-combo (== the constellation superposition, see
    `phy.channel`), then per-core constellation lookup + AWGN +
    decision-region decode from the same ChannelState the analytic BER came
    from. ``state`` is sharded with the cores (`phy.state_spec`).

    With ``cfg.representation == "packed"`` protos/queries are uint32 word arrays
    ([C, dim/32] / [B, S_tx, e_per, dim/32], see `hv.pack`); the bundled query,
    the per-core channel noise, the prototype shards and the local search all
    stay packed (the symbol tier decodes bits, then packs): the top-1 is the
    fused `hamming_topk_banked` Pallas kernel — one launch over all cores (and
    permuted banks) that reduces the class axis in VMEM, so the [G, B, C]
    distance tensor never reaches HBM. The vote tally itself shrinks with
    ``cfg.collective == "psum_packed"`` (guard-bit field packing sized by the
    cfg.m_tx ACTIVE voters, ONE uint32 psum, bit-identical to the int8 psum).
    Predictions and maxsim are bit-identical to the unpacked path on the same
    RNG stream (cfg.noise="exact") across all collective modes.

    ``process`` (a `phy.ChannelProcess`) switches the serve to the LIVING
    channel: the built fn becomes

        fn(protos, queries, pstate phy.ProcessState, key, process_key)
          -> (pred, maxsim, pstate')

    Each call first advances the channel one process step (the per-row RNG is
    ``fold_in(fold_in(process_key, pstate.t), rx)`` — hold ``process_key``
    FIXED across steps and the state sequence is reproducible from
    `phy.rollout` on any mesh), then serves through the evolved
    ``pstate.chan`` with ``pstate.quarantine`` masking quarantined cores out
    of the top-1. The carried pytree structure is fixed, so an N-step serve
    loop compiles ONCE; with `phy.StaticProcess` predictions are bit-identical
    to the process-free fn on the same keys.

    ``faults`` (a `faults.FaultModel`) threads a `faults.FaultState` through
    the step — injected hard faults (dead encoders/cores, stuck prototype
    cells, per-step vote erasures) plus the tolerance machinery (live-voter
    re-bias, ``serve_rows`` failover, ``rx_mask`` bank exclusion; see
    `repro.faults`). The built fn appends ``(fstate, fault_key)`` inputs and
    an evolved ``fstate'`` output after the process arguments:

        fn(protos, queries, state, key, fstate, fault_key)
          -> (pred, maxsim, fstate')                       # process=None
        fn(protos, queries, pstate, key, pkey, fstate, fault_key)
          -> (pred, maxsim, pstate', fstate')              # both

    With `faults.healthy_state` (and any model whose step leaves it healthy)
    predictions are bit-identical to the faults-free fn on the same keys —
    fault evolution consumes only ``fault_key``, never the serve stream.

    ``cfg.representation == "sparse"`` serves ultra-sparse queries as sorted
    int32 index lists ([B, S_tx, e_per, k_max], `core.sparse`): the OTA wire
    becomes `collectives.sparse_index_allgather` + a local O(k log k) sparse
    majority (``collective="index_ag"``; psum/psum_packed remain as dense
    fallbacks for the crossover benchmark), the per-core BSC is the O(k)
    drop+insert channel, and the top-1 is the gather-overlap
    `sparse_topk_banked` kernel over the UNCHANGED packed prototype shards
    [C, dim/32] — predictions are bit-identical to the packed serve at
    channel="ideal" whenever no bundle saturates k_max. "auto" picks sparse
    vs packed per (dim, k_max) from the fitted density crossover
    (`resolve_representation`).
    """
    cfg = resolve_representation(cfg)
    model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
    assert cfg.n_rx_cores % model_size == 0, (cfg.n_rx_cores, model_size)
    cores_per_shard = cfg.n_rx_cores // model_size
    e_per = -(-cfg.m_tx // model_size)
    dp = _dp_axes(mesh)
    manual = set(dp) | {"model"}
    packed = cfg.packed
    chan = phy.get_channel(cfg.channel)
    _validate_channel(cfg, chan)
    _validate_coarse(cfg)
    if cfg.sparse and (process is not None or faults is not None):
        raise ValueError(
            "representation='sparse' does not compose with living-channel "
            "processes or fault injection (stuck-at / failover state is "
            "word-addressed dense machinery); use representation='packed'"
        )

    def serve_core(protos, queries, state, key, qmask, fstate=None):
        # protos: [C_l, d|W]; queries: [B_l, 1, e_per, d|W];
        # state: local ChannelState shard (RX-leading leaves [cores_per_shard])
        tx, gids, n_act_local = _tx_ids(cfg, e_per)
        q_mine = queries[:, 0]                      # [B_l, e_per, d|W]
        if cfg.permuted:  # TX g transmits rho^g(q_g) — its signature
            rho = hv.permute_packed if packed else hv.permute
            q_mine = jax.vmap(lambda q, g: rho(q, g), in_axes=(1, 0), out_axes=1)(
                q_mine, gids
            )
        # --- the OTA collective over the encoder/model axis ---
        if cfg.sparse:
            q_bundled = _sparse_bundle(cfg, chan, model_size, e_per, q_mine,
                                       gids, n_act_local)
        else:
            q_bundled = _ota_bundle(cfg, chan, model_size, e_per, q_mine,
                                    gids, n_act_local, fstate)
        # --- per-core decode through the PHY tier ---
        kq = jax.random.fold_in(key, _dpos(mesh, dp))
        if cfg.sparse:
            q_rx = _sparse_rx_fanout(cfg, cores_per_shard, tx, q_bundled,
                                     state, kq)
        else:
            q_rx = _rx_fanout(cfg, chan, cores_per_shard, tx, q_bundled[None],
                              state, kq[None])[0]
        # [n_core, B_l, d|W] -> each core searches its class sub-shard
        stuck = None
        if fstate is not None:
            q_rx, qmask = _apply_rx_faults(fstate, tx, cores_per_shard, q_rx,
                                           qmask, 0)
            stuck = (fstate.stuck0, fstate.stuck1)
        val, idx = _shard_top1(cfg, cores_per_shard, tx, q_rx, protos, qmask,
                               stuck)
        # --- global top-1: tiny (value, index) all-gather over the cores ---
        return _gather_top1(cfg, val, idx)

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    if process is None and faults is None:
        def body(protos, queries, state, key):
            return serve_core(protos, queries, state, key, None)

        in_specs = (
            P("model", None),                 # prototype shards (the IMC cores)
            P(dp_spec, "model", None, None),  # per-encoder queries
            phy.state_spec("model"),          # per-core channel state
            P(),                              # key
        )
        out_specs = (P(dp_spec), P(dp_spec))
    elif faults is None:
        def body(protos, queries, pstate, key, pkey):
            tx = jax.lax.axis_index("model")
            # evolve the channel one step, THEN serve through the live state
            pstate = process.step(pkey, pstate, rx_base=tx * cores_per_shard)
            pred, maxsim = serve_core(protos, queries, pstate.chan, key,
                                      pstate.quarantine)
            return pred, maxsim, pstate

        in_specs = (
            P("model", None),
            P(dp_spec, "model", None, None),
            phy.pstate_spec("model"),         # per-core process state
            P(),                              # serve key
            P(),                              # process key (fixed across steps)
        )
        out_specs = (P(dp_spec), P(dp_spec), phy.pstate_spec("model"))
    elif process is None:
        def body(protos, queries, state, key, fstate, fkey):
            tx = jax.lax.axis_index("model")
            # evolve the faults one step, THEN serve through the live state
            fstate = faults.step(fkey, fstate, rx_base=tx * cores_per_shard)
            pred, maxsim = serve_core(protos, queries, state, key, None,
                                      fstate)
            return pred, maxsim, fstate

        in_specs = (
            P("model", None),
            P(dp_spec, "model", None, None),
            phy.state_spec("model"),
            P(),                              # serve key
            faultlib.fstate_spec("model"),    # per-core fault state
            P(),                              # fault key (fixed across steps)
        )
        out_specs = (P(dp_spec), P(dp_spec), faultlib.fstate_spec("model"))
    else:
        def body(protos, queries, pstate, key, pkey, fstate, fkey):
            tx = jax.lax.axis_index("model")
            pstate = process.step(pkey, pstate, rx_base=tx * cores_per_shard)
            fstate = faults.step(fkey, fstate, rx_base=tx * cores_per_shard)
            pred, maxsim = serve_core(protos, queries, pstate.chan, key,
                                      pstate.quarantine, fstate)
            return pred, maxsim, pstate, fstate

        in_specs = (
            P("model", None),
            P(dp_spec, "model", None, None),
            phy.pstate_spec("model"),
            P(),                              # serve key
            P(),                              # process key
            faultlib.fstate_spec("model"),
            P(),                              # fault key
        )
        out_specs = (P(dp_spec), P(dp_spec), phy.pstate_spec("model"),
                     faultlib.fstate_spec("model"))

    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=manual,
        check_vma=False,
    )
    return jax.jit(fn)


@jax.named_scope("search")
def _shard_top1_slots(cfg: ScaleOutConfig, cores_per_shard: int, tx,
                      q_rx, store, rows, qmask=None, stuck=None):
    """Slot-batched local top-1: slot s searches tenant bank ``rows[s]`` of the
    resident store. ONE `hamming_topk_banked` launch covers every
    (slot, core[, permuted bank]) — the G axis of the kernel grid — via the
    ``bank_rows`` indirection (packed) or a row gather (unpacked MXU path);
    never a vmap over the kernel (its revisited-tile running-min is not
    vmap-safe). Per-slot reductions keep the standalone [B, core(, M), class]
    axis order, so ties break identically to `_shard_top1` on that slot alone.

    q_rx [N, n_core, B_l, d|W]; store [T, C_l, d|W]; rows [N] int32.
    ``qmask`` [cores_per_shard] bool quarantines cores exactly as in
    `_shard_top1` (masked before the core reduction; all slots share the one
    physical link, so one mask covers them all). ``stuck`` applies the
    per-core stuck-at column masks to the resident store (one physical
    crossbar per core — every tenant's rows on it share the core's faults).
    Returns (val, idx) [N, B_l] or [N, B_l, M].
    """
    t, c_l = store.shape[0], store.shape[1]
    last = store.shape[-1]
    d = cfg.dim
    n, b_l = q_rx.shape[0], q_rx.shape[2]
    packed = cfg.packed
    assert c_l % cores_per_shard == 0
    c_core = c_l // cores_per_shard
    core_ids = jnp.arange(cores_per_shard)
    store_c = store.reshape(t, cores_per_shard, c_core, last)

    if cfg.permuted:
        if packed:
            # permute the T-tenant store ONCE (not per slot); bank g of the
            # single launch is (slot, core, m) -> store row rows[slot]
            banks = jnp.stack(
                [hv.permute_packed(store_c, m) for m in range(cfg.m_tx)], 2
            )  # [T, n_core, M, c_core, W]
            banks = _apply_stuck(banks, stuck, d, True, 1)
            bank_rows = (
                (rows[:, None] * cores_per_shard + core_ids[None])[:, :, None]
                * cfg.m_tx + jnp.arange(cfg.m_tx)[None, None]
            ).reshape(-1)
            g = n * cores_per_shard * cfg.m_tx
            q_rep = jnp.broadcast_to(
                q_rx[:, :, None], (n, cores_per_shard, cfg.m_tx) + q_rx.shape[2:]
            ).reshape(g, b_l, last)
            dmin, amin = hamming_topk_banked(
                q_rep, banks.reshape(t * cores_per_shard * cfg.m_tx, c_core, last),
                bank_rows=bank_rows, use_kernel=cfg.use_kernels,
            )  # each [g, B_l]
            dmin = jnp.moveaxis(
                dmin.reshape(n, cores_per_shard, cfg.m_tx, b_l), 3, 1
            )  # [N, B_l, n_core, M]
            amin = jnp.moveaxis(
                amin.reshape(n, cores_per_shard, cfg.m_tx, b_l), 3, 1
            )
            if qmask is not None:
                dmin = jnp.where(qmask[None, None, :, None], d + 1, dmin)
            val = d - 2 * jnp.min(dmin, 2)                # [N, B_l, M]
            core_star = jnp.argmin(dmin, 2)
            idx_in_core = jnp.take_along_axis(
                amin, core_star[:, :, None, :], 2
            )[:, :, 0, :]
        else:
            banks = jnp.stack(
                [hv.permute(store_c, m) for m in range(cfg.m_tx)], 2
            )  # [T, n_core, M, c_core, d]
            banks = _apply_stuck(banks, stuck, d, False, 1)
            banks_n = jnp.take(banks, rows, axis=0)  # [N, n_core, M, c_core, d]
            sims = jax.vmap(jax.vmap(
                lambda qc, pc: jax.vmap(
                    lambda bank: _local_search(qc, bank, cfg.use_kernels)
                )(pc)
            ))(q_rx, banks_n)  # [N, n_core, M, B_l, c_core]
            sims = jnp.moveaxis(sims, 3, 1)  # [N, B_l, n_core, M, c_core]
            val_c = jnp.max(sims, -1)
            idx_c = jnp.argmax(sims, -1).astype(jnp.int32)
            if qmask is not None:
                val_c = jnp.where(qmask[None, None, :, None], -2.0 * d, val_c)
            val = jnp.max(val_c, 2)                       # [N, B_l, M]
            core_star = jnp.argmax(val_c, 2)
            idx_in_core = jnp.take_along_axis(
                idx_c, core_star[:, :, None, :], 2
            )[:, :, 0, :]
    else:
        store_c = _apply_stuck(store_c, stuck, d, packed, 1)
        if packed:
            bank_rows = (
                rows[:, None] * cores_per_shard + core_ids[None]
            ).reshape(-1)
            q_flat = q_rx.reshape(n * cores_per_shard, b_l, last)
            if cfg.coarse_group:
                dmin, amin = _coarse_fine_packed(
                    cfg, store_c.reshape(t * cores_per_shard, c_core, last),
                    q_flat, bank_rows=bank_rows,
                )  # each [N*n_core, B_l]
            else:
                dmin, amin = hamming_topk_banked(
                    q_flat, store_c.reshape(t * cores_per_shard, c_core, last),
                    bank_rows=bank_rows, use_kernel=cfg.use_kernels,
                )  # each [N*n_core, B_l]
            dmin = jnp.moveaxis(dmin.reshape(n, cores_per_shard, b_l), 2, 1)
            amin = jnp.moveaxis(amin.reshape(n, cores_per_shard, b_l), 2, 1)
            if qmask is not None:
                dmin = jnp.where(qmask[None, None, :], d + 1, dmin)
            val = d - 2 * jnp.min(dmin, -1)               # [N, B_l]
            core_star = jnp.argmin(dmin, -1)
            idx_in_core = jnp.take_along_axis(
                amin, core_star[..., None], -1
            )[..., 0]
        else:
            if cfg.coarse_group:
                core_rows = (
                    rows[:, None] * cores_per_shard + core_ids[None]
                ).reshape(-1)
                vg, rg = _coarse_fine_unpacked(
                    cfg, store_c.reshape(t * cores_per_shard, c_core, last),
                    q_rx.reshape(n * cores_per_shard, b_l, last),
                    bank_rows=core_rows,
                )  # each [N*n_core, B_l]
                val_c = jnp.moveaxis(vg.reshape(n, cores_per_shard, b_l), 2, 1)
                idx_c = jnp.moveaxis(rg.reshape(n, cores_per_shard, b_l), 2, 1)
            else:
                protos_n = jnp.take(store_c, rows, axis=0)
                # protos_n: [N, n_core, c_core, d]
                sims = jax.vmap(jax.vmap(
                    lambda qc, pc: _local_search(qc, pc, cfg.use_kernels)
                ))(q_rx, protos_n)  # [N, n_core, B_l, c_core]
                sims = jnp.moveaxis(sims, 2, 1)  # [N, B_l, n_core, c_core]
                val_c = jnp.max(sims, -1)
                idx_c = jnp.argmax(sims, -1).astype(jnp.int32)
            if qmask is not None:
                val_c = jnp.where(qmask[None, None, :], -2.0 * d, val_c)
            val = jnp.max(val_c, -1)                      # [N, B_l]
            core_star = jnp.argmax(val_c, -1)
            idx_in_core = jnp.take_along_axis(
                idx_c, core_star[..., None], -1
            )[..., 0]
    idx = (tx * c_l + core_star * c_core + idx_in_core).astype(jnp.int32)
    return val, idx


def make_mt_ota_serve(mesh: Mesh, cfg: ScaleOutConfig, process=None,
                      faults=None) -> Callable:
    """Build the multi-tenant slot-batched OTA serve step.

    fn(store [T, C, d|W], queries [N, B, S_tx, e_per, d|W], rows [N] i32,
       state phy.ChannelState, keys [N, 2] u32)
      -> (pred, maxsim), each [N, B] (baseline) or [N, B, M] (permuted).

    One launch serves N resident slots against a T-tenant prototype store
    (class axis sharded over ``model`` exactly like the standalone serve —
    every tenant's bank lives on the same IMC cores); slot s searches tenant
    bank ``rows[s]``. Onboarding/eviction edit the store outside this fn
    (``dynamic_update_slice`` of one tenant row — no recompile here).

    Per-slot prediction identity with `make_ota_serve`: the bundle collective
    runs on the slot-flattened [N*B] rows through the SAME stage code
    (elementwise over rows), the PHY fan-out vmaps over slots with slot s's
    own key (vmapped counter-based RNG == the standalone draw for that key),
    and the slot-batched search keeps standalone per-slot reduction order. So
    row s of the output is bit-identical to a standalone serve of slot s's
    queries against its tenant's codebook with key ``keys[s]`` — the lifecycle
    tests pin this across representations and channels.

    ``process`` switches to the living-channel form (see `make_ota_serve`):

        fn(store, queries, rows, pstate, keys, process_key)
          -> (pred, maxsim, pstate')

    ONE process step per serve step — every slot shares the one physical
    link, evolved before the batched decode and searched under the shared
    ``pstate.quarantine`` mask.

    ``faults`` threads a shared `faults.FaultState` exactly as in
    `make_ota_serve` (one fault step per serve step — every slot rides the
    same hardware): the fn appends ``(fstate, fault_key)`` inputs and a
    ``fstate'`` output after the process arguments, and with the all-healthy
    state stays bit-identical to the faults-free build.
    """
    if cfg.representation in ("sparse", "auto"):
        raise ValueError(
            "the multi-tenant serve does not support the sparse "
            "representation (slot-batched bank indirection is a dense-store "
            "contract); use representation='packed'"
        )
    model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
    assert cfg.n_rx_cores % model_size == 0, (cfg.n_rx_cores, model_size)
    cores_per_shard = cfg.n_rx_cores // model_size
    e_per = -(-cfg.m_tx // model_size)
    dp = _dp_axes(mesh)
    manual = set(dp) | {"model"}
    packed = cfg.packed
    chan = phy.get_channel(cfg.channel)
    _validate_channel(cfg, chan)
    _validate_coarse(cfg)

    def serve_core(store, queries, rows, state, keys, qmask, fstate=None):
        # store: [T, C_l, d|W]; queries: [N, B_l, 1, e_per, d|W]; rows: [N];
        # keys: [N, 2] — slot s serves with its request's own RNG stream
        n, b_l = queries.shape[0], queries.shape[1]
        tx, gids, n_act_local = _tx_ids(cfg, e_per)
        q_mine = queries[:, :, 0]                   # [N, B_l, e_per, d|W]
        q_flat = q_mine.reshape((n * b_l,) + q_mine.shape[2:])
        if cfg.permuted:  # TX g transmits rho^g(q_g) — its signature
            rho = hv.permute_packed if packed else hv.permute
            q_flat = jax.vmap(lambda q, g: rho(q, g), in_axes=(1, 0), out_axes=1)(
                q_flat, gids
            )
        # --- ONE OTA collective for all slots: elementwise over the flattened
        # [N*B] rows, so each row tallies exactly as its standalone serve ---
        q_bundled = _ota_bundle(cfg, chan, model_size, e_per, q_flat, gids,
                                n_act_local, fstate)
        q_bundled = q_bundled.reshape((n, b_l) + q_bundled.shape[1:])
        # --- PHY fan-out per slot with the slot's own key (RNG identity) ---
        dpos = _dpos(mesh, dp)
        kqs = jax.vmap(lambda k: jax.random.fold_in(k, dpos))(keys)
        q_rx = _rx_fanout(cfg, chan, cores_per_shard, tx, q_bundled, state,
                          kqs)  # [N, n_core, B_l, d|W]
        stuck = None
        if fstate is not None:
            q_rx, qmask = _apply_rx_faults(fstate, tx, cores_per_shard, q_rx,
                                           qmask, 1)
            stuck = (fstate.stuck0, fstate.stuck1)
        # --- slot-batched search: one banked launch over (slot, core, bank) ---
        val, idx = _shard_top1_slots(cfg, cores_per_shard, tx, q_rx, store,
                                     rows, qmask, stuck)
        return _gather_top1(cfg, val, idx)

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    if process is None and faults is None:
        def body(store, queries, rows, state, keys):
            return serve_core(store, queries, rows, state, keys, None)

        in_specs = (
            P(None, "model", None),                 # tenant store (class-sharded)
            P(None, dp_spec, "model", None, None),  # per-slot encoder queries
            P(),                                    # slot -> store row
            phy.state_spec("model"),                # per-core channel state
            P(),                                    # per-slot keys
        )
        out_specs = (P(None, dp_spec), P(None, dp_spec))
    elif faults is None:
        def body(store, queries, rows, pstate, keys, pkey):
            tx = jax.lax.axis_index("model")
            pstate = process.step(pkey, pstate, rx_base=tx * cores_per_shard)
            pred, maxsim = serve_core(store, queries, rows, pstate.chan, keys,
                                      pstate.quarantine)
            return pred, maxsim, pstate

        in_specs = (
            P(None, "model", None),
            P(None, dp_spec, "model", None, None),
            P(),
            phy.pstate_spec("model"),               # per-core process state
            P(),                                    # per-slot keys
            P(),                                    # process key (fixed)
        )
        out_specs = (P(None, dp_spec), P(None, dp_spec),
                     phy.pstate_spec("model"))
    elif process is None:
        def body(store, queries, rows, state, keys, fstate, fkey):
            tx = jax.lax.axis_index("model")
            fstate = faults.step(fkey, fstate, rx_base=tx * cores_per_shard)
            pred, maxsim = serve_core(store, queries, rows, state, keys, None,
                                      fstate)
            return pred, maxsim, fstate

        in_specs = (
            P(None, "model", None),
            P(None, dp_spec, "model", None, None),
            P(),
            phy.state_spec("model"),
            P(),                                    # per-slot keys
            faultlib.fstate_spec("model"),          # per-core fault state
            P(),                                    # fault key (fixed)
        )
        out_specs = (P(None, dp_spec), P(None, dp_spec),
                     faultlib.fstate_spec("model"))
    else:
        def body(store, queries, rows, pstate, keys, pkey, fstate, fkey):
            tx = jax.lax.axis_index("model")
            pstate = process.step(pkey, pstate, rx_base=tx * cores_per_shard)
            fstate = faults.step(fkey, fstate, rx_base=tx * cores_per_shard)
            pred, maxsim = serve_core(store, queries, rows, pstate.chan, keys,
                                      pstate.quarantine, fstate)
            return pred, maxsim, pstate, fstate

        in_specs = (
            P(None, "model", None),
            P(None, dp_spec, "model", None, None),
            P(),
            phy.pstate_spec("model"),
            P(),                                    # per-slot keys
            P(),                                    # process key (fixed)
            faultlib.fstate_spec("model"),          # per-core fault state
            P(),                                    # fault key (fixed)
        )
        out_specs = (P(None, dp_spec), P(None, dp_spec),
                     phy.pstate_spec("model"), faultlib.fstate_spec("model"))

    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=manual,
        check_vma=False,
    )
    return jax.jit(fn)


def make_wired_serve(
    mesh: Mesh, cfg: ScaleOutConfig
) -> Callable[[jax.Array, jax.Array, phy.ChannelState, jax.Array], tuple[jax.Array, jax.Array]]:
    """Wired-baseline dataflow: queries all-gathered over the NoC, bundled at every
    core (broadcast M·d bytes/trial instead of the OTA psum). Error-free wires —
    the ChannelState rides along for signature parity with `make_ota_serve`
    (matched-physics wired-vs-OTA comparisons thread the same state through
    both) but no PHY noise applies on the NoC.
    Same outputs as `make_ota_serve` (baseline bundling only). Packed
    representation: the NoC broadcast moves d/8 bytes per HV, bundling runs the
    bit-sliced carry-save majority, similarity is XOR+popcount."""
    if cfg.representation in ("sparse", "auto"):
        raise ValueError(
            "the wired baseline has no sparse dataflow (the comparison the "
            "paper draws is dense-field NoC broadcast vs OTA); use "
            "representation='packed'"
        )
    model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
    cores_per_shard = cfg.n_rx_cores // model_size
    dp = _dp_axes(mesh)
    manual = set(dp) | {"model"}
    packed = cfg.packed

    e_per = -(-cfg.m_tx // model_size)

    def body(protos, queries, state, key):
        c_l = protos.shape[0]
        d = cfg.dim
        last = queries.shape[-1]
        tx = jax.lax.axis_index("model")
        # --- wired pattern: explicit all-gather (the NoC broadcast bottleneck) ---
        q_all = jax.lax.all_gather(queries[:, 0], "model", axis=0)  # [S_tx, B_l, e, d|W]
        q_act = jnp.moveaxis(q_all, 2, 1).reshape(-1, q_all.shape[1], last)[: cfg.m_tx]
        if packed:
            q_bundled = hv.majority_packed(q_act)
            sims = d - 2 * hamming_search(q_bundled, protos, use_kernel=cfg.use_kernels)
        else:
            q_bundled = majority_bundle(q_act, use_kernel=cfg.use_kernels)
            sims = _local_search(q_bundled, protos, cfg.use_kernels)  # [B_l, C_l]
        val = jnp.max(sims, -1)
        idx = (jnp.argmax(sims, -1) + tx * c_l).astype(jnp.int32)
        vals = jax.lax.all_gather(val, "model")
        idxs = jax.lax.all_gather(idx, "model")
        shard_star = jnp.argmax(vals, 0)
        pred = jnp.take_along_axis(idxs, shard_star[None], 0)[0]
        maxsim = jnp.max(vals, 0) / (2.0 * cfg.dim) + 0.5
        return pred, maxsim

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P("model", None), P(dp_spec, "model", None, None),
                  phy.state_spec("model"), P()),
        out_specs=(P(dp_spec), P(dp_spec)),
        axis_names=manual,
        check_vma=False,
    )
    return jax.jit(fn)


def make_hdc_train(
    mesh: Mesh, cfg: ScaleOutConfig
) -> Callable[[jax.Array, jax.Array], jax.Array]:
    """One-shot HDC 'training': bundle every class's examples into its prototype.

    fn(examples [B, dim] u8, labels [B] i32) -> protos [C, dim] u8 (sharded over
    model). Bipolar per-class sums are psum'd over the data axes (the learning
    analogue of the OTA reduction), then thresholded — majority bundling of all
    examples of a class. Packed representation: examples/protos are uint32 word
    arrays [.., dim/32]; the per-bit tally unpacks transiently, the learned
    prototype shards are stored packed (what the IMC macro would write).
    """
    dp = _dp_axes(mesh)
    manual = set(dp) | {"model"}
    model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
    assert cfg.n_classes % model_size == 0
    c_l = cfg.n_classes // model_size
    packed = cfg.packed

    def body(examples, labels):
        tx = jax.lax.axis_index("model")
        lo = tx * c_l
        onehot = (labels[:, None] == (lo + jnp.arange(c_l))[None, :]).astype(jnp.int32)
        ex = hv.unpack(examples, cfg.dim) if packed else examples
        bipolar = 2 * ex.astype(jnp.int32) - 1              # [B_l, d]
        sums = jnp.einsum("bc,bd->cd", onehot, bipolar)     # [C_l, d]
        for ax in dp:
            sums = jax.lax.psum(sums, ax)
        protos = (sums > 0).astype(jnp.uint8)
        return hv.pack(protos) if packed else protos

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    fn = compat.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(dp_spec, None), P(dp_spec)),
        out_specs=P("model", None),
        axis_names=manual,
        check_vma=False,
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# host-level helpers (inputs + single-device oracle)
# ---------------------------------------------------------------------------

def make_queries(
    key: jax.Array, cfg: ScaleOutConfig, protos: jax.Array, model_size: int
) -> tuple[jax.Array, jax.Array]:
    """Random trial queries: classes [B, m_tx], queries [B, S_tx, e_per, dim].

    `protos` is the unpacked [C, dim] codebook; with a packed cfg the returned
    queries are bit-packed to [B, S_tx, e_per, dim/32] uint32 (pack the protos
    with `hv.pack` before feeding the packed serve fn). With a sparse cfg the
    SAME classes draw yields sorted index lists [B, S_tx, e_per, k_max] int32
    (`sparse.sparsify` of each class HV — keep-smallest truncation past
    k_max), padded slots all-SENTINEL; feed the serve fn `hv.pack(protos)`.
    """
    k1 = jax.random.fold_in(key, 1)
    e_per = -(-cfg.m_tx // model_size)
    classes = jax.random.randint(k1, (cfg.batch, cfg.m_tx), 0, cfg.n_classes)
    if cfg.sparse:
        codes = sparse.sparsify(protos, cfg.k_max)        # [C, k_max]
        q = codes[classes]                                # [B, M, k_max]
        pad = jnp.full(
            (cfg.batch, model_size * e_per - cfg.m_tx, cfg.k_max),
            sparse.SENTINEL, jnp.int32)
        q = jnp.concatenate([q, pad], axis=1)
        return classes, q.reshape(cfg.batch, model_size, e_per, cfg.k_max)
    q = protos[classes]  # [B, M, d]
    pad = jnp.zeros((cfg.batch, model_size * e_per - cfg.m_tx, cfg.dim), jnp.uint8)
    q = jnp.concatenate([q, pad], axis=1)
    q = q.reshape(cfg.batch, model_size, e_per, cfg.dim)
    return classes, (hv.pack(q) if cfg.packed else q)


def serve_reference(
    cfg: ScaleOutConfig, protos: jax.Array, queries: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """Single-device noise-free oracle for the distributed serve step.

    Always computes in the unpacked representation; packed (uint32) protos or
    queries are unpacked first, and sparse (int32 index-list) queries are
    densified, so the same oracle serves every dataflow. Sparse queries carry
    the keep-smallest k_max truncation already; the oracle's dense majority
    has no further capacity, so it matches the sparse serve exactly whenever
    no bundle saturates.
    Honors ``cfg.m_active`` (only the first m_act TXs bundle — the M-drop
    oracle); permuted predictions keep all m_tx columns, of which only the
    first m_act are meaningful, matching the serve step.
    """
    if queries.dtype == jnp.int32:    # sparse index lists
        queries = sparse.densify(queries, cfg.dim)
    if queries.dtype == jnp.uint32:
        queries = hv.unpack(queries, cfg.dim)
    if protos.dtype == jnp.uint32:
        protos = hv.unpack(protos, cfg.dim)
    b = queries.shape[0]
    m_act = cfg.m_act
    q_act = queries.reshape(b, -1, cfg.dim)[:, :m_act, :]
    if cfg.permuted:
        shifts = jnp.arange(m_act)
        q_act = jax.vmap(lambda qs: hv.permute_batch(qs, shifts))(q_act)
        q = jnp.moveaxis(q_act, 1, 0)
        counts = jnp.sum(q.astype(jnp.int32), 0)
        bundled = (counts * 2 > m_act).astype(jnp.uint8)
        banks = jnp.stack([hv.permute(protos, m) for m in range(cfg.m_tx)], 0)
        sims = jnp.einsum(
            "bd,mcd->bmc",
            2.0 * bundled.astype(jnp.float32) - 1,
            2.0 * banks.astype(jnp.float32) - 1,
        )
        pred = jnp.argmax(sims, -1).astype(jnp.int32)
        maxsim = jnp.max(sims, -1) / (2.0 * cfg.dim) + 0.5
        return pred, maxsim
    q = jnp.moveaxis(q_act, 1, 0)
    counts = jnp.sum(q.astype(jnp.int32), 0)
    bundled = (counts * 2 > m_act).astype(jnp.uint8)
    sims = jnp.einsum(
        "bd,cd->bc",
        2.0 * bundled.astype(jnp.float32) - 1,
        2.0 * protos.astype(jnp.float32) - 1,
    )
    pred = jnp.argmax(sims, -1).astype(jnp.int32)
    maxsim = jnp.max(sims, -1) / (2.0 * cfg.dim) + 0.5
    return pred, maxsim
