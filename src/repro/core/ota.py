"""Over-the-air (OTA) majority computation — constellation engineering.

The paper's central mechanism (Sec. IV): M transmitters emit simultaneously, each
encoding its bit in one of two phases drawn from an 8-phase (45-degree) codebook.
Each receiver r observes the superposition

    y_r(b) = sum_m H[r, m] * exp(j * phi_m(b_m)),          b in {0,1}^M

and decodes the *logical majority* maj(b) by a pre-computed binary decision region:
balanced K-means (K=2) over the 2^M constellation points, constrained to coincide
with the majority labelling.  TX phases are optimized *jointly across all receivers*
to minimize the mean BER, with the BPSK-style error model of Eq. (1):

    BER = 0.5 * erfc(0.5 * d_c / sqrt(N0))

(d_c = centroid distance; complex AWGN with per-component variance N0/2).

Everything here is pure JAX and fully vectorized: the exhaustive search for M = 3
evaluates all gauge-reduced phase assignments (7 * 56^(M-1)) against all receivers at
once; a coordinate-descent search covers M > 3.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp

N_PHASES = 8  # 45-degree discretization (Sec. IV)


# ---------------------------------------------------------------------------
# enumeration helpers
# ---------------------------------------------------------------------------

def bit_combos(m: int) -> jnp.ndarray:
    """All 2^m TX bit combinations, [B, m] uint8 (LSB = TX 0)."""
    b = jnp.arange(2 ** m, dtype=jnp.uint32)
    return ((b[:, None] >> jnp.arange(m, dtype=jnp.uint32)) & 1).astype(jnp.uint8)


def majority_labels(m: int) -> jnp.ndarray:
    """maj(b) for every bit combination, [B] uint8 (m odd -> no ties)."""
    combos = bit_combos(m)
    return (2 * jnp.sum(combos.astype(jnp.int32), axis=-1) > m).astype(jnp.uint8)


def phase_codebook() -> jnp.ndarray:
    return 2.0 * jnp.pi * jnp.arange(N_PHASES) / N_PHASES


def ordered_phase_pairs() -> jnp.ndarray:
    """All ordered pairs (i0, i1), i0 != i1, of codebook indices: [56, 2]."""
    i = jnp.arange(N_PHASES)
    a, b = jnp.meshgrid(i, i, indexing="ij")
    mask = a.reshape(-1) != b.reshape(-1)
    pairs = jnp.stack([a.reshape(-1), b.reshape(-1)], axis=-1)
    return pairs[mask]


# ---------------------------------------------------------------------------
# constellation synthesis + decision metrics
# ---------------------------------------------------------------------------

def rx_constellations(h: jnp.ndarray, phase_idx: jnp.ndarray) -> jnp.ndarray:
    """Received superposition symbols for every RX and bit combo.

    h: [N, M] complex channel; phase_idx: [M, 2] int codebook indices (bit 0/1).
    Returns y: [N, B] complex64.
    """
    m = h.shape[1]
    phases = phase_codebook()
    combos = bit_combos(m)  # [B, M]
    tx_phase = phases[phase_idx]  # [M, 2]
    sel = jnp.where(combos.astype(bool), tx_phase[None, :, 1], tx_phase[None, :, 0])  # [B, M]
    tx_sym = jnp.exp(1j * sel)  # [B, M]
    return jnp.einsum("nm,bm->nb", h, tx_sym)


def majority_centroids(
    y: jnp.ndarray, maj: jnp.ndarray, mask: jnp.ndarray | None = None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Centroids (c0, c1) of the two majority decision regions.

    y: [..., B] symbols (B = 2^M bit combos); maj: [B] labels. The single
    definition of the decision-region centers shared by `decision_metrics`,
    `simulate_ota_bundle`, and the `phy` symbol-channel decode — they must
    agree or the analytic BER describes a different decoder than the one the
    serve path runs.

    ``mask`` [B] bool restricts the fit to a sub-constellation: only masked
    combos contribute to either centroid. Used by the erasure-aware refit
    (`repro.faults.recenter_state`) where dead encoders make part of the
    constellation unreachable — the live combos are then labelled by the
    LIVE majority, so ``maj`` and ``mask`` travel together. ``mask=None``
    (or all-True) is exactly the historical all-combo fit.
    """
    m0 = (maj == 0)
    m1 = ~m0
    if mask is not None:
        mask = jnp.asarray(mask, bool)
        m0 = m0 & mask
        m1 = m1 & mask
    c0 = jnp.sum(jnp.where(m0, y, 0.0), axis=-1) / jnp.sum(m0)
    c1 = jnp.sum(jnp.where(m1, y, 0.0), axis=-1) / jnp.sum(m1)
    return c0, c1


def per_symbol_ber(
    y: jnp.ndarray, c0: jnp.ndarray, c1: jnp.ndarray, maj: jnp.ndarray, n0
) -> jnp.ndarray:
    """Per-RX BER of nearest-centroid decoding `y` against GIVEN centroids.

    y: [..., B] symbols; c0/c1: [...] centroids (broadcast over the symbol
    axis); maj: [B] labels.  Each symbol's error probability is the Gaussian
    tail beyond its signed margin to the decision boundary (the perpendicular
    bisector of c0/c1), averaged over the B equiprobable combos.

    Unlike `decision_metrics` the centroids are an argument, NOT refit from
    `y` — this is the TRUE flip rate of a receiver whose decision regions may
    be stale: the channel-truth side of a drifting link (`repro.phy.process`
    evolves `y` while the receiver keeps yesterday's c0/c1).  With
    ``c0, c1 = majority_centroids(y, maj)`` it equals the method="symbol"
    branch of `decision_metrics` exactly.  A symbol on the WRONG side of the
    boundary contributes > 0.5 — a rigidly rotated constellation decoded
    against stale centroids degrades toward (and past) chance, which is what
    makes re-characterization measurable.
    """
    axis = (c1 - c0)
    axis = axis / jnp.maximum(jnp.abs(axis), 1e-12)
    mid = 0.5 * (c0 + c1)
    t = jnp.real((y - mid[..., None]) * jnp.conj(axis[..., None]))
    t_correct = jnp.where(maj.astype(bool), t, -t)  # signed margin, own side +
    return jnp.mean(0.5 * jax.scipy.special.erfc(t_correct / jnp.sqrt(n0)), axis=-1)


def decision_metrics(
    y: jnp.ndarray, maj: jnp.ndarray, n0: float, method: str = "centroid"
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-RX BER + validity of the majority decision regions.

    y: [..., N, B] symbols; maj: [B] labels.  Returns (ber [..., N], valid [..., N]).

    * validity: the balanced majority partition must be a 2-means solution — every
      symbol strictly closer to its own centroid (paper: "we make sure that each
      cluster contains four symbols and the combination of TX phases allows the
      mapping to the majority result"). Invalid regions decode at chance: BER 0.5.
    * method "centroid": Eq. (1) on the centroid distance (paper-faithful).
    * method "symbol": refined per-symbol error — distance of each symbol to the
      decision boundary (perpendicular bisector of the centroids); tighter when the
      constellation is asymmetric. Used as a beyond-paper refinement.
    """
    m0 = (maj == 0)
    m1 = ~m0
    c0, c1 = majority_centroids(y, maj)
    d0 = jnp.abs(y - c0[..., None])
    d1 = jnp.abs(y - c1[..., None])
    own_closer = jnp.where(m0, d0 < d1, d1 < d0)
    valid = jnp.all(own_closer, axis=-1)

    if method == "centroid":
        d_c = jnp.abs(c1 - c0)
        ber = 0.5 * jax.scipy.special.erfc(0.5 * d_c / jnp.sqrt(n0))
    elif method == "symbol":
        ber = per_symbol_ber(y, c0, c1, maj, n0)
    else:
        raise ValueError(f"unknown method {method!r}")
    return jnp.where(valid, ber, 0.5), valid


# ---------------------------------------------------------------------------
# joint TX-phase optimization
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class OTAResult:
    phase_idx: jnp.ndarray   # [M, 2] chosen codebook indices
    ber_per_rx: jnp.ndarray  # [N]
    valid_per_rx: jnp.ndarray
    symbols: jnp.ndarray     # [N, B] constellation of the winner
    n0: float

    @property
    def avg_ber(self) -> jnp.ndarray:
        return jnp.mean(self.ber_per_rx)

    @property
    def max_ber(self) -> jnp.ndarray:
        return jnp.max(self.ber_per_rx)


@functools.partial(jax.jit, static_argnames=("method",))
def _score_assignments(h, phase_idx_batch, maj, n0, method):
    """phase_idx_batch: [A, M, 2] -> mean-over-RX BER [A].

    Jitted once per (shapes, method): the coordinate-descent search calls this
    from a sweeps x TX Python loop with a fixed [56, M, 2] candidate shape, so
    without the jit every iteration re-traced the whole scoring program and the
    M > 3 search paid compile time per step.
    """
    def one(pi):
        y = rx_constellations(h, pi)
        ber, _ = decision_metrics(y, maj, n0, method)
        return jnp.mean(ber)
    return jax.lax.map(one, phase_idx_batch, batch_size=256)


def optimize_phases_exhaustive(
    h: jnp.ndarray, n0: float, method: str = "centroid", chunk: int = 4096
) -> OTAResult:
    """Exhaustive gauge-reduced joint search (feasible for M <= 3).

    Gauge reduction: a global rotation of all TX phases by a codebook step rotates
    every constellation rigidly and leaves all distances (hence BERs) unchanged, so
    TX 0's bit-0 phase is pinned to index 0.
    """
    n, m = h.shape
    pairs = ordered_phase_pairs()  # [56, 2]
    maj = majority_labels(m)

    tx0 = jnp.stack([jnp.zeros(N_PHASES - 1, jnp.int32), jnp.arange(1, N_PHASES)], -1)  # [7, 2]
    spaces = [tx0] + [pairs] * (m - 1)
    sizes = [s.shape[0] for s in spaces]
    total = int(jnp.prod(jnp.array(sizes)))

    def assignment_at(flat_idx):
        idxs = []
        rem = flat_idx
        for s in reversed(sizes):
            idxs.append(rem % s)
            rem = rem // s
        idxs = list(reversed(idxs))
        return jnp.stack([spaces[k][idxs[k]] for k in range(m)], axis=0)  # [M, 2]

    # the running best stays ON DEVICE: `sc < best` / `int(flat[i])` here would
    # force a host round-trip per 4096-candidate chunk, serializing the async
    # dispatch of the whole search. One implicit sync when the winner is used.
    best_score = jnp.full((), jnp.inf, jnp.float32)
    best_flat = jnp.zeros((), jnp.int32)
    for start in range(0, total, chunk):
        flat = jnp.arange(start, min(start + chunk, total))
        batch = jax.vmap(assignment_at)(flat)
        scores = _score_assignments(h, batch, maj, n0, method)
        i = jnp.argmin(scores)
        better = scores[i] < best_score
        best_flat = jnp.where(better, flat[i].astype(jnp.int32), best_flat)
        best_score = jnp.where(better, scores[i], best_score)

    phase_idx = assignment_at(best_flat)
    y = rx_constellations(h, phase_idx)
    ber, valid = decision_metrics(y, maj, n0, method)
    return OTAResult(phase_idx=phase_idx, ber_per_rx=ber, valid_per_rx=valid, symbols=y, n0=n0)


def optimize_phases_coordinate(
    h: jnp.ndarray,
    n0: float,
    key: jax.Array,
    sweeps: int = 4,
    method: str = "centroid",
) -> OTAResult:
    """Coordinate-descent joint search for arbitrary M (used for M > 3).

    One TX's phase pair is optimized at a time (56 candidates) holding the others
    fixed; a few sweeps converge since each step can only lower the objective.
    """
    n, m = h.shape
    pairs = ordered_phase_pairs()
    maj = majority_labels(m)

    init = jax.random.randint(key, (m, 2), 0, N_PHASES)
    # ensure distinct phases per TX
    init = init.at[:, 1].set((init[:, 0] + 1 + init[:, 1] % (N_PHASES - 1)) % N_PHASES)
    phase_idx = init

    def score(pi):
        y = rx_constellations(h, pi)
        ber, _ = decision_metrics(y, maj, n0, method)
        return jnp.mean(ber)

    for _ in range(sweeps):
        for tx in range(m):
            cand = jnp.repeat(phase_idx[None], pairs.shape[0], axis=0)
            cand = cand.at[:, tx].set(pairs)
            scores = _score_assignments(h, cand, maj, n0, method)
            phase_idx = cand[jnp.argmin(scores)]

    y = rx_constellations(h, phase_idx)
    ber, valid = decision_metrics(y, maj, n0, method)
    return OTAResult(phase_idx=phase_idx, ber_per_rx=ber, valid_per_rx=valid, symbols=y, n0=n0)


# ---------------------------------------------------------------------------
# end-to-end OTA transmission (empirical cross-check of Eq. 1)
# ---------------------------------------------------------------------------

def awgn_decide(
    key: jax.Array, sym: jnp.ndarray, c0: jnp.ndarray, c1: jnp.ndarray, n0
) -> jnp.ndarray:
    """Physical receiver decode: complex AWGN + binary decision regions.

    sym: [...] complex noiseless received symbols; c0/c1 broadcastable
    majority-region centroids (`majority_centroids`). Complex noise with
    per-component variance n0/2 (Eq. 1's error model), then nearest-centroid
    decision. Returns uint8 bits. The ONE decode definition shared by the
    host-level `simulate_ota_bundle`, the batched classifier channel and the
    in-graph serve tier (re-exported as `phy.awgn_decide`) — they must agree
    or the analytic BER describes a different decoder than the one served.
    """
    kr, ki = jax.random.split(key)
    noise = jnp.sqrt(jnp.asarray(n0, jnp.float32) / 2.0) * (
        jax.random.normal(kr, sym.shape) + 1j * jax.random.normal(ki, sym.shape)
    )
    r = sym + noise
    return (jnp.abs(r - c1) < jnp.abs(r - c0)).astype(jnp.uint8)


def simulate_ota_bundle(
    key: jax.Array,
    queries: jnp.ndarray,   # [M, d] uint8 — the M hypervectors to bundle
    h: jnp.ndarray,         # [N, M] channel
    phase_idx: jnp.ndarray, # [M, 2]
    n0: float,
) -> jnp.ndarray:
    """Physically simulate the OTA majority: per dimension, all TXs transmit their
    bit simultaneously; each RX adds AWGN and decodes via its decision regions.

    Returns decoded [N, d] uint8 — each receiver's (noisy) view of maj(queries),
    ready to drive its local similarity search. This is the paper's Fig. 3b dataflow.
    """
    m, d = queries.shape
    n = h.shape[0]
    maj = majority_labels(m)
    y = rx_constellations(h, phase_idx)  # [N, B]
    c0, c1 = majority_centroids(y, maj)  # [N] each

    combo = jnp.sum(queries.astype(jnp.int32) * (2 ** jnp.arange(m))[:, None], axis=0)  # [d]
    sym = y[:, combo]  # [N, d] noiseless received symbols
    return awgn_decide(key, sym, c0[:, None], c1[:, None], n0)


def default_n0(h: jnp.ndarray, snr_db: float = 7.0) -> float:
    """Noise density yielding a given mean per-link SNR — calibration knob.

    The paper transmits at 0 dBm and lands at avg BER ~1e-2 / max ~0.1 over 64 RX
    (Fig. 8); with our parametric cavity channel the same operating point is hit at
    ~7 dB mean SNR (avg BER 0.010, max 0.04, half the RXs below 1e-5).
    """
    p_rx = float(jnp.mean(jnp.abs(h) ** 2))
    return p_rx / (10.0 ** (snr_db / 10.0))
