"""Plain reference of the link's offline precharacterization: each RX core's
bit error rate, from the package geometry and Eq. 1, in float64 numpy. It
imports nothing of the program.

1. The channel H[r, t] of the lidded package (Fig. 5: a 30 mm x 29.7 mm
   cavity, TX antennas 3.75 mm apart on the left edge, the RX cores on a grid
   right of a 7.5 mm keep-out, nudged 0.2 mm off the nodal lines of the
   (12, 0) mode): the sum over the cavity modes (p, q) of
   cos(p pi x/L1) cos(q pi y/L2) at both ends over k_pq^2 - k0^2 (1 + j/Q),
   at 59.96 GHz and Q = 400, scaled by 1e3 / (k0^2 L1 L2).
2. The noise density: the mean of |H|^2 over the link's SNR.
3. The joint TX phase search over the 8-phase codebook: TX 0's bit-0 phase
   pinned to 0, every other pair of distinct phases, minimizing the mean
   over the cores of Eq. 1, BER = erfc(|c1 - c0| / (2 sqrt(N0))) / 2, with
   c0 and c1 the centroids of the combinations whose majority is 0 and 1; a
   core whose combinations are not each nearer their own centroid reads 0.5.
   Ties go to the first assignment in the search's order.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import erfc

L1, L2 = 30.0, 29.7          # package extent (mm)
TX_SPACING, TX_EDGE = 3.75, 1.5
RX_KEEPOUT = 7.5
FREQ_HZ = 59.96e9
CAVITY_Q = 400.0
C_MM_PER_S = 2.998e11
N_PHASES = 8


def tx_positions(m: int) -> np.ndarray:
    y0 = L2 / 2 - (m - 1) * TX_SPACING / 2
    return np.stack([np.full(m, TX_EDGE), y0 + TX_SPACING * np.arange(m)], -1)


def rx_positions(n: int) -> np.ndarray:
    cols = int(math.ceil(math.sqrt(n)))
    rows = int(math.ceil(n / cols))
    gx, gy = np.meshgrid(np.linspace(RX_KEEPOUT + 1.0, L1 - 1.0, cols),
                         np.linspace(1.0, L2 - 1.0, rows), indexing="ij")
    period = L1 / 12.0
    d = np.mod(gx, period) - period / 2.0
    gx = gx + np.where(np.abs(d) < 0.2, np.sign(d + 1e-9) * (0.2 - np.abs(d)), 0.0)
    return np.stack([gx.reshape(-1), gy.reshape(-1)], -1)[:n]


def channel(m: int, n: int) -> np.ndarray:
    """[n, m] complex gains."""
    k0 = 2.0 * np.pi * FREQ_HZ / C_MM_PER_S
    kx = np.arange(int(2.0 * k0 * L1 / np.pi) + 2) * np.pi / L1
    ky = np.arange(int(2.0 * k0 * L2 / np.pi) + 2) * np.pi / L2
    denom = kx[:, None] ** 2 + ky[None, :] ** 2 - k0 ** 2 * (1.0 + 1j / CAVITY_Q)

    def phi(pos):
        return (np.cos(pos[:, :1] * kx)[:, :, None]
                * np.cos(pos[:, 1:] * ky)[:, None, :])

    h = np.einsum("npq,mpq->nm", phi(rx_positions(n)) / denom, phi(tx_positions(m)))
    return h / (k0 ** 2 * L1 * L2) * 1e3


def eq1_ber(u0, u1, u2, n0):
    """Eq. 1 of each core under one phase assignment, from u_t (below)."""
    big = u0 + u1 + u2
    half = 0.5 * np.abs(big) ** 2
    valid = ((np.real(u0 * np.conj(big)) < half)
             & (np.real(u1 * np.conj(big)) < half)
             & (np.real(u2 * np.conj(big)) < half))
    return np.where(valid, 0.5 * erfc(0.5 * np.abs(big) / np.sqrt(n0)), 0.5)


def per_core_ber(m_tx: int, n_rx_cores: int, snr_db: float) -> np.ndarray:
    """[n_rx_cores] float64: each core's BER at the jointly best phases.

    With the pair (a, b) of TX t and d_t = (e^{j b pi/4} - e^{j a pi/4}) / 2,
    u_t = H[r, t] d_t and U = sum_t u_t, the received symbol of a combination
    lies at sum_t (+-u_t) about the midpoint of the centroids, and
    c1 - c0 = U. So |c1 - c0| = |U|, and every combination lies nearer its
    own centroid exactly when Re(u_t conj(U)) < |U|^2 / 2 for each t (M = 3).
    """
    if m_tx != 3:
        raise ValueError("the closed form of the decision regions is for M = 3")
    h = channel(m_tx, n_rx_cores)
    n0 = np.mean(np.abs(h) ** 2) / 10.0 ** (snr_db / 10.0)
    rot = np.exp(2j * np.pi * np.arange(N_PHASES) / N_PHASES)
    a, b = np.meshgrid(np.arange(N_PHASES), np.arange(N_PHASES), indexing="ij")
    pairs = np.stack([a.reshape(-1), b.reshape(-1)], -1)
    pairs = pairs[pairs[:, 0] != pairs[:, 1]]           # [56, 2]
    tx0 = np.stack([np.zeros(N_PHASES - 1, int), np.arange(1, N_PHASES)], -1)
    d_pair = (rot[pairs[:, 1]] - rot[pairs[:, 0]]) / 2  # [56]
    d_tx0 = (rot[tx0[:, 1]] - rot[tx0[:, 0]]) / 2       # [7]
    # u_1 + u_2 for every (TX 1 pair, TX 2 pair), in the search's order
    u1 = h[:, 1][None, None, :] * d_pair[:, None, None]  # [56, 1, N]
    u2 = h[:, 2][None, None, :] * d_pair[None, :, None]  # [1, 56, N]
    u1, u2 = np.broadcast_arrays(u1, u2)
    u1, u2 = u1.reshape(-1, n_rx_cores), u2.reshape(-1, n_rx_cores)

    best, best_at = np.inf, None
    for i0 in range(len(tx0)):
        u0 = np.broadcast_to(h[:, 0] * d_tx0[i0], u1.shape)
        score = eq1_ber(u0, u1, u2, n0).mean(-1)
        j = int(np.argmin(score))
        if score[j] < best:
            best, best_at = score[j], (i0, j)
    i0, j = best_at
    return eq1_ber(h[:, 0] * d_tx0[i0], u1[j], u2[j], n0)
