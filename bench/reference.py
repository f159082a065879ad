"""Plain reference of the multi-tenant OTA similarity service.

Straightforward ``jax.numpy`` on one device; it imports nothing of the
program. For one request it computes what the service's guarantees say each
trial's answer is:

1. the OTA bundle: the strict bitwise majority of the M transmitted class
   hypervectors;
2. the PHY fan-out: IMC core g (global id) receives its own copy, each bit
   flipped where a ``planes``-bit uniform drawn from the request's key is
   below the core's precharacterized BER (quantized to 2^-planes). The draw
   is ``jax.random.bits(fold_in(fold_in(key, 0), g), (planes, B, d/32))``,
   plane i supplying bit i of each lane's uniform;
3. the search: core g holds classes [g*C/N, (g+1)*C/N) of the tenant's
   bank; the answer is the class at the least Hamming distance from its
   core's copy, the lowest class index among ties, and
   ``maxsim = (d - 2*dist) / (2d) + 0.5``.

Cores are taken in chunks so that the reference fits beside whatever the
process already holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

WORD = 32


def _bits(words):
    """[..., W] uint32 -> [..., W, 32] uint32 of 0/1, bit b of word w."""
    return (words[..., None] >> jnp.arange(WORD, dtype=jnp.uint32)) & 1


def _words(bits):
    return jnp.sum(bits << jnp.arange(WORD, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def majority(tx_hvs):
    """[B, M, W] -> [B, W]: bit set where more than half of the M are set."""
    m = tx_hvs.shape[1]
    counts = jnp.sum(_bits(tx_hvs), axis=1)
    return _words((2 * counts > m).astype(jnp.uint32))


def flip_threshold(ber: np.ndarray, planes: int) -> np.ndarray:
    """Per-core flip threshold on the ``planes``-bit uniform."""
    scaled = np.round(np.asarray(ber, np.float32) * np.float32(2**planes))
    return np.clip(scaled, 0, 2**planes - 1).astype(np.uint32)


def core_copy(key, bundled, threshold, planes: int):
    """One core's received copy of the bundled query [B, W]."""
    draws = jax.random.bits(key, (planes,) + bundled.shape, jnp.uint32)
    u = jnp.zeros(bundled.shape + (WORD,), jnp.uint32)
    for i in range(planes):
        u = u + (_bits(draws[i]) << i)
    return bundled ^ _words((u < threshold).astype(jnp.uint32))


@functools.partial(jax.jit, static_argnames=("n_cores", "planes", "chunk"))
def serve(bank, classes, key, thresholds, *, n_cores: int, planes: int,
          chunk: int):
    """One request: bank [C, W] uint32, classes [B, M] int32, key [2] uint32,
    thresholds [n_cores] uint32 -> (pred [B] int32, maxsim [B] float32)."""
    c, w = bank.shape
    d = w * WORD
    c_core = c // n_cores
    bundled = majority(bank[classes])                      # [B, W]
    kq = jax.random.fold_in(key, 0)
    n_chunks = n_cores // chunk
    cores = jnp.arange(n_cores, dtype=jnp.int32).reshape(n_chunks, chunk)
    rows = bank.reshape(n_chunks, chunk, c_core, w)
    thr = thresholds.reshape(n_chunks, chunk)

    def one_core(g, t, core_rows):
        rx = core_copy(jax.random.fold_in(kq, g), bundled, t, planes)
        x = rx[:, None, :] ^ core_rows[None]               # [B, c_core, W]
        return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), -1)

    def step(carry, xs):
        best, arg = carry
        g, t, r = xs
        dist = jax.vmap(one_core)(g, t, r)                 # [chunk, B, c_core]
        dist = jnp.moveaxis(dist, 1, 0).reshape(dist.shape[1], -1)
        low = jnp.min(dist, -1)
        at = jnp.argmin(dist, -1).astype(jnp.int32) + g[0] * c_core
        better = low < best
        return (jnp.where(better, low, best), jnp.where(better, at, arg)), None

    b = classes.shape[0]
    init = (jnp.full((b,), d + 1, jnp.int32), jnp.zeros((b,), jnp.int32))
    (best, arg), _ = jax.lax.scan(step, init, (cores, thr, rows))
    maxsim = (d - 2 * best).astype(jnp.float32) / jnp.float32(2 * d) + 0.5
    return arg, maxsim
