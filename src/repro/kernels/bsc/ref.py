"""Pure-jnp oracle for the bitplane BSC copies kernel: the XLA path it
replaces, `collectives.ota_noise_packed` in ``bitplane`` mode per core."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hypervector as hv


def bsc_bitplane_copies_ref(keys: jax.Array, words: jax.Array, ber: jax.Array,
                            precision: int = 16) -> jax.Array:
    """keys [N, n_core, 2], words [N, B, W], ber [n_core] -> [N, n_core, B, W]:
    ``words[n] ^ bernoulli_words(keys[n, i], ber[i], (B, W), precision)``."""
    def core(key, p, w):
        return jnp.bitwise_xor(w, hv.bernoulli_words(key, p, w.shape, precision))

    return jax.vmap(lambda ks, w: jax.vmap(lambda k, p: core(k, p, w))(ks, ber))(
        keys, words)
