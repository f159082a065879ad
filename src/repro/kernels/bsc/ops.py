"""Public op: every RX core's bitplane-noisy copy, lane-dense padding and
backend dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hypervector as hv
from repro.kernels import common
from repro.kernels.bsc.kernel import LANES, bsc_bitplane_pallas
from repro.kernels.bsc.ref import bsc_bitplane_copies_ref


def bsc_bitplane_copies(
    keys: jax.Array,
    words: jax.Array,
    ber: jax.Array,
    *,
    precision: int = 16,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Each core's BSC copy of each slot's packed bundle, bitplane masks.

    keys [N, n_core, 2] uint32 (slot n, core i's raw threefry key), words
    [N, B, W] uint32, ber [n_core] -> [N, n_core, B, W] uint32, bit for bit
    ``words[n] ^ hv.bernoulli_words(keys[n, i], ber[i], (B, W), precision)``.
    The kernel reads each slot's B*W words as rows of 128 lanes (zero-padded
    and sliced away), in the blocks of the `common.bitplane_blocks` policy.
    """
    if not use_kernel:
        return bsc_bitplane_copies_ref(keys, words, ber, precision)
    if interpret is None:
        interpret = common.default_interpret()
    n, n_core = keys.shape[0], keys.shape[1]
    b, w = words.shape[1], words.shape[2]
    f = b * w
    sb, cb, rb = common.bitplane_blocks(n, n_core, b, w)
    rows = common.cdiv(common.cdiv(f, LANES), rb) * rb
    flat = common.pad_dim(words.reshape(n, f), 1, rows * LANES)
    t = jnp.broadcast_to(hv.bernoulli_threshold(ber, precision), (n, n_core))
    kt = jnp.concatenate([keys.astype(jnp.uint32), t[..., None]], axis=-1)
    out = bsc_bitplane_pallas(
        jnp.broadcast_to(kt[..., None], (n, n_core, 3, LANES)),
        flat.reshape(n, rows, LANES),
        precision=precision, plane_words=f, sb=sb, cb=cb, rb=rb,
        interpret=interpret,
    )
    return out.reshape(n, n_core, rows * LANES)[:, :, :f].reshape(n, n_core, b, w)
