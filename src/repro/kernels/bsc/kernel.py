"""Pallas TPU kernel: each RX core's bitplane-noisy copy of the OTA bundle.

The packed serve's BSC in ``bitplane`` mode (`hv.bernoulli_words`): core i of
slot n receives ``words[n] ^ mask``, where the mask compares a 16-bit uniform
per bit lane against the core's threshold ``round(ber[i] * 2^16)`` with a
bit-sliced comparator, and plane p of the uniform is ``jax.random.bits`` of
the core's key. The XLA path draws every plane to HBM (16 words per mask
word); here the planes live only in vregs and HBM sees the copy alone.

The stream is reproduced bit for bit. With ``jax_threefry_partitionable``
(on by default), ``jax.random.bits(key, (P, B, W))`` gives the word at
``(p, b, w)`` as ``x0 ^ x1`` of ``threefry2x32(key, (0, p*B*W + b*W + w))``;
the kernel computes that hash from the counter, so the copies equal the XLA
path's and the benchmark's plain reference.

TPU mapping: a core's [B, W] plane is read lane-dense as [R, 128] words
(B*W zero-padded to R*128), so narrow word axes (16 words at d=512) waste no
lanes. A block holds ``(sb, cb)`` (slot, core) pairs of ``rb`` rows; one
``fori_loop`` walks the block's pairs and its 8-row chunks, so the traced
body is one chunk's hash and comparator whatever the block holds (the
16-plane comparator is the only unrolled loop). Each pair's two key words
and threshold come in a [3, 128] row block, broadcast along sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows of one traced chunk: one uint32 vreg
CHUNK = 8

_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))
_PARITY = 0x1BD11BDA


def _rotl(x: jax.Array, r: int) -> jax.Array:
    return lax.bitwise_or(lax.shift_left(x, np.uint32(r)),
                          lax.shift_right_logical(x, np.uint32(32 - r)))


def key_schedule(k0: jax.Array, k1: jax.Array):
    """threefry2x32's key words and its five (x0, x1) key injections."""
    ks = (k0, k1, lax.bitwise_xor(lax.bitwise_xor(k0, k1), np.uint32(_PARITY)))
    inject = tuple((ks[(g + 1) % 3], lax.add(ks[(g + 2) % 3], np.uint32(g + 1)))
                   for g in range(5))
    return ks, inject


def threefry_bits(schedule, ctr: jax.Array) -> jax.Array:
    """``x0 ^ x1`` of threefry2x32 at counter (0, ctr) under the key of
    `key_schedule`: the 32-bit word `jax.random.bits` draws there (JAX's
    five 4-round groups, each followed by its key injection)."""
    ks, inject = schedule
    x0, x1 = ks[0], lax.add(ctr, ks[1])
    for g, (a, b) in enumerate(inject):
        for r in _ROT[g % 2]:
            x0 = lax.add(x0, x1)
            x1 = lax.bitwise_xor(_rotl(x1, r), x0)
        x0, x1 = lax.add(x0, a), lax.add(x1, b)
    return lax.bitwise_xor(x0, x1)


def _bitplane_kernel(precision: int, plane_words: int, kt_ref, w_ref, o_ref):
    """One block: ``o[s, c] = w[s] ^ mask(kt[s, c])`` for every (slot, core)
    pair of the block, in ``CHUNK``-row chunks.

    kt_ref (sb, cb, 3, 128): key words and threshold of each pair, the same
    in every lane; w_ref (sb, rb, 128): the bundles; o_ref (sb, cb, rb, 128).
    ``plane_words`` is B*W, the counter stride between planes. The kernel is
    written in `lax` ops: each `jnp` operator is a jitted call of its own,
    about a millisecond apiece to trace.
    """
    sb, cb, rb, _ = o_ref.shape
    chunks = rb // CHUNK
    shape = (CHUNK, LANES)
    row0 = pl.program_id(2) * rb
    flat = (lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
            + lax.broadcasted_iota(jnp.int32, shape, 1)).astype(jnp.uint32)

    def chunk(j, carry):
        s = j // (cb * chunks)
        c = (j // chunks) % cb
        r = pl.multiple_of((j % chunks) * CHUNK, CHUNK)
        k0, k1, t = (lax.broadcast_in_dim(kt_ref[s, c, pl.ds(i, 1), :], shape,
                                          (0, 1))
                     for i in range(3))
        schedule = key_schedule(k0, k1)
        ctr = lax.add(flat, ((row0 + r) * LANES).astype(jnp.uint32))
        lt = jnp.zeros(shape, jnp.uint32)
        eq = jnp.full(shape, 0xFFFFFFFF, jnp.uint32)
        # most significant plane first; the planes' hashes are independent,
        # so the unrolled comparator keeps the VPU's slots busy
        for p in reversed(range(precision)):
            bits = threefry_bits(schedule,
                                 lax.add(ctr, np.uint32(p * plane_words)))
            tb = lax.neg(lax.bitwise_and(
                lax.shift_right_logical(t, np.uint32(p)), np.uint32(1)))
            lt = lax.bitwise_or(lt, lax.bitwise_and(
                lax.bitwise_and(eq, lax.bitwise_not(bits)), tb))
            eq = lax.bitwise_and(eq, lax.bitwise_not(lax.bitwise_xor(bits, tb)))
        o_ref[s, c, pl.ds(r, CHUNK), :] = lax.bitwise_xor(
            w_ref[s, pl.ds(r, CHUNK), :], lt)
        return carry

    lax.fori_loop(0, sb * cb * chunks, chunk, 0)


@functools.partial(
    jax.jit,
    static_argnames=("precision", "plane_words", "sb", "cb", "rb", "interpret"),
)
def bsc_bitplane_pallas(
    kt: jax.Array,
    words: jax.Array,
    *,
    precision: int,
    plane_words: int,
    sb: int,
    cb: int,
    rb: int,
    interpret: bool = False,
) -> jax.Array:
    """Every core's noisy copy of every slot's bundle in ONE kernel launch.

    kt [N, n_core, 3, 128] uint32 (rows: key word 0, key word 1, threshold,
    each repeated over the lanes); words [N, R, 128] uint32, a slot's B*W
    words zero-padded to R*128 -> [N, n_core, R, 128] uint32. Grid
    (N/sb, n_core/cb, R/rb), rounded up: an edge block's pairs beyond N or
    n_core compute on padding and are not written. R % rb == rb % 8 == 0.
    """
    n, n_core = kt.shape[0], kt.shape[1]
    rows = words.shape[1]
    assert words.shape == (n, rows, LANES) and kt.shape[2:] == (3, LANES), (
        kt.shape, words.shape)
    assert rows % rb == 0 and rb % CHUNK == 0, (rows, rb)
    assert precision * plane_words <= 2**32, (precision, plane_words)
    grid = (pl.cdiv(n, sb), pl.cdiv(n_core, cb), rows // rb)
    return pl.pallas_call(
        functools.partial(_bitplane_kernel, precision, plane_words),
        grid=grid,
        in_specs=[
            pl.BlockSpec((sb, cb, 3, LANES), lambda s, c, r: (s, c, 0, 0)),
            pl.BlockSpec((sb, rb, LANES), lambda s, c, r: (s, r, 0)),
        ],
        out_specs=pl.BlockSpec((sb, cb, rb, LANES), lambda s, c, r: (s, c, r, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n_core, rows, LANES), jnp.uint32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")
        ),
        interpret=interpret,
    )(kt, words)
