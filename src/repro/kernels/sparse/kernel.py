"""Pallas kernels for sparse-query vs packed-prototype similarity search.

The hot operation of the ultra-sparse representation: a query is k_max sorted
bit indices (sentinel-padded), a prototype row stays bit-packed uint32 words
exactly as the IMC macro stores it. Overlap |q AND p| is a GATHER of the word
holding each query index plus a bit test — O(k_max) loads per (query, class)
pair instead of O(d/32) — and the Hamming distance follows from
``|q XOR p| = |q| + |p| - 2 |q AND p|`` with |p| a popcount of the prototype
tile. The dense [bq, d] query is never materialized, in VMEM or anywhere.

Two kernels, mirroring kernels/hamming/kernel.py:

* `sparse_search_pallas` — full distance tile [bq, bc] per grid step (the
  classifier's top-m decision needs every class's distance);
* `sparse_topk_banked_pallas` — fused per-bank top-1 with the same
  revisited-output-tile running (min, argmin) carry and FIRST-minimum tie
  convention as `hamming_topk_banked_pallas`, so the sparse serve path reuses
  the packed serve's downstream unchanged.

TPU mapping: the query tile rides in SMEM, one scalar index per loop step,
and the prototype tile is transposed once per grid step into a word-major
[W, bc] VMEM scratch, so the word a query index names is one dynamic
sublane row holding that word of all bc classes. The gather is then a row
load per (query, index) — the TPU lowering refuses a vector `jnp.take`
gather on the class tile. CPU runs use interpret mode
(`common.default_interpret()`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hamming.kernel import merge_top1, top1_banked_call

# sentinel-padded query slots (must match repro.core.sparse.SENTINEL)
_SENTINEL = 2**31 - 1


def _overlap_rows(idx_at, bq: int, k: int, p, pt_ref, store_row):
    """Distances of bq index-list queries to the bc classes of tile p.

    ``idx_at(i, s)`` reads slot s of query i (a scalar from SMEM); p is the
    [bc, W] uint32 prototype tile; ``store_row(i, row)`` receives query i's
    [1, bc] int32 distances ``|q| + |p| - 2 |q AND p|``.
    """
    pt_ref[...] = p.T                                         # [W, bc]
    pop = jnp.sum(jax.lax.population_count(pt_ref[...]).astype(jnp.int32),
                  axis=0, keepdims=True)                      # [1, bc]
    bc = pop.shape[1]

    def query(i, carry):
        def slot(s, acc):
            ov, cnt = acc
            idx = idx_at(i, s)
            valid = idx != _SENTINEL
            word = pt_ref[pl.ds(jnp.where(valid, idx >> 5, 0), 1), :]
            bit = jnp.where(valid, idx & 31, 0).astype(jnp.uint32)
            hit = ((word >> bit) & jnp.uint32(1)).astype(jnp.int32)
            v = valid.astype(jnp.int32)
            return ov + hit * v, cnt + v

        ov, cnt = jax.lax.fori_loop(
            0, k, slot, (jnp.zeros((1, bc), jnp.int32), jnp.int32(0)))
        store_row(i, cnt + pop - 2 * ov)
        return carry

    jax.lax.fori_loop(0, bq, query, 0)


def _search_kernel(q_ref, p_ref, out_ref, pt_ref):
    bq, k = q_ref.shape

    def store_row(i, row):
        out_ref[pl.ds(i, 1), :] = row

    _overlap_rows(lambda i, s: q_ref[i, s], bq, k, p_ref[...], pt_ref,
                  store_row)


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def sparse_search_pallas(
    q: jax.Array, protos: jax.Array, *, bq: int, bc: int, interpret: bool
) -> jax.Array:
    """Full sparse-vs-packed distances: q [B, k], protos [C, W] -> [B, C] int32.

    B must be a multiple of bq and C of bc (callers pad; padded query rows are
    all-sentinel, padded class rows all-zero words — both sliced away after).
    """
    b, k = q.shape
    c, w = protos.shape
    assert b % bq == 0 and c % bc == 0, (q.shape, protos.shape, bq, bc)
    return pl.pallas_call(
        _search_kernel,
        grid=(b // bq, c // bc),
        in_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bc, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.int32),
        scratch_shapes=[pltpu.VMEM((w, bc), jnp.uint32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(q, protos)


def _topk_banked_kernel(c_real, bc, q_ref, p_ref, val_ref, idx_ref,
                        pt_ref, dist_ref):
    """Fused per-bank top-1 with a revisited output tile over the class grid.

    Same carry as the hamming top-1 (`merge_top1`): grid step j streams
    class block j through the running (min, argmin); strict `<` in the merge
    + FIRST-minimum inside the block preserve the global first-minimum tie
    convention of the oracle.
    """
    _, bq, k = q_ref.shape

    def store_row(i, row):
        dist_ref[pl.ds(i, 1), :] = row

    _overlap_rows(lambda i, s: q_ref[0, i, s], bq, k, p_ref[0], pt_ref,
                  store_row)
    merge_top1(c_real, bc, pl.program_id(2), dist_ref[...], val_ref,
                idx_ref)


@functools.partial(
    jax.jit, static_argnames=("c_real", "bq", "bc", "interpret")
)
def sparse_topk_banked_pallas(
    q: jax.Array, protos: jax.Array, *, c_real: int, bq: int, bc: int,
    interpret: bool,
) -> tuple[jax.Array, jax.Array]:
    """Fused per-bank sparse top-1: (min_dist, argmin), each [G, B] int32.

    q: [G, B, k] int32 sorted sentinel-padded; protos: [G, C, W] uint32.
    B must be a multiple of bq and C of bc; class columns >= c_real are
    poisoned so padding never wins.
    """
    w = protos.shape[-1]
    kernel = functools.partial(_topk_banked_kernel, c_real, bc)
    return top1_banked_call(
        kernel, q, protos, bq=bq, bc=bc, interpret=interpret,
        q_memory_space=pltpu.SMEM,
        scratch_shapes=[pltpu.VMEM((w, bc), jnp.uint32),
                        pltpu.VMEM((bq, bc), jnp.int32)],
    )
