"""Pallas TPU kernel: batched packed Hamming distance (XOR + popcount).

The associative-memory similarity search of the paper (Fig. 2) over bit-packed
hypervectors. One output tile [bq, bc] is produced per grid step from a query tile
[bq, W] and a prototype tile [bc, W] resident in VMEM; the packed dimension W is
small (d/32 words; 16 words for d=512, 313 for d=10,000) so it is not tiled.

TPU mapping notes:
* uint32 bitwise XOR + population_count lower to the VPU; the [bq, bc, W] intermediate
  stays in VREGs/VMEM (bq=8, bc=128, W<=512 -> <=2 MiB).
* last-dim block sizes are multiples of 128 lanes; bq rides the 8-sublane dimension.
* the fused top-1 carries its running (min, argmin) in [G, B, 1] outputs with
  (1, bq, 1) blocks: the TPU lowering refuses an output block whose last two
  dims are neither (8, 128)-aligned nor the array's own dims, which a (1, bq)
  block on a [G, B] array is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Sorted-key buffer sentinel: strictly greater than every real key (real keys
# are bounded by (d+1)*C < 2**31, checked by the caller), so padded classes and
# already-extracted entries can never win a rank. Kept as a Python int —
# a module-level jnp scalar would be captured as a constant by pallas_call.
_KEY_SENTINEL = 2**31 - 1
# padded class columns get this distance in the top-1 carry so they never
# win; a Python int for the same reason
_POISON = 2**30


def _hamming_kernel(q_ref, p_ref, o_ref):
    q = q_ref[...]  # [bq, W] uint32
    p = p_ref[...]  # [bc, W] uint32
    x = jnp.bitwise_xor(q[:, None, :], p[None, :, :])        # [bq, bc, W]
    pc = jax.lax.population_count(x).astype(jnp.int32)
    o_ref[...] = jnp.sum(pc, axis=-1)


def _hamming_banked_kernel(q_ref, p_ref, o_ref):
    q = q_ref[0]  # [bq, W] uint32 — this bank's query tile
    p = p_ref[0]  # [bc, W] uint32 — this bank's prototype tile
    x = jnp.bitwise_xor(q[:, None, :], p[None, :, :])        # [bq, bc, W]
    pc = jax.lax.population_count(x).astype(jnp.int32)
    o_ref[0] = jnp.sum(pc, axis=-1)


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def hamming_banked_pallas(
    q: jax.Array,
    protos: jax.Array,
    *,
    bq: int = 8,
    bc: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Per-bank packed Hamming search in ONE kernel launch.

    q [G, B, W] uint32, protos [G, C, W] uint32 -> [G, B, C] int32: bank g's
    queries are searched only against bank g's prototypes. This is the scale-out
    per-IMC-core search ([n_core, B, W] noisy queries x [n_core, C_core, W]
    memory shards) as a single grid (G, B/bq, C/bc) launch — one pipeline over
    all cores instead of a vmap of G tiny calls. B % bq == C % bc == 0.
    """
    g, b, w = q.shape
    g2, c, w2 = protos.shape
    assert g == g2 and w == w2, (q.shape, protos.shape)
    assert b % bq == 0 and c % bc == 0, (b, bq, c, bc)
    grid = (g, b // bq, c // bc)
    return pl.pallas_call(
        _hamming_banked_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, w), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bc, w), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, bc), lambda g, i, j: (g, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, b, c), jnp.int32),
        interpret=interpret,
    )(q, protos)


def _topk_banked_kernel(c_real: int, bc: int, q_ref, p_ref, val_ref, idx_ref):
    """Fused top-1 step: revisits the (g, i) output tile across the j grid axis.

    The running (min_dist, argmin) pair lives in the output VMEM tiles — the
    [bq, bc] distance tile is reduced in-register and never reaches HBM (the
    IMC macro's in-memory argmax, Karunaratne et al. 2020). Ties break toward
    the lowest class index: argmin is first-match inside a tile and the strict
    `<` merge keeps the earlier tile, matching `jnp.argmax` on similarities
    (= first minimum of distances) exactly.
    """
    j = pl.program_id(2)
    q = q_ref[0]  # [bq, W] uint32 — this bank's query tile
    p = p_ref[0]  # [bc, W] uint32 — this bank's prototype tile
    x = jnp.bitwise_xor(q[:, None, :], p[None, :, :])        # [bq, bc, W]
    dist = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
    merge_top1(c_real, bc, j, dist, val_ref, idx_ref)


def merge_top1(c_real: int, bc: int, j, dist, val_ref, idx_ref):
    """Fold one [bq, bc] distance tile (class block j) into the running
    (min, first argmin) carry held in the [1, bq, 1] output tiles. Classes at
    or beyond ``c_real`` are padding and are poisoned so they never win."""
    col = j * bc + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    dist = jnp.where(col < c_real, dist, jnp.int32(_POISON))
    loc_v = jnp.min(dist, axis=-1, keepdims=True)            # [bq, 1]
    # first column attaining the minimum (argmin's tie order)
    loc_i = jnp.min(jnp.where(dist == loc_v, col, jnp.int32(_KEY_SENTINEL)),
                    axis=-1, keepdims=True)

    @pl.when(j == 0)
    def _init():
        val_ref[0] = loc_v
        idx_ref[0] = loc_i

    @pl.when(j > 0)
    def _update():
        better = loc_v < val_ref[0]
        idx_ref[0] = jnp.where(better, loc_i, idx_ref[0])
        val_ref[0] = jnp.where(better, loc_v, val_ref[0])


def top1_banked_call(kernel, q, protos, *, bq: int, bc: int, interpret: bool,
                     scratch_shapes=(), q_memory_space=None):
    """Launch a fused per-bank top-1 kernel over grid (G, B/bq, C/bc).

    Shared by the packed (hamming) and index-list (sparse) top-1 kernels:
    both stream class blocks through `merge_top1`'s carry. Returns
    (min_dist, argmin), each [G, B] int32.
    """
    g, b = q.shape[0], q.shape[1]
    c, w = protos.shape[1], protos.shape[2]
    assert b % bq == 0 and c % bc == 0, (q.shape, protos.shape, bq, bc)
    out = jax.ShapeDtypeStruct((g, b, 1), jnp.int32)
    out_spec = pl.BlockSpec((1, bq, 1), lambda g, i, j: (g, i, 0))
    val, idx = pl.pallas_call(
        kernel,
        grid=(g, b // bq, c // bc),
        in_specs=[
            pl.BlockSpec((1, bq, q.shape[2]), lambda g, i, j: (g, i, 0),
                         memory_space=q_memory_space),
            pl.BlockSpec((1, bc, w), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[out_spec, out_spec],
        out_shape=[out, out],
        scratch_shapes=list(scratch_shapes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, protos)
    return val[..., 0], idx[..., 0]


@functools.partial(jax.jit, static_argnames=("c_real", "bq", "bc", "interpret"))
def hamming_topk_banked_pallas(
    q: jax.Array,
    protos: jax.Array,
    *,
    c_real: int,
    bq: int = 8,
    bc: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-bank fused top-1 Hamming search in ONE kernel launch.

    q [G, B, W] uint32, protos [G, C, W] uint32 -> (min_dist, argmin), each
    [G, B] int32, over bank g's own prototypes. Same grid (G, B/bq, C/bc) as
    `hamming_banked_pallas`, but the class axis is reduced inside the kernel:
    the output tile (indexed by (g, i) only) stays resident in VMEM across the
    j steps and carries the running (min, argmin), so the [G, B, C] distance
    tensor never exists in HBM. `c_real` (<= C) masks zero-padded prototype
    rows. B % bq == C % bc == 0.
    """
    g, b, w = q.shape
    g2, c, w2 = protos.shape
    assert g == g2 and w == w2, (q.shape, protos.shape)
    assert 0 < c_real <= c, (c_real, c)
    kernel = functools.partial(_topk_banked_kernel, c_real, bc)
    return top1_banked_call(kernel, q, protos, bq=bq, bc=bc,
                            interpret=interpret)


def _smallest_k(keys: jax.Array, k: int) -> jax.Array:
    """Ascending k smallest entries of keys [..., n] by repeated min-extraction.

    Real keys are globally unique (dist*C + col with distinct cols), so the
    extract-then-poison step retires exactly one real entry per rank; only
    sentinels ever collide, and poisoning a sentinel with a sentinel is a
    no-op. Unrolled k times — k is a small static (the coarse-screen keep).
    """
    sentinel = jnp.int32(_KEY_SENTINEL)
    outs = []
    for _ in range(k):
        m = jnp.min(keys, axis=-1, keepdims=True)
        outs.append(m)
        keys = jnp.where(keys == m, sentinel, keys)
    return jnp.concatenate(outs, axis=-1)


def _topk_k_banked_kernel(c_real: int, c_pad: int, bc: int, k: int,
                          q_ref, p_ref, key_ref):
    """Fused top-k step: the top-1 kernel's scalar carry generalized to a small
    SORTED key buffer per (g, i) output tile.

    The running state is [bq, k] int32 keys ``dist*c_pad + col`` (ascending);
    minimizing keys IS lexicographic (dist, col) order, so every rank keeps the
    first-minimum tie convention of the top-1 kernel. Each j step merges the
    buffer with the tile's bc candidate keys by k repeated min-extractions —
    the [bq, bc] distance tile is consumed in-register and never reaches HBM.
    Padded classes (col >= c_real) carry the sentinel key.
    """
    j = pl.program_id(2)
    q = q_ref[0]  # [bq, W] uint32 — this bank's query tile
    p = p_ref[0]  # [bc, W] uint32 — this bank's prototype tile
    x = jnp.bitwise_xor(q[:, None, :], p[None, :, :])        # [bq, bc, W]
    dist = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)
    col = j * bc + jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    keys = jnp.where(col < c_real, dist * c_pad + col, jnp.int32(_KEY_SENTINEL))

    @pl.when(j == 0)
    def _init():
        key_ref[0] = _smallest_k(keys, k)

    @pl.when(j > 0)
    def _update():
        cand = jnp.concatenate([key_ref[0], keys], axis=-1)  # [bq, k + bc]
        key_ref[0] = _smallest_k(cand, k)


@functools.partial(
    jax.jit, static_argnames=("c_real", "k", "bq", "bc", "interpret")
)
def hamming_topk_k_banked_pallas(
    q: jax.Array,
    protos: jax.Array,
    *,
    c_real: int,
    k: int,
    bq: int = 8,
    bc: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-bank fused top-k Hamming search in ONE kernel launch.

    q [G, B, W] uint32, protos [G, C, W] uint32 -> (dists, idxs), each
    [G, B, k] int32 rank-sorted ascending by (distance, class index), over bank
    g's own prototypes. Same revisited-output-tile scheme as the fused top-1
    (`hamming_topk_banked_pallas`), with the carry widened to a sorted key
    buffer — the [G, B, C] distance tensor never exists in HBM. Requires the
    int32 key encoding to fit: (d+1)*C < 2**31. B % bq == C % bc == 0.
    """
    g, b, w = q.shape
    g2, c, w2 = protos.shape
    assert g == g2 and w == w2, (q.shape, protos.shape)
    assert b % bq == 0 and c % bc == 0, (b, bq, c, bc)
    assert 0 < c_real <= c, (c_real, c)
    assert 1 <= k <= c_real, (k, c_real)
    assert (w * 32 + 1) * c < 2**31, "key encoding would overflow int32"
    grid = (g, b // bq, c // bc)
    kernel = functools.partial(_topk_k_banked_kernel, c_real, c, bc, k)
    keys = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, w), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bc, w), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, k), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((g, b, k), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, protos)
    return keys // c, keys % c


@functools.partial(jax.jit, static_argnames=("bq", "bc", "interpret"))
def hamming_pallas(
    q: jax.Array,
    protos: jax.Array,
    *,
    bq: int = 8,
    bc: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """q [B, W] uint32, protos [C, W] uint32 -> [B, C] int32. B % bq == C % bc == 0."""
    b, w = q.shape
    c, w2 = protos.shape
    assert w == w2, (w, w2)
    assert b % bq == 0 and c % bc == 0, (b, bq, c, bc)
    grid = (b // bq, c // bc)
    return pl.pallas_call(
        _hamming_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bq, w), lambda i, j: (i, 0)),
            pl.BlockSpec((bc, w), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bq, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.int32),
        interpret=interpret,
    )(q, protos)
