"""Public op: packed Hamming similarity search with padding + backend dispatch."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import common
from repro.kernels.hamming.kernel import (
    _KEY_SENTINEL,
    hamming_banked_pallas,
    hamming_pallas,
    hamming_topk_banked_pallas,
    hamming_topk_k_banked_pallas,
)
from repro.kernels.hamming.ref import hamming_search_banked_ref, hamming_search_ref


def _blocked(ref_fn, protos, c_axis: int, bc: int, *args):
    """Evaluate a hamming ref in prototype chunks of `bc`.

    The plain refs broadcast a [..., C, W] XOR intermediate; past ~8 MiB that
    falls out of cache and the jnp fallback goes ~6x slower than the same math
    chunked (numerics are identical — integer ops). Used by the use_kernel=False
    dispatch; the refs themselves stay the canonical one-liners.
    """
    c = protos.shape[c_axis]
    if c <= bc:
        return ref_fn(*args, protos)
    chunks = [
        ref_fn(*args, jax.lax.slice_in_dim(protos, i, min(i + bc, c), axis=c_axis))
        for i in range(0, c, bc)
    ]
    return jnp.concatenate(chunks, axis=-1)


def hamming_search(
    q: jax.Array,
    protos: jax.Array,
    *,
    bq: int | None = None,
    bc: int | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Hamming distances between packed queries [.., W] and prototypes [C, W].

    Accepts arbitrary leading query dims; pads B to bq and C to bc (padding words are
    zero on both sides, so padded prototypes report distance 0 against padded queries
    only — padded rows/cols are sliced away before returning). Block sizes
    default to the `common.hamming_blocks` policy.
    """
    if interpret is None:
        interpret = common.default_interpret()
    lead = q.shape[:-1]
    w = q.shape[-1]
    qf = q.reshape((-1, w))
    b, c = qf.shape[0], protos.shape[0]
    bq, bc = common.hamming_blocks(b, c, bq, bc)
    if not use_kernel:
        return _blocked(hamming_search_ref, protos, 0, bc, qf).reshape(lead + (c,))
    qp = common.pad_dim(qf, 0, bq)
    pp = common.pad_dim(protos, 0, bc)
    out = hamming_pallas(qp, pp, bq=bq, bc=bc, interpret=interpret)
    return out[:b, :c].reshape(lead + (c,))


def hamming_search_banked(
    q: jax.Array,
    protos: jax.Array,
    *,
    bq: int | None = None,
    bc: int | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Per-bank Hamming distances: q [G, B, W], protos [G, C, W] -> [G, B, C].

    Bank g searches only bank g's prototypes — the scale-out per-core associative
    search as ONE grid (G, B/bq, C/bc) kernel launch (instead of a vmap of G tiny
    calls). B and C are zero-padded to the block sizes and sliced away; zero
    padding is safe because padded rows/banks are dropped before returning.
    Block sizes default to the `common.hamming_blocks` policy.
    """
    if interpret is None:
        interpret = common.default_interpret()
    g, b, w = q.shape
    g2, c, w2 = protos.shape
    assert g == g2 and w == w2, (q.shape, protos.shape)
    bq, bc = common.hamming_blocks(b, c, bq, bc)
    if not use_kernel:
        return _blocked(hamming_search_banked_ref, protos, 1, bc, q)
    qp = common.pad_dim(q, 1, bq)
    pp = common.pad_dim(protos, 1, bc)
    out = hamming_banked_pallas(qp, pp, bq=bq, bc=bc, interpret=interpret)
    return out[:, :b, :c]


def _extract_smallest_k(cand: jax.Array, k: int) -> jax.Array:
    """Ascending k smallest of `cand` [..., n] by k rounds of min-extraction
    (find the minimum, emit it, poison every entry equal to it). Requires the
    values to be UNIQUE — true for ``dist*C + col`` keys (distinct cols) —
    or equal minima collapse. This is the same merge the Pallas kernel runs
    in VMEM, and on CPU it beats a per-chunk ``lax.top_k`` by ~10x: XLA
    lowers top_k to a full row sort (scalar comparator loops), while k
    min+select rounds stay vectorized and fusion-friendly."""
    outs = []
    for _ in range(k):
        m = jnp.min(cand, axis=-1, keepdims=True)
        outs.append(m[..., 0])
        cand = jnp.where(cand == m, jnp.int32(_KEY_SENTINEL), cand)
    return jnp.stack(outs, axis=-1)


def _streamed_topk_banked(
    q: jax.Array, protos: jax.Array, bc: int, key_encode: bool | None = None,
    bank_rows: jax.Array | None = None, k: int | None = None,
) -> tuple[jax.Array, jax.Array]:
    """jnp fallback for the fused top-1: stream prototype chunks of `bc` through
    a running minimum carry. The full [G, B, C] distance tensor (and the
    [G, B, C, W] XOR intermediate past one chunk) never materializes — the same
    streaming reduction the Pallas kernel performs in VMEM. With ``bank_rows``
    set, protos is a [T, C, W] table and bank g streams row ``bank_rows[g]`` —
    the gather happens per chunk tile, so the expanded [G, C, W] view never
    materializes either.

    The (dist, col) pair is encoded as ONE int32 key ``dist * C + col`` so each
    chunk is a single reduction with a single consumer of its distance tile —
    XLA then fuses the whole XOR+popcount+min chain and the [G, B, bc] tile
    stays fusion-internal (min + argmin as two separate reductions each
    re-materialize the tile to HBM). Minimizing the key IS lexicographic
    (dist, col) order, i.e. first-minimum tie breaking, identical to
    `jnp.argmin`. Falls back to the two-reduction merge if the key could
    overflow int32 (never for the paper's shapes: needs (d+1)*C >= 2^31);
    `key_encode` overrides the auto-choice so tests can pin either branch on
    small shapes.

    With ``k`` set, the scalar carry widens to a length-k sorted buffer per
    (g, b) and the result is ([G, B, k], [G, B, k]) rank-sorted ascending by
    (dist, col) — the key branch merges each chunk's keys with k rounds of
    min-extraction (`_extract_smallest_k`, the kernel's VMEM merge; a
    per-chunk ``lax.top_k`` lowers to a full row SORT on CPU and costs ~6x
    the scan itself); the overflow branch carries (val, idx) pairs through a
    two-operand lexicographic ``lax.sort``. Neither re-materializes the
    [G, B, C] distances.
    """
    g, b, w = q.shape
    c = protos.shape[1]
    d = w * 32

    def tile(start, stop):
        chunk = jax.lax.slice_in_dim(protos, start, stop, axis=1)
        if bank_rows is not None:
            chunk = jnp.take(chunk, bank_rows, axis=0)      # [G, <=bc, W]
        return chunk

    if key_encode is None:
        key_encode = (d + 1) * c < 2**31
    if k is not None:
        assert 1 <= k <= c, (k, c)
        bc = max(bc, k)  # every chunk (and so every merge) holds >= k entries
        if key_encode:
            assert (d + 1) * c < 2**31, (d, c)
            best = None                                     # [G, B, k] keys, ascending
            for start in range(0, c, bc):
                chunk = tile(start, min(start + bc, c))
                dist = hamming_search_banked_ref(q, chunk)  # [G, B, <=bc]
                cols = start + jnp.arange(chunk.shape[1], dtype=jnp.int32)
                keys = dist * c + cols
                cand = keys if best is None else jnp.concatenate([best, keys], -1)
                best = _extract_smallest_k(cand, k)
            return best // c, best % c
        best_v = best_i = None
        for start in range(0, c, bc):
            chunk = tile(start, min(start + bc, c))
            dist = hamming_search_banked_ref(q, chunk)      # [G, B, <=bc]
            cols = jnp.broadcast_to(
                start + jnp.arange(chunk.shape[1], dtype=jnp.int32), dist.shape
            )
            if best_v is None:
                cand_v, cand_i = dist, cols
            else:
                cand_v = jnp.concatenate([best_v, dist], -1)
                cand_i = jnp.concatenate([best_i, cols], -1)
            # stable two-key sort == lexicographic (dist, col) rank order
            sv, si = jax.lax.sort((cand_v, cand_i), dimension=-1, num_keys=2)
            best_v, best_i = sv[..., :k], si[..., :k]
        return best_v, best_i
    if key_encode:
        assert (d + 1) * c < 2**31, (d, c)
        best_key = None
        for start in range(0, c, bc):
            chunk = tile(start, min(start + bc, c))
            dist = hamming_search_banked_ref(q, chunk)      # [G, B, <=bc]
            cols = start + jnp.arange(chunk.shape[1], dtype=jnp.int32)
            key = jnp.min(dist * c + cols, axis=-1)         # [G, B]
            best_key = key if best_key is None else jnp.minimum(best_key, key)
        return best_key // c, best_key % c
    best_v = best_i = None
    for start in range(0, c, bc):
        chunk = tile(start, min(start + bc, c))
        dist = hamming_search_banked_ref(q, chunk)          # [G, B, <=bc]
        v = jnp.min(dist, axis=-1)
        i = start + jnp.argmin(dist, axis=-1).astype(jnp.int32)
        if best_v is None:
            best_v, best_i = v, i
        else:
            better = v < best_v
            best_i = jnp.where(better, i, best_i)
            best_v = jnp.where(better, v, best_v)
    return best_v, best_i


def hamming_topk_banked(
    q: jax.Array,
    protos: jax.Array,
    *,
    k: int | None = None,
    bank_rows: jax.Array | None = None,
    bq: int | None = None,
    bc: int | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Fused per-bank top-k Hamming search: q [G, B, W], protos [G, C, W]
    -> (min_dist [G, B] int32, argmin [G, B] int32) for the default k=None
    (the fused top-1), or (dists, idxs) each [G, B, k] int32 for an explicit
    ``k``, rank-sorted ascending by (distance, class index) — rank r is the
    r-th first minimum, so every rank keeps the top-1 tie convention.

    Bank g's queries are searched only against bank g's prototypes and the
    class axis is reduced without writing the [G, B, C] distances to HBM —
    the kernel carries the running (min, argmin) in the revisited output VMEM
    tile; the jnp fallback streams prototype chunks through the same carry.
    Ties break toward the lowest class index (first minimum), exactly
    `jnp.argmax` over sims = d - 2*dist. B is zero-padded to bq and sliced
    away; padded prototype rows are masked inside the reduction so zero
    padding can never win.

    ``bank_rows`` [G] int32 adds a row indirection for multi-tenant serving:
    protos is then a [T, C, W] bank *table* and bank g searches table row
    ``bank_rows[g]`` (rows may repeat — slots sharing a tenant share the
    bank). The kernel path gathers the G referenced rows before the launch
    (same footprint the direct [G, C, W] call pays); the streamed fallback
    gathers per chunk tile and never materializes the expanded view.

    Block sizes default to the `common.hamming_blocks` policy. The top-k
    kernel needs the int32 key encoding ``dist*C + col`` to fit. If
    (d+1)*C_padded >= 2^31, interpret mode streams instead (the streamed
    overflow branch carries (val, idx) pairs); compiled for the chip, the
    call raises, so a served top-k never leaves the kernel unannounced.
    """
    if interpret is None:
        interpret = common.default_interpret()
    g, b, w = q.shape
    c, w2 = protos.shape[1], protos.shape[2]
    if bank_rows is None:
        assert g == protos.shape[0] and w == w2, (q.shape, protos.shape)
    else:
        assert bank_rows.shape == (g,) and w == w2, (
            q.shape, protos.shape, bank_rows.shape
        )
    bq, bc = common.hamming_blocks(b, c, bq, bc)
    if k is None:
        if not use_kernel:
            return _streamed_topk_banked(q, protos, bc, bank_rows=bank_rows)
        if bank_rows is not None:
            protos = jnp.take(protos, bank_rows, axis=0)    # [G, C, W]
        qp = common.pad_dim(q, 1, bq)
        pp = common.pad_dim(protos, 1, bc)
        val, idx = hamming_topk_banked_pallas(
            qp, pp, c_real=c, bq=bq, bc=bc, interpret=interpret
        )
        return val[:, :b], idx[:, :b]
    assert 1 <= k <= c, (k, c)
    c_pad = common.cdiv(c, bc) * bc
    overflow = (w * 32 + 1) * c_pad >= 2**31
    if overflow and use_kernel and not interpret:
        raise ValueError(
            f"top-{k} key (d+1)*C = {(w * 32 + 1) * c_pad} overflows int32: "
            "the fused top-k kernel cannot search this bank; shard the class "
            "axis over more cores"
        )
    if not use_kernel or overflow:
        return _streamed_topk_banked(q, protos, bc, bank_rows=bank_rows, k=k)
    if bank_rows is not None:
        protos = jnp.take(protos, bank_rows, axis=0)        # [G, C, W]
    qp = common.pad_dim(q, 1, bq)
    pp = common.pad_dim(protos, 1, bc)
    val, idx = hamming_topk_k_banked_pallas(
        qp, pp, c_real=c, k=k, bq=bq, bc=bc, interpret=interpret
    )
    return val[:, :b], idx[:, :b]
