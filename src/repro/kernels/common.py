"""Shared helpers for the Pallas kernels.

All kernels target TPU (pl.pallas_call + explicit BlockSpec VMEM tiling) and are
validated on CPU with ``interpret=True`` — the kernel body runs in Python against the
same BlockSpec pipeline, so index maps / tiling bugs surface on CPU.
"""
from __future__ import annotations

import math

import jax


def default_interpret() -> bool:
    """Interpret mode on anything that is not a real TPU (CPU CI, dry-run host)."""
    return jax.default_backend() != "tpu"


def pad_dim(x: jax.Array, axis: int, multiple: int, fill=0) -> jax.Array:
    """Pad `axis` of `x` up to the next multiple of `multiple` with `fill`."""
    import jax.numpy as jnp

    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=fill)


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


# Canonical Hamming-kernel tile sizes. ``BQ`` rides the 8-sublane dimension of
# the query tile; ``BC`` is one 128-lane row of the class axis. Every hamming
# entry point (fused kernels AND the streamed jnp fallback) resolves its block
# sizes through ``hamming_blocks`` so the tiling policy lives in exactly one
# place.
BQ = 8
BC = 128

# Class-axis size above which the wider class tile pays off (see
# ``hamming_blocks``).
TALL_C = 4096


def hamming_blocks(
    b: int, c: int, bq: int | None = None, bc: int | None = None
) -> tuple[int, int]:
    """Resolve the (bq, bc) tile sizes for a Hamming search over ``b`` queries
    and ``c`` classes; explicit values win, ``None`` takes the policy default.

    Tall class axes (the WHYPE-scale per-core shards and the coarse-to-fine
    screen/rescore) get a 4x wider class tile: 4x fewer revisits of the
    ``(g, i)`` running-min carry per output tile — and 4x fewer unrolled
    chunks in the streamed fallback — while an ``[8, 512, W]`` tile still sits
    far inside VMEM at the paper's word counts.
    """
    if bq is None:
        bq = BQ
    if bc is None:
        bc = 4 * BC if c >= TALL_C else BC
    return bq, bc


# The fused top-1 runs on the MXU, so its tiles follow the matmul and not the
# VPU: a query block holds the bank's whole trial axis up to ``TOP1_BQ`` rows,
# a class block the bank's whole class axis below ``TALL_C``, and a grid step
# holds whole banks until it has about ``TOP1_STEP_MACS`` bit products.
TOP1_BQ = 512
TOP1_STEP_MACS = 2**27


def top1_blocks(
    g: int, b: int, c: int, w: int, bq: int | None = None,
    bc: int | None = None,
) -> tuple[int, int, int]:
    """Resolve (banks per step, bq, bc) for the fused top-1 over ``g`` banks
    of ``b`` queries and ``c`` classes of ``w`` packed words; explicit values
    win, ``None`` takes the policy.

    - ``bq``: ``b`` rounded up to 8 while that is at most ``TOP1_BQ``, else
      128, 256 or 512 rows, whichever divides ``b`` rounded up to 128 (an
      output block's last dim is the whole padded axis or lane-aligned).
    - ``bc``: all ``c`` classes in one block (a full-dim block needs no
      padding, as at 100 classes a core), or ``4 * BC`` on a tall class axis,
      where the kernel carries the running (min, argmin) across class blocks.
    - banks per step: the largest divisor of ``g`` that keeps a step near
      ``TOP1_STEP_MACS``, so small banks (64 trials of 512 bits) do not each
      pay a grid step's overhead.
    """
    if bq is None:
        bq = cdiv(b, 8) * 8
        if bq > TOP1_BQ:
            bq = 128 * math.gcd(cdiv(b, 128), TOP1_BQ // 128)
    if bc is None:
        bc = 4 * BC if c >= TALL_C else c
    want = max(1, TOP1_STEP_MACS // (bq * bc * 32 * w))
    nb = max(k for k in range(1, min(g, want) + 1) if g % k == 0)
    return nb, bq, bc


# The RX fan-out's bitplane kernel is bound by the VPU (16 threefry hashes a
# mask word), so its blocks only have to amortize a grid step: a block holds
# whole lane-dense core planes of up to ``BITPLANE_RB`` rows and about
# ``BITPLANE_STEP_WORDS`` output words.
BITPLANE_RB = 512
BITPLANE_STEP_WORDS = 2**18


def bitplane_blocks(n: int, n_core: int, b: int, w: int) -> tuple[int, int, int]:
    """Resolve (slots, cores, rows) a block for the bitplane copies of ``n``
    slots x ``n_core`` cores of [b, w] words, each core's plane read as
    rows of 128 words.

    - rows: the plane's rows rounded up to 8, split evenly into blocks of at
      most ``BITPLANE_RB`` rows (Table I's plane is 8 rows, WHYPE's 256);
    - cores, then slots: as many whole planes as keep the block near
      ``BITPLANE_STEP_WORDS`` (8 cores of one slot at WHYPE, all 64 cores
      of 4 slots at Table I). They need not divide ``n_core`` or ``n``: the
      edge block computes on padding that is not written.
    """
    rows = cdiv(cdiv(b * w, 128), 8)
    rb = 8 * cdiv(rows, cdiv(rows * 8, BITPLANE_RB))
    pairs = max(1, BITPLANE_STEP_WORDS // (rb * 128))
    cb = min(n_core, pairs)
    sb = min(n, max(1, pairs // cb))
    return sb, cb, rb
