"""Pallas TPU kernel: bipolar associative-memory matmul (the MXU IMC analogue).

Computes dots[b, c] = sum_k (2 q[b,k]-1)(2 p[c,k]-1) with uint8 {0,1} inputs
converted to bipolar bf16 *inside* the kernel (so HBM traffic stays 1 byte/element)
and accumulation in an f32 VMEM scratch across the k grid dimension.

Tiling: classic (bm, bn, bk) matmul; MXU-aligned blocks (multiples of 128 on the
lane dim, 8 on sublanes). The k-axis padding is masked in-kernel: a zero-padded
{0,1} input would otherwise turn into bipolar -1 and bias every dot by +1 per pad.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _to_bf16(bits: jax.Array) -> jax.Array:
    """uint8 {0,1} -> bf16 through int32: the TPU lowering has no direct
    uint8 -> bfloat16 cast."""
    return bits.astype(jnp.int32).astype(jnp.float32).astype(jnp.bfloat16)


def _assoc_kernel(q_ref, p_ref, o_ref, acc_ref, *, nk: int, bk: int, k_actual: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # bipolar conversion with k-padding mask (pads contribute 0, not (-1)·(-1)=+1)
    kpos = k * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    mask = (kpos < k_actual).astype(jnp.bfloat16)                      # [1, bk]
    qb = (2.0 * _to_bf16(q_ref[...]) - 1.0) * mask                     # [bm, bk]
    pb = (2.0 * _to_bf16(p_ref[...]) - 1.0) * mask                     # [bn, bk]
    acc_ref[...] += jax.lax.dot_general(
        qb, pb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _flush():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "k_actual", "interpret"))
def assoc_matmul_pallas(
    q: jax.Array,
    protos: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    k_actual: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """q [B, K] uint8, protos [C, K] uint8 -> [B, C] f32; dims divisible by blocks."""
    b, kdim = q.shape
    c, k2 = protos.shape
    assert kdim == k2, (kdim, k2)
    assert b % bm == 0 and c % bn == 0 and kdim % bk == 0, (b, bm, c, bn, kdim, bk)
    if k_actual is None:
        k_actual = kdim
    nk = kdim // bk
    grid = (b // bm, c // bn, nk)
    return pl.pallas_call(
        functools.partial(_assoc_kernel, nk=nk, bk=bk, k_actual=k_actual),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, c), jnp.float32),
        scratch_shapes=[_vmem_scratch(bm, bn)],
        interpret=interpret,
    )(q, protos)


def _vmem_scratch(bm: int, bn: int):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM((bm, bn), jnp.float32)
