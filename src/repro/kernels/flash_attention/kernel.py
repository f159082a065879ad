"""Pallas TPU kernel: fused causal flash attention (forward).

This is the fix for the dominant memory term of §Roofline: in the pure-JAX
blockwise formulation the per-(q,kv)-block score/probability tensors
materialize at fusion boundaries (HBM round-trips); here they live in VMEM for
the lifetime of a grid cell.

Grid: (batch*heads, Sq/block_q, Skv/block_k) with the kv axis innermost
("arbitrary" — it carries the online-softmax state in VMEM scratch). BlockSpecs
stream q/k/v blocks HBM->VMEM; per-cell working set is
block_q*d + block_k*d (+ block_q*block_k scores) — a few hundred KB at the
default 512/1024 blocks, well under the 128 MB VMEM budget. GQA is handled by
mapping each q-head's grid row to its kv head via the index map (no expanded KV
is ever materialized).

The backward pass stays on the custom-VJP scan path (models/layers.py); a
fused bwd kernel is the natural next step and follows the same tiling.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               nk: int, block_q: int, block_k: int, causal: bool, window: int,
               scale: float):
    kj = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                         # [bq, d]
    k = k_ref[0]                         # [bk, d]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                            # [bq, bk]
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        ok &= k_pos <= q_pos
    if window > 0:
        ok &= (q_pos - k_pos) < window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_prev * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(kj == nk - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret")
)
def flash_fwd_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = -1,
    block_q: int = 512,
    block_k: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    """q [B, Sq, H, D]; k, v [B, Skv, KH, D], H % KH == 0 -> out [B, Sq, H, D].

    Sq % block_q == Skv % block_k == 0 (ops.py pads).
    """
    b, sq, h, d = q.shape
    skv, kh = k.shape[1], k.shape[2]
    g = h // kh
    nq, nk = sq // block_q, skv // block_k
    scale = 1.0 / math.sqrt(d)

    # head-major layouts: q [B*H, Sq, D]; kv [B*KH, Skv, D]
    qh = jnp.moveaxis(q, 2, 1).reshape(b * h, sq, d)
    kh_ = jnp.moveaxis(k, 2, 1).reshape(b * kh, skv, d)
    vh = jnp.moveaxis(v, 2, 1).reshape(b * kh, skv, d)

    kernel = functools.partial(
        _fa_kernel, nk=nk, block_q=block_q, block_k=block_k,
        causal=causal, window=window, scale=scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
            # GQA: q-head bh reads kv head bh//g — no expanded KV materializes
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kj: (bh // g, kj, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, qi, kj: (bh // g, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh_, vh)
    return jnp.moveaxis(out.reshape(b, h, sq, d), 1, 2)
