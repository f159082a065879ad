"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # prefer the real engine when installed
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline CI: deterministic seeded fallback
    from _propcheck import given, settings, strategies as st

from repro.core import hypervector as hv
from repro.kernels.assoc_matmul import assoc_matmul
from repro.kernels.assoc_matmul.ref import assoc_matmul_ref
from repro.kernels.hamming import (
    hamming_search,
    hamming_search_banked,
    hamming_topk_banked,
)
from repro.kernels.hamming.ref import (
    hamming_search_banked_ref,
    hamming_search_ref,
    hamming_topk_banked_ref,
)
from repro.kernels.majority import majority_bundle
from repro.kernels.majority.ref import majority_bundle_ref

KEY = jax.random.PRNGKey(0)

SHAPES = [(4, 100, 512), (17, 33, 1024), (1, 7, 10016), (8, 128, 512), (3, 257, 2048)]


@pytest.mark.parametrize("b,c,d", SHAPES)
def test_hamming_kernel_sweep(b, c, d):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, b * c))
    q, p = hv.random_hv(k1, b, d), hv.random_hv(k2, c, d)
    qp, pp = hv.pack(q), hv.pack(p)
    got = hamming_search(qp, pp, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(hamming_search_ref(qp, pp)))


BANKED_SHAPES = [(4, 8, 128, 512), (3, 5, 7, 224), (8, 16, 2, 512), (1, 9, 130, 1024)]


@pytest.mark.parametrize("g,b,c,d", BANKED_SHAPES)
def test_hamming_banked_kernel_sweep(g, b, c, d):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, g * b * c))
    q = hv.pack(hv.random_hv(k1, g * b, d)).reshape(g, b, d // 32)
    p = hv.pack(hv.random_hv(k2, g * c, d)).reshape(g, c, d // 32)
    got = hamming_search_banked(q, p, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(hamming_search_banked_ref(q, p))
    )


# the served banks' geometry: 100 classes a core (a full-dim class block,
# not a multiple of 8), W = 16 (Table I, B = 64) and W = 64 (WHYPE, B = 512),
# a B that is not a multiple of 8, a B above one 512-row query block, and a
# class axis above TALL_C (class blocks of 512 with the running carry)
SERVED_BANKS = [(3, 64, 100, 512), (2, 512, 100, 2048), (2, 13, 100, 2048),
                (2, 600, 100, 512), (1, 5, 4100, 256)]


@pytest.mark.parametrize(
    "g,b,c,d", BANKED_SHAPES + [(2, 3, 300, 512)] + SERVED_BANKS
)
@pytest.mark.parametrize("use_kernel", [True, False])
def test_hamming_topk_banked_sweep(g, b, c, d, use_kernel):
    """Fused top-1 (kernel and streaming-jnp fallback) == jnp min/argmin oracle."""
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, g * b * c + 1))
    q = hv.pack(hv.random_hv(k1, g * b, d)).reshape(g, b, d // 32)
    p = hv.pack(hv.random_hv(k2, g * c, d)).reshape(g, c, d // 32)
    rv, ri = hamming_topk_banked_ref(q, p)
    v, i = hamming_topk_banked(q, p, use_kernel=use_kernel, interpret=True)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_hamming_topk_banked_tie_breaking(use_kernel):
    """Ties resolve toward the LOWEST class index — `jnp.argmax` first-max
    semantics on similarities — inside one full 100-class block, and when the
    duplicates straddle the bc=128 tile boundary of the revisited-grid
    reduction (the strict `<` merge must keep the earlier tile's winner)."""
    d = 512
    q = hv.pack(hv.random_hv(jax.random.PRNGKey(0), 2, d)).reshape(1, 2, d // 32)
    base = hv.pack(hv.random_hv(jax.random.PRNGKey(1), 300, d))
    # plant the query itself (distance 0) at several duplicate positions that
    # span different tiles; the reported argmin must always be the first one
    cases = [(100, None, dup) for dup in [(5, 17), (0, 99), (31, 32, 98)]] + [
        (300, bc, dup)  # one 300-class block, or 3 tiles of 128 (one padded)
        for bc in (None, 128)
        for dup in [(5, 17), (5, 200), (130, 260), (129, 130, 299)]
    ]
    for c, bc, dup_positions in cases:
        p = base[:c]
        for pos in dup_positions:
            p = p.at[pos].set(q[0, 0])
        pb = p[None]  # [1, C, W]
        v, i = hamming_topk_banked(q[:, :1], pb, bc=bc, use_kernel=use_kernel,
                                   interpret=True)
        assert int(v[0, 0]) == 0
        assert int(i[0, 0]) == dup_positions[0], (dup_positions, int(i[0, 0]))
        # and it matches the one-shot argmax-over-similarities semantics
        dist = hamming_search_banked_ref(q[:, :1], pb)
        sims = d - 2 * dist
        assert int(i[0, 0]) == int(jnp.argmax(sims[0, 0]))


@pytest.mark.parametrize("key_encode", [True, False])
def test_hamming_topk_streamed_both_branches(key_encode):
    """Both merge strategies of the streamed fallback (int32 key encoding and
    the two-reduction strict-< carry for shapes where the key would overflow)
    must agree with the oracle — including duplicate-distance ties straddling
    the chunk boundary, which is exactly what the two-pass merge can get wrong."""
    from repro.kernels.hamming import ops

    d, c = 512, 300  # 3 chunks of bc=128
    q = hv.pack(hv.random_hv(jax.random.PRNGKey(3), 4, d)).reshape(2, 2, d // 32)
    p = hv.pack(hv.random_hv(jax.random.PRNGKey(4), 2 * c, d)).reshape(2, c, d // 32)
    # plant cross-chunk duplicates of one query so the merge sees exact ties
    p = p.at[0, 130].set(q[0, 0]).at[0, 260].set(q[0, 0])
    rv, ri = hamming_topk_banked_ref(q, p)
    v, i = ops._streamed_topk_banked(q, p, bc=128, key_encode=key_encode)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    assert int(i[0, 0]) == 130  # the first duplicate wins


@pytest.mark.parametrize("backend,interpret", [("tpu", False), ("cpu", True)])
def test_interpret_mode_only_off_the_chip(monkeypatch, backend, interpret):
    """Kernels compile for the chip on a TPU backend; interpret mode is what
    the CPU runs."""
    from repro.kernels import common

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert common.default_interpret() is interpret


def test_hamming_banked_equals_per_bank_loop():
    """One banked launch == G independent hamming_search calls."""
    g, b, c, d = 5, 6, 40, 512
    k1, k2 = jax.random.split(KEY)
    q = hv.pack(hv.random_hv(k1, g * b, d)).reshape(g, b, d // 32)
    p = hv.pack(hv.random_hv(k2, g * c, d)).reshape(g, c, d // 32)
    got = hamming_search_banked(q, p, interpret=True)
    loop = jnp.stack([hamming_search(q[i], p[i], interpret=True) for i in range(g)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(loop))


@pytest.mark.parametrize("b,c,d", SHAPES)
def test_assoc_matmul_kernel_sweep(b, c, d):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, b + c))
    q, p = hv.random_hv(k1, b, d), hv.random_hv(k2, c, d)
    got = assoc_matmul(q, p, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(assoc_matmul_ref(q, p)), atol=0)


@pytest.mark.parametrize("m,b,d", [(3, 5, 512), (7, 2, 384), (4, 33, 129), (11, 8, 2048)])
def test_majority_kernel_sweep(m, b, d):
    x = hv.random_hv(jax.random.fold_in(KEY, m * d), m * b, d).reshape(m, b, d)
    got = majority_bundle(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(majority_bundle_ref(x)))


def test_kernel_identity_dot_equals_dim_minus_2hamming():
    """Cross-kernel invariant: assoc dot == d - 2*hamming (the IMC MVM identity)."""
    k1, k2 = jax.random.split(KEY)
    q, p = hv.random_hv(k1, 6, 768), hv.random_hv(k2, 50, 768)
    dots = assoc_matmul(q, p, interpret=True)
    dist = hamming_search(hv.pack(q), hv.pack(p), interpret=True)
    np.testing.assert_allclose(np.asarray(dots), 768 - 2 * np.asarray(dist), atol=0)


@settings(max_examples=10, deadline=None)
@given(
    st.integers(0, 2**31 - 1),
    st.integers(1, 9),
    st.integers(1, 40),
    st.integers(2, 40).map(lambda w: w * 32),
)
def test_hamming_kernel_property(seed, b, c, d):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    q, p = hv.random_hv(k1, b, d), hv.random_hv(k2, c, d)
    qp, pp = hv.pack(q), hv.pack(p)
    got = hamming_search(qp, pp, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(hamming_search_ref(qp, pp)))


@pytest.mark.parametrize("dtype", [jnp.uint8])
def test_majority_vs_core_majority(dtype):
    """Kernel agrees with core.hypervector.majority for odd M."""
    x = hv.random_hv(KEY, 5 * 4, 640).reshape(5, 4, 640).astype(dtype)
    np.testing.assert_array_equal(
        np.asarray(majority_bundle(x, interpret=True)), np.asarray(hv.majority(x))
    )


# ---------------------------------------------------------------------------
# fused flash attention (TPU fast path)
# ---------------------------------------------------------------------------

import jax as _jax
import jax.numpy as _jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention.ref import flash_fwd_ref


@pytest.mark.parametrize(
    "s,h,kh,d,win,causal,bq,bk",
    [(256, 4, 2, 32, -1, True, 64, 64),
     (256, 4, 1, 64, 64, True, 64, 128),
     (128, 6, 6, 16, -1, False, 64, 64),
     (512, 2, 2, 128, 128, True, 128, 256)],
)
def test_pallas_flash_attention_sweep(s, h, kh, d, win, causal, bq, bk):
    ks = _jax.random.split(_jax.random.fold_in(KEY, s + h + d), 3)
    q = _jax.random.normal(ks[0], (2, s, h, d), _jnp.float32)
    k = _jax.random.normal(ks[1], (2, s, kh, d), _jnp.float32)
    v = _jax.random.normal(ks[2], (2, s, kh, d), _jnp.float32)
    got = flash_attention_fwd(q, k, v, causal=causal, window=win,
                              block_q=bq, block_k=bk, interpret=True)
    want = flash_fwd_ref(q, k, v, causal=causal, window=win, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# RX fan-out: each core's bitplane-noisy copy (`kernels.bsc`)
# ---------------------------------------------------------------------------

from jax.extend import core as _jcore

from repro import phy
from repro.kernels import common
from repro.kernels.bsc import bsc_bitplane_copies
from repro.kernels.bsc.kernel import LANES, bsc_bitplane_pallas

# the largest BER the 16-bit threshold holds: every lane flips but u = 2^16 - 1
EDGE_BER = 1.0 - 2.0**-16


@pytest.mark.parametrize("n,n_core,b,d,rx_base,blocks", [
    (3, 4, 8, 512, 0, None),        # the ring (slots), W=16, the policy's blocks
    (3, 5, 8, 2048, 7, (2, 2, 8)),  # W=64; 5 cores and 3 slots in blocks of 2
    (1, 6, 40, 2048, 64, (1, 4, 8)),  # standalone (one slot), 3 row blocks
    (1, 3, 13, 512, 5, None),       # B*W = 208 words: the last row padded
])
def test_bsc_bitplane_copies_equal_the_xla_path(monkeypatch, n, n_core, b, d,
                                                rx_base, blocks):
    """`BSCChannel.rx_copies_slots` with the kernel equals, bit for bit, the
    vmap of `rx_copies` over the slots (`collectives.ota_noise_packed` in
    bitplane mode, 16 planes), cores keyed ``fold_in(key, rx_base + i)``;
    BER 0 and 1 - 2^-16 on the edge cores."""
    if blocks is not None:
        monkeypatch.setattr(common, "bitplane_blocks", lambda *a: blocks)
    w = d // 32
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, n_core * d + b), 3)
    words = jax.random.bits(k1, (n, b, w), jnp.uint32)
    keys = jax.random.bits(k2, (n, 2), jnp.uint32)
    ber = jax.random.uniform(k3, (n_core,), maxval=0.5)
    ber = ber.at[0].set(0.0).at[-1].set(EDGE_BER)
    state = phy.state_from_ber(ber, 3)
    chan = phy.get_channel("bsc")
    kw = dict(rx_base=jnp.int32(rx_base), n_cores=n_core, packed=True, dim=d,
              noise="bitplane", planes=16)
    got = chan.rx_copies_slots(keys, words, state, use_kernel=True, **kw)
    want = chan.rx_copies_slots(keys, words, state, use_kernel=False, **kw)
    assert got.shape == (n, n_core, b, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:, 0]),
                                  np.broadcast_to(np.asarray(words)[:, None],
                                                  (n, 1, b, w))[:, 0])
    flipped = np.unpackbits(np.asarray(got[:, -1] ^ words).view(np.uint8)).mean()
    assert flipped > 0.999


def test_bsc_bitplane_copies_at_eight_planes():
    """The control's precision (8 planes) runs the kernel, bit for bit."""
    k1, k2 = jax.random.split(KEY)
    words = jax.random.bits(k1, (2, 8, 16), jnp.uint32)
    keys = jax.random.bits(k2, (2, 3, 2), jnp.uint32)
    ber = jnp.array([0.01, 0.2, 0.37])
    got = bsc_bitplane_copies(keys, words, ber, precision=8)
    want = bsc_bitplane_copies(keys, words, ber, precision=8, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bitplane_blocks_policy():
    # (slots, cores, rows) a block at the cells' shapes: whole lane-dense
    # planes, about BITPLANE_STEP_WORDS a block
    assert common.bitplane_blocks(2, 1024, 512, 64) == (1, 8, 256)   # whype
    assert common.bitplane_blocks(32, 64, 64, 16) == (4, 64, 8)      # table1
    assert common.bitplane_blocks(8, 256, 512, 64) == (1, 8, 256)    # whype4
    assert common.bitplane_blocks(1, 3, 13, 16) == (1, 3, 8)
    # planes taller than BITPLANE_RB rows split evenly, in multiples of 8
    assert common.bitplane_blocks(1, 4, 4096, 64) == (1, 4, common.BITPLANE_RB)
    assert common.bitplane_blocks(1, 4, 600 * 8, 16)[2] == 304  # 600 rows: 2 x 304
    for shape in [(2, 1024, 512, 64), (32, 64, 64, 16), (5, 7, 40, 64)]:
        sb, cb, rb = common.bitplane_blocks(*shape)
        assert rb % 8 == 0 and sb * cb * rb * LANES <= max(
            common.BITPLANE_STEP_WORDS, rb * LANES)


def _eqn_count(jaxpr) -> int:
    """Equations of a jaxpr, counting those of every nested jaxpr."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, _jcore.ClosedJaxpr):
                    n += _eqn_count(sub.jaxpr)
                elif isinstance(sub, _jcore.Jaxpr):
                    n += _eqn_count(sub)
    return n


def _kernel_body_eqns(n, n_core, b, w, blocks=None) -> int:
    sb, cb, rb = blocks or common.bitplane_blocks(n, n_core, b, w)
    rows = common.cdiv(common.cdiv(b * w, LANES), rb) * rb
    jaxpr = jax.make_jaxpr(
        lambda kt, x: bsc_bitplane_pallas(kt, x, precision=16, plane_words=b * w,
                                          sb=sb, cb=cb, rb=rb)
    )(jax.ShapeDtypeStruct((n, n_core, 3, LANES), jnp.uint32),
      jax.ShapeDtypeStruct((n, rows, LANES), jnp.uint32)).jaxpr

    def find(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for v in eqn.params.values():
                if isinstance(v, _jcore.ClosedJaxpr) and (hit := find(v.jaxpr)):
                    return hit
        return None

    return _eqn_count(find(jaxpr).params["jaxpr"])


def test_bsc_bitplane_kernel_body_does_not_grow_with_its_block():
    """The kernel's traced body has one size whatever its block holds: Table
    I's block of 4 slots x 64 cores of 8 rows, WHYPE's of 8 cores of 256 rows
    and a one-pair block trace to the same equations. A body that looped in
    Python over its block's slots or cores would grow with them, and every
    process traces and lowers it again before the compile cache is read."""
    table1 = _kernel_body_eqns(32, 64, 64, 16)
    whype = _kernel_body_eqns(2, 1024, 512, 64)
    one_pair = _kernel_body_eqns(1, 1, 8, 128, blocks=(1, 1, 8))
    big = _kernel_body_eqns(8, 64, 64, 16, blocks=(8, 64, 8))
    assert table1 == whype == one_pair == big
    assert table1 < 2500  # 16 unrolled planes of ~125 ops each
