"""Multi-centroid associative memory: fused top-k kernel, masked majority,
k-means-in-packed-space training, and the coarse-to-fine two-level serve.

Single-device layers (kernel vs oracle, tie-breaking, masked majority,
multi-centroid train/predict) run in-process; the serve layers run on 8 fake
CPU devices via subprocess (same pattern as test_distributed.py — the main
test process must keep seeing 1 device).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import classifier, hypervector as hv
from repro.core.scaleout import ScaleOutConfig, _validate_coarse
from repro.kernels import common
from repro.kernels.hamming import hamming_topk_banked
from repro.kernels.hamming.ops import _streamed_topk_banked
from repro.kernels.hamming.ref import (
    hamming_topk_banked_ref,
    hamming_topk_k_banked_ref,
)
from repro.serving.hdc import centroid_to_class, multicentroid_bank

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

KEY = jax.random.PRNGKey(0)

# (g, b, c, d): multi-tile class axes, non-multiple-of-block shapes, c < k
# headroom, and a c spanning several 128-row tiles
SHAPES = [(4, 8, 128, 512), (3, 5, 7, 224), (8, 16, 2, 512), (1, 9, 300, 1024)]


def run8(code: str, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"
    return r.stdout


def _banks(g, b, c, d, seed=0):
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, seed + g * b * c))
    q = hv.pack(hv.random_hv(k1, g * b, d)).reshape(g, b, -1)
    p = hv.pack(hv.random_hv(k2, g * c, d)).reshape(g, c, -1)
    return q, p


@pytest.mark.parametrize("g,b,c,d", SHAPES)
@pytest.mark.parametrize("use_kernel", [True, False])
def test_topk_matches_oracle(g, b, c, d, use_kernel):
    q, p = _banks(g, b, c, d)
    for k in sorted({1, 2, min(5, c)}):
        got_d, got_i = hamming_topk_banked(
            q, p, k=k, use_kernel=use_kernel, interpret=True
        )
        ref_d, ref_i = hamming_topk_k_banked_ref(q, p, k)
        np.testing.assert_array_equal(np.asarray(got_d), np.asarray(ref_d))
        np.testing.assert_array_equal(np.asarray(got_i), np.asarray(ref_i))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_topk_k1_bit_identical_to_fused_top1(use_kernel):
    g, b, c, d = 3, 7, 260, 512
    q, p = _banks(g, b, c, d, seed=1)
    top1_d, top1_i = hamming_topk_banked(
        q, p, use_kernel=use_kernel, interpret=True
    )
    k_d, k_i = hamming_topk_banked(
        q, p, k=1, use_kernel=use_kernel, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(k_d[..., 0]), np.asarray(top1_d))
    np.testing.assert_array_equal(np.asarray(k_i[..., 0]), np.asarray(top1_i))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_topk_tie_breaking_across_tiles(use_kernel):
    # adversarial ties: every prototype row identical, so every distance ties
    # and rank r must be class index r (first minimum at every rank) — with a
    # tiny bc the class axis spans many tiles, so the merge carry must
    # preserve the cross-tile rank order, not just the within-tile one
    g, b, c, d, k = 2, 4, 24, 256, 6
    kq, kp = jax.random.split(jax.random.fold_in(KEY, 99))
    q = hv.pack(hv.random_hv(kq, g * b, d)).reshape(g, b, -1)
    row = hv.pack(hv.random_hv(kp, g, d))
    p = jnp.broadcast_to(row[:, None, :], (g, c, row.shape[-1]))
    got_d, got_i = hamming_topk_banked(
        q, p, k=k, bc=8, use_kernel=use_kernel, interpret=True
    )
    np.testing.assert_array_equal(
        np.asarray(got_i),
        np.broadcast_to(np.arange(k, dtype=np.int32), (g, b, k)),
    )
    assert bool(jnp.all(got_d == got_d[..., :1]))
    # controlled distances: row j of each bank is the query with exactly j
    # bits flipped, and the 12 rows are duplicated at col j+12 — the exact
    # rank order is forced: (dist 0, col 0), (dist 0, col 12), (dist 1,
    # col 1), ... interleaving copies across the 8-wide tile boundaries
    q_bits = hv.random_hv(jax.random.fold_in(KEY, 3), g, d)
    flips = np.zeros((12, d), np.uint8)
    for j in range(12):
        flips[j, :j] = 1
    p_bits = np.asarray(q_bits)[:, None, :] ^ flips[None]   # [g, 12, d]
    p2 = jnp.concatenate([hv.pack(jnp.asarray(p_bits))] * 2, axis=1)
    q2 = hv.pack(q_bits)[:, None, :]                        # b = 1
    d2, i2 = hamming_topk_banked(
        q2, p2, k=6, bc=8, use_kernel=use_kernel, interpret=True
    )
    want_d = np.repeat(np.arange(3, dtype=np.int32), 2)     # 0,0,1,1,2,2
    want_i = np.array([0, 12, 1, 13, 2, 14], np.int32)
    np.testing.assert_array_equal(
        np.asarray(d2), np.broadcast_to(want_d, (g, 1, 6))
    )
    np.testing.assert_array_equal(
        np.asarray(i2), np.broadcast_to(want_i, (g, 1, 6))
    )


def test_streamed_topk_both_branches_match_oracle():
    g, b, c, d, k = 2, 6, 70, 512, 5
    q, p = _banks(g, b, c, d, seed=4)
    ref = hamming_topk_k_banked_ref(q, p, k)
    for key_encode in (True, False):
        got = _streamed_topk_banked(q, p, 16, key_encode=key_encode, k=k)
        for gx, rx in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))


@pytest.mark.parametrize("use_kernel", [True, False])
def test_topk_bank_rows_indirection(use_kernel):
    t, g, b, c, d, k = 5, 8, 3, 40, 256, 3
    kq, kp, kr = jax.random.split(jax.random.fold_in(KEY, 5), 3)
    q = hv.pack(hv.random_hv(kq, g * b, d)).reshape(g, b, -1)
    table = hv.pack(hv.random_hv(kp, t * c, d)).reshape(t, c, -1)
    rows = jax.random.randint(kr, (g,), 0, t, dtype=jnp.int32)  # repeats likely
    rows = rows.at[1].set(rows[0])                              # and certain
    banks = jnp.take(table, rows, axis=0)
    for kk, ref in [(k, hamming_topk_k_banked_ref(q, banks, k)),
                    (None, hamming_topk_banked_ref(q, banks))]:
        got = hamming_topk_banked(
            q, table, k=kk, bank_rows=rows, use_kernel=use_kernel,
            interpret=True
        )
        for gx, rx in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(gx), np.asarray(rx))


@pytest.mark.parametrize("interpret", [True, False])
def test_topk_key_overflow_streams_only_in_interpret_mode(interpret):
    """(d+1)*C >= 2**31 cannot be key-encoded: interpret mode streams the
    (val, idx) carry instead, the chip's kernel path refuses the shape."""
    q = jax.ShapeDtypeStruct((1, 8, 2048), jnp.uint32)        # d = 65,536
    p = jax.ShapeDtypeStruct((1, 32768, 2048), jnp.uint32)    # C = 32,768

    def call(q, p):
        return hamming_topk_banked(q, p, k=2, interpret=interpret)

    if interpret:
        dist, idx = jax.eval_shape(call, q, p)
        assert dist.shape == idx.shape == (1, 8, 2)
    else:
        with pytest.raises(ValueError, match="overflows int32"):
            jax.eval_shape(call, q, p)


def test_hamming_blocks_policy():
    # defaults below / at the tall-C threshold; explicit overrides always win
    assert common.hamming_blocks(64, 512) == (common.BQ, common.BC)
    assert common.hamming_blocks(64, common.TALL_C) == (common.BQ, 4 * common.BC)
    assert common.hamming_blocks(64, 10 * common.TALL_C) == (
        common.BQ, 4 * common.BC
    )
    assert common.hamming_blocks(64, common.TALL_C - 1) == (common.BQ, common.BC)
    assert common.hamming_blocks(64, common.TALL_C, bq=4, bc=32) == (4, 32)
    assert common.hamming_blocks(64, 512, bc=256) == (common.BQ, 256)


def test_top1_blocks_policy():
    # (banks per step, bq, bc) of the MXU top-1; explicit overrides win
    whype, table1 = (2048, 512, 100, 64), (2048, 64, 100, 16)
    assert common.top1_blocks(*whype) == (1, 512, 100)     # one bank a step
    nb, bq, bc = common.top1_blocks(*table1)
    assert (bq, bc) == (64, 100) and 2048 % nb == 0 and nb > 1
    assert nb * 64 * 100 * 512 <= common.TOP1_STEP_MACS
    assert common.top1_blocks(7, 64, 100, 16)[0] in (1, 7)  # divides G
    assert common.top1_blocks(1, 13, 100, 64)[1] == 16      # B up to x8
    assert common.top1_blocks(1, 600, 100, 16)[1] == 128    # 640 = 5 x 128
    assert common.top1_blocks(1, 1024, 100, 16)[1] == common.TOP1_BQ
    assert common.top1_blocks(1, 8, common.TALL_C - 1, 64)[2] == common.TALL_C - 1
    assert common.top1_blocks(1, 8, common.TALL_C, 64)[2] == 4 * common.BC
    assert common.top1_blocks(4, 64, 100, 16, bq=8, bc=32)[1:] == (8, 32)


def test_majority_packed_masked_matches_numpy():
    m, n, d = 9, 6, 256
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 6))
    bits = hv.random_hv(k1, m * n, d).reshape(m, n, d)
    hvs = hv.pack(bits)
    mask = jax.random.bernoulli(k2, 0.6, (m, n))
    got = hv.unpack(hv.majority_packed_masked(hvs, mask), d)
    b_np, m_np = np.asarray(bits), np.asarray(mask)
    counts = (b_np * m_np[..., None]).sum(0)
    want = (counts * 2 > m_np.sum(0)[..., None]).astype(np.uint8)
    np.testing.assert_array_equal(np.asarray(got), want)
    # empty mask -> all-zero words; full mask == unmasked majority_packed
    zero = hv.majority_packed_masked(hvs, jnp.zeros((m, n), bool))
    assert not np.asarray(zero).any()
    full = hv.majority_packed_masked(hvs[:, 0], jnp.ones((m,), bool))
    np.testing.assert_array_equal(
        np.asarray(full), np.asarray(hv.majority_packed(hvs[:, 0]))
    )
    # the threshold comparator must accept a TRACED mask (k-means assignment)
    jitted = jax.jit(hv.majority_packed_masked)
    np.testing.assert_array_equal(
        np.asarray(jitted(hvs, mask)),
        np.asarray(hv.majority_packed_masked(hvs, mask)),
    )


def test_train_multicentroid_accuracy():
    c, d, k_c = 20, 512, 4
    protos = hv.random_hv(jax.random.fold_in(KEY, 7), c, d)
    cents = classifier.train_multicentroid(
        jax.random.PRNGKey(1), protos, k_c, samples_per_class=16, ber=0.08
    )
    assert cents.shape == (c, k_c, d // 32) and cents.dtype == jnp.uint32
    # centroids stay near their class prototype: well under the d/2 distance
    # of an unrelated random HV
    pp = hv.pack(protos)
    dist = jax.vmap(lambda ce, pr: hv.hamming_distance_packed(ce, pr[None]))(
        cents, pp
    )
    assert int(jnp.max(dist)) < d // 4
    # clean queries classify perfectly; noisy queries should too at this scale
    for ber in (0.0, 0.1):
        qs = hv.flip_bits_packed(jax.random.PRNGKey(2), pp, ber)
        pred = classifier.multicentroid_predict(qs, cents, use_kernels=False)
        np.testing.assert_array_equal(np.asarray(pred), np.arange(c))


def test_multicentroid_bank_serving_helpers():
    c, d, k_c = 10, 256, 3
    protos = hv.random_hv(jax.random.fold_in(KEY, 8), c, d)
    for rep in ("packed", "unpacked"):
        cfg = ScaleOutConfig(n_classes=c * k_c, dim=d, m_tx=3, n_rx_cores=2,
                             batch=4, representation=rep)
        bank = multicentroid_bank(jax.random.PRNGKey(3), protos, k_c, cfg,
                                  samples_per_class=8)
        last = cfg.words if cfg.packed else cfg.dim
        assert bank.shape == (c * k_c, last) and bank.dtype == (
            jnp.uint32 if cfg.packed else jnp.uint8
        )
        # class-major layout: flat row i*k_c + j is class i's j-th centroid
        cents = classifier.train_multicentroid(
            jax.random.PRNGKey(3), protos, k_c, samples_per_class=8
        )
        flat = cents.reshape(c * k_c, -1)
        if not cfg.packed:
            flat = hv.unpack(flat, d).astype(jnp.uint8)
        np.testing.assert_array_equal(np.asarray(bank), np.asarray(flat))
    pred = jnp.array([[0, 2], [5, 29]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(centroid_to_class(pred, k_c)),
        np.asarray(pred) // k_c,
    )


def test_coarse_validation():
    base = dict(n_classes=64, dim=512, m_tx=3, n_rx_cores=8, batch=8)
    _validate_coarse(ScaleOutConfig(**base))  # coarse off: always fine
    _validate_coarse(ScaleOutConfig(**base, coarse_group=4, coarse_keep=2))
    with pytest.raises(ValueError, match="permuted"):
        _validate_coarse(ScaleOutConfig(**base, permuted=True, coarse_group=4))
    with pytest.raises(ValueError, match="divide"):
        _validate_coarse(ScaleOutConfig(**base, coarse_group=3))
    with pytest.raises(ValueError, match="divide"):
        _validate_coarse(ScaleOutConfig(**base, coarse_group=1))
    with pytest.raises(ValueError, match="coarse_keep"):
        _validate_coarse(ScaleOutConfig(**base, coarse_group=4, coarse_keep=0))


def test_coarse_identity_when_keep_covers_all_groups():
    # keep == n_grp means the screen keeps every group — the two-level serve
    # must be BIT-identical to the flat scan (pred AND maxsim), across every
    # vote collective and both representations
    run8("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv
    mesh = make_mesh((2, 4), ("data", "model"))
    for rep in ("unpacked", "packed"):
        for coll in ("psum", "psum_packed", "rs_ag"):
            cfg = scaleout.ScaleOutConfig(
                n_classes=128, dim=512, m_tx=3, n_rx_cores=8, batch=16,
                representation=rep, collective=coll, noise="exact",
                use_kernels=False)
            # c_core=16, gs=4 -> n_grp=4 == keep
            ccfg = dataclasses.replace(cfg, coarse_group=4, coarse_keep=4)
            protos_u = hv.random_hv(jax.random.PRNGKey(0), cfg.n_classes, cfg.dim)
            protos = hv.pack(protos_u) if cfg.packed else protos_u
            _, queries = scaleout.make_queries(
                jax.random.PRNGKey(1), cfg, protos_u, 4)
            state = phy.state_from_ber(
                jnp.full((cfg.n_rx_cores,), 0.05, jnp.float32), cfg.m_tx)
            key = jax.random.PRNGKey(2)
            pf, sf = scaleout.make_ota_serve(mesh, cfg)(protos, queries, state, key)
            pc, sc = scaleout.make_ota_serve(mesh, ccfg)(protos, queries, state, key)
            assert bool(jnp.all(pf == pc)), (rep, coll)
            assert bool(jnp.all(sf == sc)), (rep, coll)
    print("ok")
    """)


def test_coarse_real_screen_matches_flat():
    # keep < n_grp: a REAL screen (survivor rescore on a strict subset). At
    # d=1024 the summary-separation margin makes a screen miss astronomically
    # unlikely, so predictions still match the flat scan trial-for-trial on
    # the same RNG stream
    run8("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv
    mesh = make_mesh((2, 4), ("data", "model"))
    for rep in ("unpacked", "packed"):
        cfg = scaleout.ScaleOutConfig(
            n_classes=512, dim=1024, m_tx=3, n_rx_cores=8, batch=32,
            representation=rep, noise="exact", use_kernels=False)
        # c_core=64, gs=4 -> n_grp=16, keep=2: rescore 8 of 64 rows
        ccfg = dataclasses.replace(cfg, coarse_group=4, coarse_keep=2)
        protos_u = hv.random_hv(jax.random.PRNGKey(0), cfg.n_classes, cfg.dim)
        protos = hv.pack(protos_u) if cfg.packed else protos_u
        _, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos_u, 4)
        state = phy.state_from_ber(
            jnp.full((cfg.n_rx_cores,), 0.02, jnp.float32), cfg.m_tx)
        key = jax.random.PRNGKey(2)
        pf, _ = scaleout.make_ota_serve(mesh, cfg)(protos, queries, state, key)
        pc, _ = scaleout.make_ota_serve(mesh, ccfg)(protos, queries, state, key)
        assert bool(jnp.all(pf == pc)), rep
    print("ok")
    """)


def test_coarse_multitenant_identity():
    # the slots path flattens (slot, core) into the kernel's bank axis via
    # bank_rows — keep == n_grp must stay bit-identical to the flat mt serve,
    # with slots SHARING tenant rows to exercise the indirection
    run8("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv
    mesh = make_mesh((2, 4), ("data", "model"))
    S, T = 4, 2
    for rep in ("unpacked", "packed"):
        cfg = scaleout.ScaleOutConfig(
            n_classes=128, dim=512, m_tx=3, n_rx_cores=8, batch=8,
            representation=rep, noise="exact", use_kernels=False)
        ccfg = dataclasses.replace(cfg, coarse_group=4, coarse_keep=4)
        ps = [hv.random_hv(jax.random.fold_in(jax.random.PRNGKey(0), t),
                           cfg.n_classes, cfg.dim) for t in range(T)]
        store = jnp.stack([hv.pack(p) if cfg.packed else p for p in ps])
        qs, keys = [], []
        for s in range(S):
            _, q = scaleout.make_queries(
                jax.random.fold_in(jax.random.PRNGKey(1), s), cfg, ps[s % T], 4)
            qs.append(q)
            keys.append(jax.random.fold_in(jax.random.PRNGKey(2), s))
        rows = jnp.array([s % T for s in range(S)], jnp.int32)
        state = phy.state_from_ber(
            jnp.full((cfg.n_rx_cores,), 0.05, jnp.float32), cfg.m_tx)
        mt_f = scaleout.make_mt_ota_serve(mesh, cfg)
        mt_c = scaleout.make_mt_ota_serve(mesh, ccfg)
        pf, sf = mt_f(store, jnp.stack(qs), rows, state, jnp.stack(keys))
        pc, sc = mt_c(store, jnp.stack(qs), rows, state, jnp.stack(keys))
        assert bool(jnp.all(pf == pc)), rep
        assert bool(jnp.all(sf == sc)), rep
    print("ok")
    """)
