"""Multi-device tests (8 fake CPU devices via subprocess — the main test process
must keep seeing 1 device, so each case runs in its own python with XLA_FLAGS).
Covers: rules engine resolution, OTA scale-out serve vs oracle, majority
all-reduce == kernel majority, sign-majority training convergence.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


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


def test_rules_engine_resolution():
    # single-device, no subprocess needed
    from jax.sharding import PartitionSpec as P

    from conftest import make_test_mesh
    from repro.distributed.sharding import DEFAULT_RULES, spec_for_shape

    mesh = make_test_mesh((1, 1), ("data", "model"))
    # divisibility drop: 15 heads on a 1-wide model axis still resolves
    spec = spec_for_shape(("embed", "heads", "head_dim"), (960, 15, 64),
                          DEFAULT_RULES, mesh)
    assert spec == P(None, "model") or spec == P(None, "model", None) or spec == P()
    # each mesh axis used at most once
    spec2 = spec_for_shape(("batch", "seq", "embed"), (8, 128, 64),
                           dict(DEFAULT_RULES) | {"embed": "data"}, mesh)
    assert "data" not in (spec2[2:] if len(spec2) > 2 else ())


def test_scaleout_serve_matches_oracle():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv
    mesh = make_mesh((2, 4), ("data", "model"))
    for permuted in (False, True):
        cfg = scaleout.ScaleOutConfig(n_classes=40, dim=512, m_tx=3, n_rx_cores=8,
                                      batch=8, permuted=permuted, use_kernels=True)
        protos = hv.random_hv(jax.random.PRNGKey(0), cfg.n_classes, cfg.dim)
        classes, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos, 4)
        state = phy.state_from_ber(jnp.zeros((cfg.n_rx_cores,)), cfg.m_tx)
        pred, sim = scaleout.make_ota_serve(mesh, cfg)(protos, queries, state, jax.random.PRNGKey(2))
        rp, rs = scaleout.serve_reference(cfg, protos, queries)
        np.testing.assert_array_equal(np.asarray(pred), np.asarray(rp))
        np.testing.assert_allclose(np.asarray(sim), np.asarray(rs), rtol=1e-6)
        if permuted:
            np.testing.assert_array_equal(np.asarray(pred), np.asarray(classes))
    wp, _ = scaleout.make_wired_serve(mesh, cfg if not cfg.permuted else
        scaleout.ScaleOutConfig(n_classes=40, dim=512, m_tx=3, n_rx_cores=8, batch=8))(
        protos, queries, state, jax.random.PRNGKey(2))
    print("OK")
    """)


def test_packed_serve_prediction_identical():
    """The bit-packed fast path must be prediction-identical (and maxsim-equal)
    to the unpacked dataflow on the SAME RNG stream with nonzero per-core BER —
    baseline and permuted bundling x psum, psum_packed and rs_ag collectives
    (the guard-bit packed vote all-reduce produces the identical tally, so every
    collective mode must land on identical predictions)."""
    run8("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv
    mesh = make_mesh((2, 4), ("data", "model"))
    protos = hv.random_hv(jax.random.PRNGKey(0), 40, 512)
    state = phy.state_from_ber(jnp.full((8,), 0.05), 3)
    key = jax.random.PRNGKey(2)
    for permuted in (False, True):
        base = None
        for coll in ("psum", "psum_packed", "rs_ag"):
            cfg = scaleout.ScaleOutConfig(n_classes=40, dim=512, m_tx=3,
                                          n_rx_cores=8, batch=8, permuted=permuted,
                                          collective=coll, use_kernels=True)
            cfg_p = dataclasses.replace(cfg, representation="packed")
            classes, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos, 4)
            _, queries_p = scaleout.make_queries(jax.random.PRNGKey(1), cfg_p, protos, 4)
            pred, sim = scaleout.make_ota_serve(mesh, cfg)(protos, queries, state, key)
            pred_p, sim_p = scaleout.make_ota_serve(mesh, cfg_p)(
                hv.pack(protos), queries_p, state, key)
            np.testing.assert_array_equal(np.asarray(pred), np.asarray(pred_p))
            np.testing.assert_array_equal(np.asarray(sim), np.asarray(sim_p))
            if base is None:
                base = (np.asarray(pred), np.asarray(sim))
            else:  # identical across collective realizations too
                np.testing.assert_array_equal(np.asarray(pred), base[0])
                np.testing.assert_array_equal(np.asarray(sim), base[1])
    print("OK")
    """)


def test_packed_vote_allreduce_matches_int8_psum():
    """Property: the guard-bit packed vote all-reduce is bit-identical to the
    int8 psum tally across mesh sizes, e_per, random votes and the adversarial
    all-(+/-)e_per inputs that exercise the field-overflow guard; the packed
    reduce-scatter leg matches psum_scatter on every shard."""
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.distributed import collectives

    for s, e_per, d in [(8, 1, 512), (4, 2, 512), (4, 1, 100), (2, 5, 96),
                        (8, 3, 257), (1, 2, 64)]:
        mesh = make_mesh((s,), ("m",))
        key = jax.random.PRNGKey(s * 1000 + e_per * 10 + d)
        cases = [
            jax.random.randint(key, (s, 4, d), -e_per, e_per + 1).astype(jnp.int8),
            jnp.full((s, 4, d), e_per, jnp.int8),    # all votes saturate +
            jnp.full((s, 4, d), -e_per, jnp.int8),   # all votes saturate -
        ]
        for votes in cases:
            def body(v):
                ref = jax.lax.psum(v[0].astype(jnp.int32), "m")
                got = collectives.packed_vote_allreduce(
                    v[0], "m", group_size=s, e_per=e_per)
                return ref[None], got[None]
            fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("m"),
                                   out_specs=(P(), P()), axis_names={"m"},
                                   check_vma=False))
            ref, got = fn(votes)
            assert got.dtype == jnp.int32
            np.testing.assert_array_equal(
                np.asarray(ref), np.asarray(got), err_msg=str((s, e_per, d)))
            if d % s == 0:
                def body2(v):
                    ref = jax.lax.psum_scatter(v[0].astype(jnp.int32), "m",
                                               scatter_dimension=1, tiled=True)
                    got = collectives.packed_vote_psum_scatter(
                        v[0], "m", group_size=s, e_per=e_per)
                    return ref[None], got[None]
                fn2 = jax.jit(shard_map(body2, mesh=mesh, in_specs=P("m"),
                                        out_specs=(P("m"), P("m")),
                                        axis_names={"m"}, check_vma=False))
                ref, got = fn2(votes)
                np.testing.assert_array_equal(
                    np.asarray(ref), np.asarray(got), err_msg=str((s, e_per, d)))
    print("OK")
    """)


def test_packed_vote_allreduce_slot_aware_matches_int8_psum():
    """Property: ACTIVE-SLOT-AWARE guard bits (fields sized by the M live
    voters, per-column bias = that column's own live count) stay bit-identical
    to the int32 psum tally under the serve's abstaining-slot vote pattern —
    across mesh widths, e_per, M, random and saturating bits — and the
    slot-aware reduce-scatter leg matches psum_scatter on every shard."""
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.distributed import collectives

    for s, e_per, m_act, d in [(4, 1, 3, 512), (8, 1, 3, 512), (8, 2, 5, 512),
                               (4, 2, 7, 100), (2, 3, 4, 96), (8, 1, 8, 256)]:
        mesh = make_mesh((s,), ("m",))
        key = jax.random.PRNGKey(s * 1000 + e_per * 100 + m_act)
        cases = [
            jax.random.randint(key, (s, e_per, 4, d), 0, 2).astype(jnp.int8),
            jnp.ones((s, e_per, 4, d), jnp.int8),   # all live slots vote +1
            jnp.zeros((s, e_per, 4, d), jnp.int8),  # all live slots vote -1
        ]
        for bits in cases:
            def body(b):
                col = jax.lax.axis_index("m")
                gids = col * e_per + jnp.arange(e_per)
                active = (gids < m_act)[:, None, None]
                votes = jnp.sum(
                    jnp.where(active, 2 * b[0].astype(jnp.int8) - 1, 0), axis=0
                ).astype(jnp.int8)
                n_loc = jnp.clip(m_act - col * e_per, 0, e_per)
                ref = jax.lax.psum(votes.astype(jnp.int32), "m")
                got = collectives.packed_vote_allreduce(
                    votes, "m", group_size=s, e_per=e_per,
                    n_active=m_act, local_active=n_loc)
                outs = [ref[None], got[None]]
                fbits, k = collectives.vote_field_spec(
                    s, e_per, pow2_fields=True, n_active=m_act)
                if d % (k * s) == 0:
                    sref = jax.lax.psum_scatter(
                        votes.astype(jnp.int32), "m",
                        scatter_dimension=votes.ndim - 1, tiled=True)
                    sgot = collectives.packed_vote_psum_scatter(
                        votes, "m", group_size=s, e_per=e_per,
                        n_active=m_act, local_active=n_loc)
                    outs += [sref[None], sgot[None]]
                return tuple(outs)
            n_out = 4 if d % (collectives.vote_field_spec(
                s, e_per, pow2_fields=True, n_active=m_act)[1] * s) == 0 else 2
            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=P("m"),
                out_specs=(P(), P()) if n_out == 2 else (P(), P(), P("m"), P("m")),
                axis_names={"m"}, check_vma=False))
            outs = fn(bits)
            assert outs[1].dtype == jnp.int32
            np.testing.assert_array_equal(
                np.asarray(outs[0]), np.asarray(outs[1]),
                err_msg=str((s, e_per, m_act, d)))
            if n_out == 4:
                np.testing.assert_array_equal(
                    np.asarray(outs[2]), np.asarray(outs[3]),
                    err_msg=str((s, e_per, m_act, d)))
    print("OK")
    """)


def test_symbol_serve_matches_host_oracle_on_mesh():
    """channel="symbol" on the 2x4 mesh: the sharded combo psum + per-core
    constellation/AWGN/decision decode equals a host re-derivation from the
    same ChannelState bit-for-bit (per data row r: fold_in(key, r), per global
    core g: fold_in(., g)) — the physical tier is mesh-layout invariant."""
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import em, hypervector as hv, ota, scaleout
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = scaleout.ScaleOutConfig(n_classes=32, dim=512, m_tx=3, n_rx_cores=8,
                                  batch=8, channel="symbol", use_kernels=True)
    h = em.channel_matrix(em.PackageGeometry(), cfg.m_tx, cfg.n_rx_cores)
    n0 = ota.default_n0(h)
    state = phy.state_from_ota(ota.optimize_phases_exhaustive(h, n0), h)
    protos = hv.random_hv(jax.random.PRNGKey(0), cfg.n_classes, cfg.dim)
    _, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos, 4)
    key = jax.random.PRNGKey(2)
    pred, sim = scaleout.make_ota_serve(mesh, cfg)(protos, queries, state, key)

    q_act = queries.reshape(cfg.batch, -1, cfg.dim)[:, : cfg.m_tx]
    combo = phy.combo_index(q_act, axis=1)                       # [B, d]
    c_core = cfg.n_classes // cfg.n_rx_cores
    b_l = cfg.batch // 2
    rows = []
    for r in range(2):                                           # data rows
        kq = jax.random.fold_in(key, r)
        cb = combo[r * b_l:(r + 1) * b_l]
        sims = []
        for g in range(cfg.n_rx_cores):                          # global cores
            q_g = phy.awgn_decide(jax.random.fold_in(kq, g),
                                  state.symbols[g][cb], state.c0[g],
                                  state.c1[g], state.n0)
            p_g = protos[g * c_core:(g + 1) * c_core]
            sims.append(jnp.einsum("bd,cd->bc",
                                   2.0 * q_g.astype(jnp.float32) - 1,
                                   2.0 * p_g.astype(jnp.float32) - 1))
        rows.append(jnp.concatenate(sims, axis=1))               # [B_l, C]
    sims = jnp.concatenate(rows, axis=0)                         # [B, C]
    np.testing.assert_array_equal(np.asarray(pred),
                                  np.asarray(jnp.argmax(sims, -1)))
    np.testing.assert_allclose(
        np.asarray(sim),
        np.asarray(jnp.max(sims, -1) / (2.0 * cfg.dim) + 0.5), rtol=1e-6)
    # packed symbol serve (decode bits -> pack -> fused top-1): identical
    import dataclasses
    cfg_p = dataclasses.replace(cfg, representation="packed")
    _, queries_p = scaleout.make_queries(jax.random.PRNGKey(1), cfg_p, protos, 4)
    pred_p, sim_p = scaleout.make_ota_serve(mesh, cfg_p)(
        hv.pack(protos), queries_p, state, key)
    np.testing.assert_array_equal(np.asarray(pred), np.asarray(pred_p))
    np.testing.assert_array_equal(np.asarray(sim), np.asarray(sim_p))
    print("OK")
    """)


def test_packed_wired_and_train_match_unpacked():
    """Wired-baseline serve and one-shot HDC train agree across representations;
    the packed bitplane noise mode also runs and matches the oracle at BER 0."""
    run8("""
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = scaleout.ScaleOutConfig(n_classes=40, dim=512, m_tx=3, n_rx_cores=8,
                                  batch=8, use_kernels=True)
    cfg_p = dataclasses.replace(cfg, representation="packed")
    protos = hv.random_hv(jax.random.PRNGKey(0), cfg.n_classes, cfg.dim)
    classes, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos, 4)
    _, queries_p = scaleout.make_queries(jax.random.PRNGKey(1), cfg_p, protos, 4)
    state = phy.state_from_ber(jnp.zeros((cfg.n_rx_cores,)), cfg.m_tx)
    key = jax.random.PRNGKey(2)
    wp, ws = scaleout.make_wired_serve(mesh, cfg)(protos, queries, state, key)
    wpp, wsp = scaleout.make_wired_serve(mesh, cfg_p)(hv.pack(protos), queries_p, state, key)
    np.testing.assert_array_equal(np.asarray(wp), np.asarray(wpp))
    np.testing.assert_array_equal(np.asarray(ws), np.asarray(wsp))
    labels = jnp.arange(cfg.batch, dtype=jnp.int32) % cfg.n_classes
    tr = scaleout.make_hdc_train(mesh, cfg)(protos[labels], labels)
    tr_p = scaleout.make_hdc_train(mesh, cfg_p)(hv.pack(protos[labels]), labels)
    np.testing.assert_array_equal(np.asarray(tr), np.asarray(hv.unpack(tr_p, cfg.dim)))
    # bitplane noise mode: valid program; at BER 0 it matches the oracle exactly
    cfg_b = dataclasses.replace(cfg_p, noise="bitplane")
    pb, _ = scaleout.make_ota_serve(mesh, cfg_b)(hv.pack(protos), queries_p, state, key)
    rp, _ = scaleout.serve_reference(cfg_b, hv.pack(protos), queries_p)
    np.testing.assert_array_equal(np.asarray(pb), np.asarray(rp))
    print("OK")
    """)


def test_vote_field_spec_values():
    # single-device, no subprocess needed
    from repro.distributed.collectives import vote_field_spec

    # paper operating point on pod1: S=4 model axis, e_per=1 -> span 8 ->
    # 4-bit fields, 8 per uint32 lane (the ~2x wire cut vs int8 votes)
    assert vote_field_spec(4, 1) == (4, 8)
    assert vote_field_spec(16, 1) == (6, 5)
    assert vote_field_spec(16, 1, pow2_fields=True) == (6, 4)
    assert vote_field_spec(1, 1) == (2, 16)
    assert vote_field_spec(8, 3) == (6, 5)
    # active-slot-aware: the tally span is 2*M regardless of the mesh width —
    # at S=16/M=3 that's 3-bit fields, 10 per lane (~2.5x vs int8 votes) where
    # slot-blind guards gave 6-bit/5 (1.25x) — ROADMAP's named next wire step
    assert vote_field_spec(16, 1, n_active=3) == (3, 10)
    assert vote_field_spec(16, 1, pow2_fields=True, n_active=3) == (3, 8)
    assert vote_field_spec(4, 1, n_active=3) == (3, 10)
    assert vote_field_spec(4, 2, n_active=3) == (3, 10)  # e_per-split slots
    assert vote_field_spec(16, 1, n_active=16) == vote_field_spec(16, 1)


def test_majority_allreduce_equals_kernel():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.distributed import collectives
    from repro.kernels.majority.ref import majority_bundle_ref
    mesh = make_mesh((8,), ("tx",))
    bits = jax.random.bernoulli(jax.random.PRNGKey(0), 0.5, (7, 64, 128)).astype(jnp.uint8)
    # 7 active senders on 8 slots: slot 7 abstains by majority_allreduce over
    # shards that carry one hv each -> emulate with shard over leading axis 8
    bits8 = jnp.concatenate([bits, jnp.zeros((1, 64, 128), jnp.uint8)])
    def body(shard):
        active = jax.lax.axis_index("tx") < 7
        votes = jnp.where(active, 2 * shard[0].astype(jnp.int8) - 1, 0)
        tally = jax.lax.psum(votes, "tx")
        return (tally > 0).astype(jnp.uint8)
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P("tx"), out_specs=P(),
                            axis_names={"tx"}, check_vma=False))(bits8)
    ref = majority_bundle_ref(bits)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    print("OK")
    """)


def test_ota_noise_per_rx_independent():
    run8("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.distributed import collectives
    mesh = make_mesh((8,), ("rx",))
    bits = jnp.zeros((4096,), jnp.uint8)
    def body(key):
        return collectives.ota_noise(key, bits, 0.1, axis_name="rx")[None]
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=P(), out_specs=P("rx"),
                            axis_names={"rx"}, check_vma=False))(jax.random.PRNGKey(0))
    rates = np.asarray(jnp.mean(out.astype(jnp.float32), axis=-1))
    assert ((rates > 0.07) & (rates < 0.13)).all(), rates
    # copies differ across receivers
    assert len({tuple(np.asarray(r)) for r in out}) == 8
    print("OK")
    """)


def test_sign_majority_training_converges():
    run8("""
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.compat import make_mesh
    from repro.models import get_model
    from repro.train.loop import build_train_fns
    from repro.train.optimizer import OptConfig
    from repro.data import SyntheticLM, DataConfig
    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = configs.get_smoke("tinyllama_1_1b")
    model = get_model(cfg)
    pipe = SyntheticLM(DataConfig(vocab=cfg.vocab, seq=128, global_batch=8))
    key = jax.random.PRNGKey(0)
    opt = OptConfig(kind="sign_majority", lr=3e-4, warmup=5, total_steps=40)
    fns = build_train_fns(model, mesh, opt, ota_ber=0.01)
    params, opt_state = fns.init(key)
    params = jax.device_put(params, fns.param_shardings)
    opt_state = jax.device_put(opt_state, fns.opt_shardings)
    losses = []
    for step in range(20):
        params, opt_state, m = fns.step(params, opt_state, pipe.batch(step),
                                        jax.random.fold_in(key, step))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.4, losses
    print("OK", losses[0], losses[-1])
    """)


def test_sign_majority_step_on_model_sharded_mesh():
    """The dp axes are manual inside the sign_majority body: activation
    constraints there must not name them (a (2, 4) mesh with smollm's
    batch-constrained layers used to be refused at trace time)."""
    out = run8("""
    import math
    import jax
    from repro import configs
    from repro.compat import make_mesh
    from repro.models import get_model
    from repro.train.loop import build_train_fns
    from repro.train.optimizer import OptConfig
    from repro.data import SyntheticLM, DataConfig
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    pipe = SyntheticLM(DataConfig(vocab=cfg.vocab, seq=64, global_batch=8))
    key = jax.random.PRNGKey(0)
    opt = OptConfig(kind="sign_majority", lr=1e-3, warmup=2, total_steps=4)
    fns = build_train_fns(model, mesh, opt, ota_ber=0.01)
    params, opt_state = fns.init(key)
    params = jax.device_put(params, fns.param_shardings)
    opt_state = jax.device_put(opt_state, fns.opt_shardings)
    for step in range(2):
        params, opt_state, m = fns.step(params, opt_state, pipe.batch(step),
                                        jax.random.fold_in(key, step))
    assert math.isfinite(float(m["loss"]))
    print("OK")
    """)
    assert "OK" in out


def test_dense_dp_equals_single_device():
    """Same seeds: 8-device DP adamw training == 1-device training."""
    code_tpl = """
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.compat import make_mesh
    from repro.models import get_model
    from repro.train.loop import build_train_fns
    from repro.train.optimizer import OptConfig
    from repro.data import SyntheticLM, DataConfig
    mesh = make_mesh({mesh_shape}, ("data", "model"))
    cfg = configs.get_smoke("smollm_360m")
    model = get_model(cfg)
    pipe = SyntheticLM(DataConfig(vocab=cfg.vocab, seq=64, global_batch=8))
    key = jax.random.PRNGKey(0)
    fns = build_train_fns(model, mesh, OptConfig(lr=1e-3, warmup=2, total_steps=10))
    params, opt_state = fns.init(key)
    params = jax.device_put(params, fns.param_shardings)
    opt_state = jax.device_put(opt_state, fns.opt_shardings)
    for step in range(5):
        params, opt_state, m = fns.step(params, opt_state, pipe.batch(step), key)
    print(float(m["loss"]))
    """
    import textwrap as tw
    out8 = run8(code_tpl.format(mesh_shape="(4, 2)"))
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    r1 = subprocess.run(
        [sys.executable, "-c", tw.dedent(code_tpl.format(mesh_shape="(1, 1)"))],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r1.returncode == 0, r1.stderr[-2000:]
    l8, l1 = float(out8.strip().splitlines()[-1]), float(r1.stdout.strip().splitlines()[-1])
    assert abs(l8 - l1) < 5e-3, (l8, l1)
