"""HDC-as-a-service: multi-tenant slot-batched serving must be bit-identical
per slot to its one-slot view `make_ota_serve` (same RNG stream), tenant
lifecycle (admit -> serve -> evict -> re-admit) must be prediction-identical to
a fresh one-slot serve across representations and channels, and the scheduler
must drain with ceil(R / slots) steps."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_test_mesh
from repro import faults as faultlib, phy
from repro.core import classifier, hypervector as hv, scaleout
from repro.serving import HDCEngine, HDCScheduler

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _cfg(**kw):
    base = dict(n_classes=40, dim=512, m_tx=3, n_rx_cores=4, batch=8,
                use_kernels=False, noise="exact")
    base.update(kw)
    return scaleout.ScaleOutConfig(**base)


def _books(cfg, n):
    tcfg = classifier.HDCTaskConfig(n_classes=cfg.n_classes, dim=cfg.dim)
    return classifier.make_tenant_codebooks(jax.random.PRNGKey(0), tcfg, n)


def _tenant_protos(cfg, book):
    return hv.pack(book) if cfg.packed else book


@pytest.mark.parametrize("form", ["plain", "process", "faults",
                                  "process+faults"])
def test_mt_serve_bit_identical_per_slot(form):
    """Each slot of one multi-tenant launch == the one-slot view
    (`make_ota_serve`) of that slot's queries against its tenant's codebook
    with the slot's own key — including slots sharing a tenant and nonzero
    per-core BER — in every form the one serve builder assembles: plain,
    with a channel process, with faults, and with both (whose evolved states
    must agree too)."""
    mesh = make_test_mesh((1, 1), ("data", "model"))
    kw = {}
    if "process" in form:
        kw["process"] = phy.StaticProcess()
    if "faults" in form:
        kw["faults"] = faultlib.StaticFaults()
    for rep in ("unpacked", "packed"):
        for permuted in (False, True):
            cfg = _cfg(permuted=permuted, representation=rep)
            books = _books(cfg, 3)
            state = phy.state_from_ber(jnp.full((cfg.n_rx_cores,), 0.05), cfg.m_tx)
            extra = ()
            if "process" in kw:
                state = kw["process"].init(state)
                extra += (jax.random.PRNGKey(7),)
            if "faults" in kw:
                extra += (faultlib.healthy_for(cfg, 1), jax.random.PRNGKey(9))
            serve = scaleout.make_ota_serve(mesh, cfg, **kw)
            mt = scaleout.make_mt_ota_serve(mesh, cfg, **kw)
            rows = jnp.array([2, 0, 2], jnp.int32)  # slots 0 and 2 share tenant 2
            keys = jnp.stack([jax.random.PRNGKey(100 + s) for s in range(3)])
            store = jnp.stack([_tenant_protos(cfg, b) for b in books])
            qs, want_p, want_s, want_ev = [], [], [], []
            for s in range(3):
                book = books[int(rows[s])]
                _, q = scaleout.make_queries(jax.random.PRNGKey(50 + s), cfg, book, 1)
                qs.append(q)
                pr, si, *ev = serve(_tenant_protos(cfg, book), q, state,
                                    keys[s], *extra)
                want_p.append(np.asarray(pr))
                want_s.append(np.asarray(si))
                want_ev.append(ev)
            pred, sim, *got_ev = mt(store, jnp.stack(qs), rows, state, keys,
                                    *extra)
            np.testing.assert_array_equal(np.asarray(pred), np.stack(want_p))
            np.testing.assert_array_equal(np.asarray(sim), np.stack(want_s))
            assert len(got_ev) == len(kw)
            for ev in want_ev:
                jax.tree.map(np.testing.assert_array_equal, got_ev, ev)


@pytest.mark.parametrize("rep", ["unpacked", "packed"])
@pytest.mark.parametrize("channel", ["bsc", "symbol"])
def test_tenant_lifecycle_identity(rep, channel):
    """admit -> serve -> evict -> re-admit (lands on a DIFFERENT store row)
    stays prediction-identical to a fresh one-slot serve, for every
    representation x channel tier."""
    cfg = _cfg(representation=rep, channel=channel)
    mesh = make_test_mesh((1, 1), ("data", "model"))
    if channel == "symbol":
        state = scaleout.precharacterize_state(cfg)
    else:
        state = phy.state_from_ber(jnp.full((cfg.n_rx_cores,), 0.05), cfg.m_tx)
    books = _books(cfg, 2)
    serve = scaleout.make_ota_serve(mesh, cfg)
    eng = HDCEngine(mesh, cfg, state, num_slots=2, max_tenants=4)
    sched = HDCScheduler(eng)
    for t in range(2):
        eng.registry.onboard(t, _tenant_protos(cfg, books[t]))
    row0_before = eng.registry.rows[0]

    def check(tenant, seed):
        _, q = scaleout.make_queries(jax.random.PRNGKey(seed), cfg, books[tenant], 1)
        key = jax.random.PRNGKey(1000 + seed)
        rid = sched.submit(tenant, q, key=key)
        sched.run(timeout=600)
        got = sched.poll(rid)
        pr, si = serve(_tenant_protos(cfg, books[tenant]), q, state, key)
        np.testing.assert_array_equal(got.pred, np.asarray(pr))
        np.testing.assert_array_equal(got.maxsim, np.asarray(si))

    check(0, 7)
    check(1, 8)
    eng.registry.evict(0)
    eng.registry.onboard(2, _tenant_protos(cfg, books[0]))  # claims the freed row
    eng.registry.onboard(0, _tenant_protos(cfg, books[0]))  # re-admit: new row
    assert eng.registry.rows[0] != row0_before
    check(0, 9)  # prediction identity is row-independent


def test_scheduler_interleaves_tenants_and_drains():
    """R requests over S slots drain in ceil(R/S) steps with tenants mixed in
    one launch; registry/scheduler guard rails raise on misuse."""
    cfg = _cfg()
    mesh = make_test_mesh((1, 1), ("data", "model"))
    state = phy.state_from_ber(jnp.zeros((cfg.n_rx_cores,)), cfg.m_tx)
    books = _books(cfg, 2)
    eng = HDCEngine(mesh, cfg, state, num_slots=2, max_tenants=2)
    sched = HDCScheduler(eng)
    eng.registry.onboard("a", books[0])
    eng.registry.onboard("b", hv.pack(books[1]) if cfg.packed else books[1])
    _, q = scaleout.make_queries(jax.random.PRNGKey(3), cfg, books[0], 1)
    rids = [sched.submit("a" if i % 2 == 0 else "b", q) for i in range(5)]
    res = sched.run(timeout=600)
    assert len(res) == 5 and sched.steps == 3  # ceil(5/2)
    assert all(sched.poll(r).pred.shape == (cfg.batch,) for r in rids)
    # guard rails
    with pytest.raises(ValueError, match="already onboarded"):
        eng.registry.onboard("a", books[0])
    with pytest.raises(ValueError, match="registry full"):
        eng.registry.onboard("c", books[0])
    with pytest.raises(ValueError, match="not onboarded"):
        sched.submit("nope", q)
    with pytest.raises(ValueError, match="must be"):
        eng.registry.evict("a")
        eng.registry.onboard("a", books[0][:10])
    # a request queued for a tenant evicted before admission must fail loudly
    eng.registry.onboard("a", books[0])
    rid = sched.submit("a", q)
    eng.registry.evict("a")
    with pytest.raises(RuntimeError, match="evicted"):
        sched.run(timeout=600)


def test_mt_serve_multidevice_packed_collectives():
    """On a real 2x4 mesh the slot-flattened wire path (guard-bit packed vote
    all-reduce, packed reduce-scatter + all-gather) must stay bit-identical
    per slot to the one-slot serve — the collectives see [N*B] rows."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import scaleout, hypervector as hv, classifier
    mesh = make_mesh((2, 4), ("data", "model"))
    tcfg = classifier.HDCTaskConfig(n_classes=40, dim=512)
    books = classifier.make_tenant_codebooks(jax.random.PRNGKey(0), tcfg, 2)
    state = phy.state_from_ber(jnp.full((8,), 0.05), 3)
    for coll in ("psum_packed", "rs_ag"):
        cfg = scaleout.ScaleOutConfig(
            n_classes=40, dim=512, m_tx=3, n_rx_cores=8, batch=8,
            collective=coll, use_kernels=True, representation="packed",
            noise="exact")
        serve = scaleout.make_ota_serve(mesh, cfg)
        mt = scaleout.make_mt_ota_serve(mesh, cfg)
        rows = jnp.array([1, 0, 1], jnp.int32)
        keys = jnp.stack([jax.random.PRNGKey(100 + s) for s in range(3)])
        store = jnp.stack([hv.pack(b) for b in books])
        qs, preds, sims = [], [], []
        for s in range(3):
            book = books[int(rows[s])]
            _, q = scaleout.make_queries(jax.random.PRNGKey(50 + s), cfg, book, 4)
            qs.append(q)
            pr, si = serve(hv.pack(book), q, state, keys[s])
            preds.append(np.asarray(pr)); sims.append(np.asarray(si))
        pred, sim = mt(store, jnp.stack(qs), rows, state, keys)
        np.testing.assert_array_equal(np.asarray(pred), np.stack(preds))
        np.testing.assert_array_equal(np.asarray(sim), np.stack(sims))
    print("OK")
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"


def test_engine_ring_on_four_chips_equals_one_chip_and_the_reference():
    """The ``whype4`` layout at toy sizes: the HDCEngine ring on a (1, 4)
    mesh (a quarter of the cores and classes a chip, the vote tally
    exchanged between the chips) gives, trial by trial, the same
    (pred, maxsim) as on a (1, 1) mesh for the same seeded banks, payloads
    and keys, and both equal the benchmark's plain reference: the four
    chips' shares together make the one-chip answer."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    bench = os.path.join(os.path.dirname(SRC), "bench")
    code = f"""
    import sys
    sys.path.insert(0, {bench!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import deploy, reference
    from repro import phy
    from repro.compat import make_mesh
    from repro.serving import HDCEngine
    s = dict(n_classes=256, dim=256, m_tx=3, n_rx_cores=64, snr_db=7.0,
             representation="packed", collective="psum_packed", channel="bsc",
             noise="bitplane", noise_planes=16, tenants=4, slots=8,
             trials_per_request=8, payloads_per_tenant=2)
    seed = 2**32 + 1505
    cfg = deploy.service_config(s)
    banks = deploy.make_banks(seed, s)
    ber = jnp.linspace(0.01, 0.08, s["n_rx_cores"])
    state = phy.state_from_ber(ber, s["m_tx"])
    rng = np.random.default_rng(7)
    keys = [k for k in rng.integers(0, 2**32, (8, 2), dtype=np.uint32)]
    tenants = [3, 0, 1, 3, 2, 0, 1, 2]
    out = {{}}
    for model in (1, 4):
        mesh = make_mesh((1, model), ("data", "model"),
                         devices=jax.devices()[:model])
        eng = HDCEngine(mesh, cfg, jax.device_put(state, NamedSharding(mesh, P())),
                        num_slots=8, max_tenants=4)
        for t in range(4):
            eng.registry.onboard(t, banks[t])
        classes, queries = deploy.make_payloads(seed, s, banks, model)
        st = eng.admit_many(eng.init_state(),
                            [queries[t, i % 2] for i, t in enumerate(tenants)],
                            tenants, list(range(8)), keys)
        _, (pred, sim) = eng.step(eng.params, st)
        out[model] = np.asarray(pred), np.asarray(sim)
    np.testing.assert_array_equal(out[4][0], out[1][0])
    np.testing.assert_array_equal(out[4][1], out[1][1])
    thr = jnp.asarray(reference.flip_threshold(np.asarray(ber), 16))
    for i, t in enumerate(tenants):
        ref_p, ref_s = reference.serve(
            banks[t], classes[t, i % 2], jnp.asarray(keys[i]), thr,
            n_cores=64, planes=16, chunk=16)
        np.testing.assert_array_equal(out[4][0][i], np.asarray(ref_p))
        np.testing.assert_array_equal(out[4][1][i], np.asarray(ref_s))
    assert len(set(out[4][0].ravel().tolist())) > 8
    print("OK")
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"


def test_engine_ring_with_the_rx_kernel_equals_the_reference():
    """The HDCEngine ring step with the RX fan-out kernel on (packed, bsc,
    16-plane bitplane, one chip) answers trial by trial as the benchmark's
    plain reference does, and as the same ring with every kernel off; the
    bitplane kernel is the one that built the copies."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    bench = os.path.join(os.path.dirname(SRC), "bench")
    code = f"""
    import dataclasses, sys
    sys.path.insert(0, {bench!r})
    import jax, jax.numpy as jnp, numpy as np
    import deploy, reference
    from repro import phy
    from repro.compat import make_mesh
    from repro.kernels.bsc import ops as bsc_ops
    from repro.serving import HDCEngine
    launches = []
    kernel = bsc_ops.bsc_bitplane_pallas
    def counted(*a, **kw):
        launches.append(kw["sb"])
        return kernel(*a, **kw)
    bsc_ops.bsc_bitplane_pallas = counted
    s = dict(n_classes=512, dim=512, m_tx=3, n_rx_cores=32, snr_db=7.0,
             representation="packed", collective="psum_packed", channel="bsc",
             noise="bitplane", noise_planes=16, tenants=3, slots=4,
             trials_per_request=16, payloads_per_tenant=1)
    seed = 2**33 + 17
    cfg = deploy.service_config(s)
    assert cfg.use_kernels
    banks = deploy.make_banks(seed, s)
    ber = jnp.linspace(0.0, 0.3, s["n_rx_cores"])
    state = phy.state_from_ber(ber, s["m_tx"])
    keys = list(np.random.default_rng(11).integers(0, 2**32, (4, 2),
                                                   dtype=np.uint32))
    tenants = [2, 0, 2, 1]
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    classes, queries = deploy.make_payloads(seed, s, banks, 1)
    out = []
    for c in (cfg, dataclasses.replace(cfg, use_kernels=False)):
        eng = HDCEngine(mesh, c, state, num_slots=4, max_tenants=3)
        for t in range(3):
            eng.registry.onboard(t, banks[t])
        st = eng.admit_many(eng.init_state(), [queries[t, 0] for t in tenants],
                            tenants, list(range(4)), keys)
        _, (pred, sim) = eng.step(eng.params, st)
        out.append((np.asarray(pred), np.asarray(sim)))
    assert launches, "the ring did not run the bitplane kernel"
    np.testing.assert_array_equal(out[0][0], out[1][0])
    np.testing.assert_array_equal(out[0][1], out[1][1])
    thr = jnp.asarray(reference.flip_threshold(np.asarray(ber), 16))
    for i, t in enumerate(tenants):
        ref_p, ref_s = reference.serve(
            banks[t], classes[t, 0], jnp.asarray(keys[i]), thr,
            n_cores=32, planes=16, chunk=16)
        np.testing.assert_array_equal(out[0][0][i], np.asarray(ref_p))
        np.testing.assert_array_equal(out[0][1][i], np.asarray(ref_s))
    assert len(set(out[0][0].ravel().tolist())) > 8
    print("OK")
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-4000:]}"


# ---------------------------------------------------------------------------
# living channels: adaptive engine + link controller
# ---------------------------------------------------------------------------

def test_adaptive_engine_static_process_is_bit_identical():
    """AdaptiveHDCEngine under StaticProcess must serve bit-identically to the
    plain HDCEngine — the controller idles (no guard trips) and the process
    tick is a pure time increment."""
    from repro.serving import AdaptiveHDCEngine, LinkControllerConfig

    cfg = _cfg(channel="symbol")
    mesh = make_test_mesh((1, 1), ("data", "model"))
    state = scaleout.precharacterize_state(cfg)
    books = _books(cfg, 2)
    engines = (
        HDCEngine(mesh, cfg, state, num_slots=2, max_tenants=2),
        AdaptiveHDCEngine(
            mesh, cfg, state, process=phy.StaticProcess(guard_dims=16),
            num_slots=2, max_tenants=2,
            controller=LinkControllerConfig(band_kwargs={"cap": 0.05})),
    )
    results = []
    for eng in engines:
        sched = HDCScheduler(eng)
        for t in range(2):
            eng.registry.onboard(t, books[t])
        rids = []
        for r in range(4):
            _, q = scaleout.make_queries(jax.random.PRNGKey(50 + r), cfg,
                                         books[r % 2], 1)
            rids.append(sched.submit(r % 2, q, key=jax.random.PRNGKey(100 + r)))
        sched.run(timeout=600)
        results.append([sched.results[r].pred for r in rids])
    for a, b in zip(*results):
        np.testing.assert_array_equal(a, b)
    adaptive = engines[1]
    assert int(adaptive.pstate.t) == 2        # 4 requests / 2 slots = 2 steps
    assert adaptive.controller.trace == []    # nothing tripped


def test_link_controller_hysteresis_no_flap():
    """Quarantine rides a bad/good re-fit hysteresis: persistently bad re-fits
    quarantine a core ONCE (no flapping while it stays bad), recovery releases
    it once, and the fleet m_drop/m_restore fires exactly once per direction."""
    from repro.serving import LinkController, LinkControllerConfig

    cfg = _cfg(channel="symbol")
    state = scaleout.precharacterize_state(cfg)
    proc = phy.StaticProcess(guard_dims=8)
    p = proc.init(state)
    n = state.n_rx
    cc = LinkControllerConfig(patience=1, quarantine_after=2, release_after=2,
                              drop_frac=0.5, band_kwargs={"cap": 0.05})
    ctl = LinkController(cc, p)
    hi = jnp.full((n,), 0.45, jnp.float32)
    junk = jax.random.normal(jax.random.PRNGKey(0), p.chan.symbols.shape,
                             jnp.float32).astype(jnp.complex64)
    p_bad = dataclasses.replace(
        p, chan=dataclasses.replace(p.chan, symbols=junk), est=hi)
    p_good = dataclasses.replace(p, est=hi)

    for _ in range(6):                        # persistently bad link
        ctl.act(p_bad)
    acts = [e["action"] for e in ctl.trace]
    assert acts.count("quarantine") == 1 and acts.count("release") == 0
    assert acts.count("m_drop") == 1 and acts.count("m_restore") == 0
    assert ctl.quarantined.all() and ctl.degraded

    for _ in range(6):                        # link recovers
        ctl.act(p_good)
    acts = [e["action"] for e in ctl.trace]
    assert acts.count("quarantine") == 1 and acts.count("release") == 1
    assert acts.count("m_drop") == 1 and acts.count("m_restore") == 1
    assert not ctl.quarantined.any() and not ctl.degraded


def test_adaptive_engine_fleet_switch_reuses_variants():
    """On a votes-wire tier the fleet degrade path (quarantine fraction over
    drop_frac) swaps to the prebuilt (m_floor, collective) serve variant —
    compiled once, reused across subsequent switches, serving uninterrupted."""
    from repro.serving import AdaptiveHDCEngine, LinkControllerConfig

    cfg = _cfg(channel="bsc")
    mesh = make_test_mesh((1, 1), ("data", "model"))
    state = scaleout.precharacterize_state(cfg)     # symbol-valid state: the
    #   guard monitor + re-fit run on physics while bsc serves off chan.ber
    books = _books(cfg, 1)
    eng = AdaptiveHDCEngine(
        mesh, cfg, state,
        process=phy.PhaseDriftProcess(sigma=0.5, alpha=0.7, guard_dims=64),
        num_slots=1, max_tenants=1,
        controller=LinkControllerConfig(
            patience=1, quarantine_ber=-1.0, quarantine_after=1,
            release_ber=-1.0, drop_frac=0.25, band_kwargs={"cap": 0.02}))
    sched = HDCScheduler(eng)
    eng.registry.onboard(0, books[0])
    for r in range(8):
        _, q = scaleout.make_queries(jax.random.PRNGKey(50 + r), cfg,
                                     books[0], 1)
        sched.submit(0, q, key=jax.random.PRNGKey(100 + r))
        sched.run(timeout=600)
    acts = [e["action"] for e in eng.controller.trace]
    assert "quarantine" in acts and "m_drop" in acts and "link_mode" in acts
    assert sorted(eng._variants) == [(1, "psum"), (3, "psum")]
    assert len(sched.results) == 8            # serving never stalled


def test_link_controller_quarantine_and_release_thresholds_exact():
    """The hysteresis counters are exact: quarantine fires on the
    quarantine_after-th consecutive bad re-fit and not one earlier; release
    fires on the release_after-th consecutive good re-fit and not one
    earlier."""
    from repro.serving import LinkController, LinkControllerConfig

    cfg = _cfg(channel="symbol")
    state = scaleout.precharacterize_state(cfg)
    p = phy.StaticProcess(guard_dims=8).init(state)
    n = state.n_rx
    cc = LinkControllerConfig(patience=1, quarantine_after=3, release_after=2,
                              drop_frac=2.0, band_kwargs={"cap": 0.05})
    ctl = LinkController(cc, p)
    hi = jnp.full((n,), 0.45, jnp.float32)
    junk = jax.random.normal(jax.random.PRNGKey(0), p.chan.symbols.shape,
                             jnp.float32).astype(jnp.complex64)
    p_bad = dataclasses.replace(
        p, chan=dataclasses.replace(p.chan, symbols=junk), est=hi)
    p_good = dataclasses.replace(p, est=hi)

    for k in range(cc.quarantine_after - 1):
        ctl.act(p_bad)
        assert not ctl.quarantined.any(), k  # one short of the threshold
    ctl.act(p_bad)
    assert ctl.quarantined.all()             # exactly at quarantine_after

    for k in range(cc.release_after - 1):
        ctl.act(p_good)
        assert ctl.quarantined.all(), k      # one short of the threshold
    ctl.act(p_good)
    assert not ctl.quarantined.any()         # exactly at release_after
    assert not ctl.degraded                  # drop_frac=2.0 never binds
    assert not any(e["action"] == "m_drop" for e in ctl.trace)


def test_link_controller_drop_frac_boundary_is_inclusive():
    """The fleet degrade threshold is frac >= drop_frac: quarantining exactly
    one of n cores trips m_drop at drop_frac == 1/n and stays below it at any
    larger threshold — pinning the boundary so a config sized to 'degrade
    when a quarter is dark' fires on exactly a quarter."""
    from repro.serving import LinkController, LinkControllerConfig

    cfg = _cfg(channel="symbol")
    state = scaleout.precharacterize_state(cfg)
    p = phy.StaticProcess(guard_dims=8).init(state)
    n = state.n_rx
    junk = jax.random.normal(jax.random.PRNGKey(0), p.chan.symbols.shape,
                             jnp.float32).astype(jnp.complex64)
    # only row 0 is out of band: est 0.45 vs a <=0.05 band; the rest sit at 0
    est = jnp.zeros((n,), jnp.float32).at[0].set(0.45)
    p_bad0 = dataclasses.replace(
        p, chan=dataclasses.replace(p.chan, symbols=junk), est=est)
    for drop_frac, fires in ((1.0 / n, True), (1.0 / n + 0.01, False)):
        cc = LinkControllerConfig(patience=1, quarantine_after=1,
                                  drop_frac=drop_frac,
                                  band_kwargs={"cap": 0.05})
        ctl = LinkController(cc, p)
        ctl.act(p_bad0)
        assert ctl.quarantined.tolist() == [True] + [False] * (n - 1)
        assert ctl.degraded == fires, drop_frac
        assert any(e["action"] == "m_drop" for e in ctl.trace) == fires


def test_link_controller_no_flap_under_oscillating_refits():
    """A link whose re-fit quality oscillates bad/good around the split
    thresholds never flaps into quarantine: each direction's counter demands
    CONSECUTIVE outcomes and the opposite outcome resets it, so an oscillator
    can never reach quarantine_after (or, once quarantined, release_after)."""
    from repro.serving import LinkController, LinkControllerConfig

    cfg = _cfg(channel="symbol")
    state = scaleout.precharacterize_state(cfg)
    p = phy.StaticProcess(guard_dims=8).init(state)
    n = state.n_rx
    cc = LinkControllerConfig(patience=1, quarantine_after=2, release_after=2,
                              drop_frac=2.0, band_kwargs={"cap": 0.05})
    ctl = LinkController(cc, p)
    hi = jnp.full((n,), 0.45, jnp.float32)
    junk = jax.random.normal(jax.random.PRNGKey(0), p.chan.symbols.shape,
                             jnp.float32).astype(jnp.complex64)
    p_bad = dataclasses.replace(
        p, chan=dataclasses.replace(p.chan, symbols=junk), est=hi)
    p_good = dataclasses.replace(p, est=hi)

    for i in range(10):                       # bad, good, bad, good, ...
        ctl.act(p_bad if i % 2 == 0 else p_good)
    assert not ctl.quarantined.any() and not ctl.degraded
    assert not any(e["action"] in ("quarantine", "release", "m_drop")
                   for e in ctl.trace)
