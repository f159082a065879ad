"""End-to-end paper pipeline: EM channel -> joint phase search -> BER -> HDC
accuracy, plus the distributed serve path wired to the pre-characterized BER —
the full Fig. 5 methodology in one test module, and the bring-up entry points
around it."""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import make_test_mesh

from repro.core import classifier, em, hypervector as hv, ota

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def pipeline():
    geom = em.PackageGeometry()
    h = em.channel_matrix(geom, 3, 64)
    n0 = ota.default_n0(h)
    res = ota.optimize_phases_exhaustive(h, n0)
    return geom, h, n0, res


def test_full_chain_reproduces_paper_claims(pipeline):
    """The headline claim: 3 TX + 64 RX, avg BER ~0.01, no accuracy impact with
    512-bit hypervectors and 100 classes (abstract + Table I)."""
    _, _, _, res = pipeline
    avg_ber = float(res.avg_ber)
    assert avg_ber <= 0.0105

    cfg = classifier.HDCTaskConfig(n_classes=100, dim=512, n_trials=400)
    acc_ideal = float(classifier.run_accuracy(KEY, cfg, 3, 0.0, "baseline"))
    acc_wireless = float(classifier.run_accuracy(KEY, cfg, 3, avg_ber, "baseline"))
    assert acc_ideal - acc_wireless <= 0.02  # "practically irrelevant"


def test_fig11_similarity_separation(pipeline):
    """Fig. 11: sent classes separate cleanly from the rest of the memory."""
    _, _, _, res = pipeline
    cfg = classifier.HDCTaskConfig(n_trials=1)
    protos = classifier.make_codebook(KEY, cfg)
    for m in (1, 3, 5):
        classes = jax.random.randint(jax.random.fold_in(KEY, m), (m,), 0, cfg.n_classes)
        q = hv.majority(protos[classes])
        q = hv.flip_bits(jax.random.fold_in(KEY, 99), q, float(res.avg_ber))
        sims = hv.hamming_similarity(q, protos)
        sent = np.asarray(sims[classes])
        rest = np.delete(np.asarray(sims), np.asarray(classes))
        assert sent.min() > rest.max(), m  # clean separation (no classification error)


def test_scaled_out_serve_with_measured_ber(pipeline):
    """Distributed scale-out on the single-device mesh with the measured channel
    state: classification accuracy unaffected (paper contribution (i)) — on the
    Eq. 1 BSC tier AND the full physical symbol tier from the SAME state."""
    import dataclasses

    _, h, _, res = pipeline
    from repro import phy
    from repro.core import scaleout

    mesh = make_test_mesh((1, 1), ("data", "model"))
    cfg = scaleout.ScaleOutConfig(
        n_classes=128, dim=512, m_tx=3, n_rx_cores=64, batch=64, use_kernels=True
    )
    state = phy.state_from_ota(res, h)
    protos = hv.random_hv(KEY, cfg.n_classes, cfg.dim)
    classes, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos, 1)
    serve = scaleout.make_ota_serve(mesh, cfg)
    pred, _ = serve(protos, queries, state, jax.random.PRNGKey(2))
    # the top-1 must be one of the bundled classes (channel noise may re-order
    # the three near-equal bundled similarities — that is not an error)
    hit = float(jnp.mean(jnp.any(pred[:, None] == classes, axis=1).astype(jnp.float32)))
    assert hit >= 0.97, hit
    # the physical channel (constellation + AWGN + decision regions in-graph)
    # reproduces the paper's operating point end-to-end — the BER abstraction
    # verified rather than assumed
    serve_s = scaleout.make_ota_serve(mesh, dataclasses.replace(cfg, channel="symbol"))
    pred_s, _ = serve_s(protos, queries, state, jax.random.PRNGKey(2))
    hit_s = float(jnp.mean(jnp.any(pred_s[:, None] == classes, axis=1).astype(jnp.float32)))
    assert hit_s >= 0.97, hit_s
    # and with a clean channel the distributed path equals the oracle exactly
    state0 = phy.state_from_ber(jnp.zeros_like(res.ber_per_rx), cfg.m_tx)
    pred0, _ = serve(protos, queries, state0, jax.random.PRNGKey(2))
    ref, _ = scaleout.serve_reference(cfg, protos, queries)
    assert bool(jnp.all(pred0 == ref))


def test_packed_serve_matches_unpacked_with_measured_ber(pipeline):
    """The packed fast path on the measured per-RX BERs: identical predictions
    to the unpacked serve on the same RNG stream (exact noise masks), with the
    Pallas hamming kernel in the loop (interpret mode on CPU)."""
    import dataclasses

    _, _, _, res = pipeline
    from repro import phy
    from repro.core import scaleout

    mesh = make_test_mesh((1, 1), ("data", "model"))
    cfg = scaleout.ScaleOutConfig(
        n_classes=128, dim=512, m_tx=3, n_rx_cores=8, batch=32, use_kernels=True
    )
    cfg_p = dataclasses.replace(cfg, representation="packed")
    protos = hv.random_hv(KEY, cfg.n_classes, cfg.dim)
    _, queries = scaleout.make_queries(jax.random.PRNGKey(1), cfg, protos, 1)
    _, queries_p = scaleout.make_queries(jax.random.PRNGKey(1), cfg_p, protos, 1)
    state = phy.state_from_ber(res.ber_per_rx[: cfg.n_rx_cores], cfg.m_tx)
    pred, sim = scaleout.make_ota_serve(mesh, cfg)(
        protos, queries, state, jax.random.PRNGKey(2))
    pred_p, sim_p = scaleout.make_ota_serve(mesh, cfg_p)(
        hv.pack(protos), queries_p, state, jax.random.PRNGKey(2))
    assert bool(jnp.all(pred == pred_p))
    np.testing.assert_array_equal(np.asarray(sim), np.asarray(sim_p))


def test_permuted_bundling_identifies_transmitter(pipeline):
    """Paper Sec. IV: permuted bundling recovers *which TX* sent each class."""
    _, _, _, res = pipeline
    cfg = classifier.HDCTaskConfig(n_classes=100, dim=512, n_trials=300)
    acc = float(classifier.run_accuracy(KEY, cfg, 5, float(res.avg_ber), "permuted"))
    assert acc >= 0.99


# ---------------------------------------------------------------------------
# bring-up entry points: chip_smoke.py, the compile cache, the CPU dry-run
# ---------------------------------------------------------------------------

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, tmp_path, *, devices=1, cwd=REPO, timeout=600, **env_extra):
    """Run a fresh interpreter on the CPU with its own compile cache."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": os.path.join(REPO, "src"),
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
                **env_extra})
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=cwd)


@pytest.mark.parametrize("flags,devices", [
    ((), 1),
    (("--tiny",), 1),
    (("--tiny", "--chips", "4"), 4),
], ids=["default", "tiny", "tiny-4chips"])
def test_chip_smoke_refuses_ok_without_tpu(tmp_path, flags, devices):
    """Without a TPU the script exits non-zero and prints no result. With
    --tiny it first runs every phase at a toy size (kernels in interpret
    mode): the ideal serves match serve_reference on every trial."""
    r = _run([os.path.join(REPO, "chip_smoke.py"), *flags], tmp_path,
             devices=devices)
    assert r.returncode != 0, r.stdout
    assert '"ok"' not in r.stdout
    if flags:
        refs = [ln for ln in r.stdout.splitlines() if "reference[" in ln]
        assert len(refs) == (3 if devices == 4 else 1), r.stdout + r.stderr
        assert all(" 0 of 16 predictions differ" in ln for ln in refs), r.stdout
        assert "rehearsal passed, but no TPU" in r.stderr
    else:
        assert "no TPU found" in r.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied out of the repository, the script cannot run and says nothing."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    r = _run(["chip_smoke.py", "--tiny"], tmp_path, cwd=alone,
             PYTHONPATH="")
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is the one cache directory and
    compiled programs land there; unset, the cache is <repo>/.jax_cache."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.cache import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    if from_env:
        code += "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()\n"
        r = _run(["-c", code], tmp_path,
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
                 JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
        want = str(tmp_path / "cache")
    else:
        r = _run(["-c", code], tmp_path, JAX_COMPILATION_CACHE_DIR="")
        want = os.path.join(REPO, ".jax_cache")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == [want, want]
    if from_env:
        assert os.listdir(want), "no compiled program was cached"


def test_dryrun_pins_cpu(tmp_path):
    """The dry-run is a CPU tool: importing it pins the CPU platform and its
    512 placeholder devices before JAX starts, whatever the environment."""
    code = ("import repro.launch.dryrun, jax\n"
            "print(jax.devices()[0].platform, len(jax.devices()))\n")
    r = _run(["-c", code], tmp_path, JAX_PLATFORMS="tpu")
    assert r.returncode == 0, r.stderr[-3000:]
    assert r.stdout.split() == ["cpu", "512"]
