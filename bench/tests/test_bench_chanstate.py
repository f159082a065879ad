"""The channel-state file: a second set-up with the same link reads it and
gets the same ChannelState."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import os

import numpy as np

import deploy

LINK = {"m_tx": 3, "n_rx_cores": 16, "snr_db": 7.0}


def test_second_setup_reads_the_file(tmp_path):
    a, _, hit_a = deploy.channel_state(LINK, cache_dir=str(tmp_path))
    path = deploy.chanstate_path(LINK, str(tmp_path))
    assert not hit_a and os.path.exists(path)
    b, _, hit_b = deploy.channel_state(LINK, cache_dir=str(tmp_path))
    assert hit_b
    for f in ("ber", "valid", "h", "phase_idx", "symbols", "c0", "c1", "n0"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
        assert getattr(a, f).dtype == getattr(b, f).dtype


def test_key_follows_the_link_and_the_code(monkeypatch):
    p = deploy.chanstate_path(LINK)
    assert p == deploy.chanstate_path(dict(LINK, tenants=9))
    assert p != deploy.chanstate_path(dict(LINK, n_rx_cores=32))
    assert p != deploy.chanstate_path(dict(LINK, snr_db=8.0))
    monkeypatch.setattr(deploy, "_program_digest", lambda: "other code")
    assert p != deploy.chanstate_path(LINK)


def test_plain_precharacterization_closed_form_equals_the_symbols():
    """``linkref.eq1_ber`` against the constellation itself: the eight
    received symbols, their majority centroids, the nearest-centroid check."""
    import linkref

    rng = np.random.default_rng(5)
    h = rng.normal(size=(200, 3)) + 1j * rng.normal(size=(200, 3))
    n0 = 0.3
    rot = np.exp(2j * np.pi * np.arange(8) / 8)
    bits = (np.arange(8)[:, None] >> np.arange(3)) & 1
    maj = bits.sum(-1) >= 2
    for _ in range(20):
        pairs = np.array([rng.choice(8, 2, replace=False) for _ in range(3)])
        y = sum(h[:, t, None] * rot[pairs[t][bits[:, t]]] for t in range(3))
        c0, c1 = y[:, ~maj].mean(-1), y[:, maj].mean(-1)
        d0, d1 = np.abs(y - c0[:, None]), np.abs(y - c1[:, None])
        valid = np.all(np.where(maj, d1 < d0, d0 < d1), -1)
        want = np.where(valid, 0.5 * linkref.erfc(0.5 * np.abs(c1 - c0)
                                                  / np.sqrt(n0)), 0.5)
        u = [h[:, t] * (rot[pairs[t][1]] - rot[pairs[t][0]]) / 2
             for t in range(3)]
        np.testing.assert_allclose(linkref.eq1_ber(*u, n0), want, rtol=1e-12)
