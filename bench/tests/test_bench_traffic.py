"""The traffic generator: each mix's arrival process is found by name from a
file of its own, and due times and tenants are reproducible from the seed
and differ between seeds."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import numpy as np
import pytest

import deploy
import spec
import traffic

BIG = 2**31 + 12345


def _arrivals(mix, seed, seconds, n_tenants, n_slots=4):
    plan = traffic.process(mix, seed, seconds, n_slots, n_tenants).start()
    return (np.array([d for d, _, _ in plan]), np.array([t for _, t, _ in plan]))


@pytest.mark.parametrize("seed", [0, 7, BIG, 2**40 + 3])
def test_open_loop_due_times_reproducible_from_seed(seed):
    mix = spec.load_traffic("poisson-zipf")
    a = _arrivals(mix, seed, 2.0, 64)
    b = _arrivals(mix, seed, 2.0, 64)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    c = _arrivals(mix, seed + 1, 2.0, 64)
    assert len(a[0]) != len(c[0]) or not np.array_equal(a[0], c[0])


def test_open_loop_rate_and_zipf_skew():
    mix = {"process": "poisson", "rate_per_s": 500, "zipf_alpha": 1.0}
    due, who = _arrivals(mix, BIG, 20.0, 16)
    assert np.all(np.diff(due) >= 0) and due[-1] < 20.0
    assert abs(len(due) / 20.0 - 500) < 5 * np.sqrt(500 * 20) / 20
    counts = np.bincount(who, minlength=16)
    assert counts[0] > 4 * counts[15]
    proc = traffic.process(mix, BIG, 1.0, 4, 16)
    assert proc.admit_sizes() == [1, 2, 3, 4] and proc.on_done(0, 0.5) == []


def test_tenant_weights_zipf_alpha_zero_is_uniform():
    np.testing.assert_allclose(traffic.tenant_weights({"zipf_alpha": 0}, 8),
                               np.full(8, 1 / 8))


def test_closed_clients_cycle_every_tenant():
    proc = traffic.process(spec.load_traffic("closed"), BIG, 1.0, 4, 8)
    first = proc.start()
    assert [c for _, _, c in first] == [0, 1, 2, 3]
    assert all(d == 0.0 for d, _, _ in first) and proc.admit_sizes() == [4]
    seen = [first[1][1]] + [proc.on_done(1, 0.25 * i)[0][1] for i in range(15)]
    assert sorted(seen[:8]) == list(range(8)) and seen[:8] == seen[8:]
    assert proc.on_done(2, 3.5)[0][::2] == (3.5, 2)


@pytest.mark.parametrize("name", ["poisson", "closed"])
def test_process_found_by_name(name):
    assert callable(spec.load_process(name))
    with pytest.raises(FileNotFoundError):
        spec.load_process("no-such-process")


def test_request_keys_reproducible_from_seed():
    a, b = deploy.KeyStream(BIG, block=8), deploy.KeyStream(BIG, block=8)
    np.testing.assert_array_equal(a[20], b[20])
    assert not np.array_equal(a[3], deploy.KeyStream(BIG + 1)[3])
