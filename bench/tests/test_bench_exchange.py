"""The cross-chip exchange's readers (``collective_ms_per_step``,
``collective_roofline_share``) on synthetic trace events, and its least
bytes counted by hand at the ``whype4`` cell's sizes."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import pytest

import exchange
import spec
from peaks import peaks
from trace_reduce import Event, Reduced, Trace

MS = 1e6   # ns
US = 1e3
MODULE = "jit_body"
SIZES = {"slots": 8, "trials_per_request": 512, "dim": 2048, "m_tx": 3}


def _trace(chip_ops, runs=2, step=30 * MS):
    """Each chip runs the serve module ``runs`` times, ``step`` apart; its
    ops in a run are ``chip_ops(chip, run_start)``; one collective outside
    every run is never counted."""
    host = [Event("bench.window", 0, runs * step + 10 * MS)]
    ops, mods = {}, {}
    for chip, ops_of in enumerate(chip_ops):
        d = f"/device:TPU:{chip}"
        ops[d], mods[d] = [], []
        for r in range(runs):
            s = r * step
            mods[d].append(Event(MODULE, s, step - 5 * MS))
            ops[d] += ops_of(s)
        ops[d].append(Event("all-reduce.9", runs * step + 1 * MS, 1 * MS))
    return Reduced(Trace(ops, mods, host))


def _ctx(red, model_size=4):
    return {"reduced": red, "serve_runs": len(red.module_runs(MODULE)),
            "serve_module": MODULE, "sizes": SIZES, "model_size": model_size,
            "device_kind": "TPU v5 lite", "notes": []}


def _read(name, ctx):
    return spec.load_reader(name)(ctx)


def test_synchronous_all_reduce_counts_its_own_duration():
    red = _trace([lambda s: [Event("add_xor_fusion", s, 10 * MS),
                             Event("all-reduce.2", s + 10 * MS, 200 * US),
                             Event("all-gather.10", s + 12 * MS, 50 * US)]] * 2)
    got = _read("collective_ms_per_step", _ctx(red, 2))
    assert got == {"value": pytest.approx(0.25), "unit": "ms"}


def test_psum_named_all_reduce_is_counted():
    red = _trace([lambda s: [Event("psum.6", s + 1 * MS, 300 * US),
                             Event("reshape.21", s + 2 * MS, 1 * MS)]] * 2)
    assert _read("collective_ms_per_step",
                 _ctx(red, 2))["value"] == pytest.approx(0.3)


def test_async_pair_counts_from_start_to_done_with_compute_between():
    red = _trace([lambda s: [
        Event("all-reduce-start.1", s + 1 * MS, 10 * US),
        Event("or_xor_fusion", s + 1.1 * MS, 400 * US),
        Event("all-reduce-done.1", s + 1.6 * MS, 40 * US),
        Event("collective-permute-start", s + 3 * MS, 5 * US),
        Event("collective-permute-done", s + 3.2 * MS, 5 * US)]] * 2)
    got = _read("collective_ms_per_step", _ctx(red, 2))["value"]
    assert got == pytest.approx(0.64 + 0.205)


def test_averaged_over_four_device_planes():
    def chip(k):
        return lambda s: [Event("reduce-scatter.3", s, (k + 1) * 100 * US),
                          Event("all-gather.4", s + 2 * MS, 100 * US)]

    red = _trace([chip(k) for k in range(4)])
    # chips read 0.2, 0.3, 0.4, 0.5 ms a step
    assert _read("collective_ms_per_step",
                 _ctx(red))["value"] == pytest.approx(0.35)
    share = _read("collective_roofline_share", _ctx(red))["value"]
    least = exchange.least_time(
        exchange.exchange_bytes(trials=4096, dim=2048, m_tx=3, chips=4),
        peaks("TPU v5 lite"))
    assert share == pytest.approx(100 * least / 0.35e-3)


def test_none_on_a_one_chip_trace():
    red = _trace([lambda s: [Event("all-reduce.2", s, 1 * MS)]])
    ctx = _ctx(red, 1)
    assert _read("collective_ms_per_step", ctx) is None
    assert _read("collective_roofline_share", ctx) is None
    assert _read("collective_ms_per_step",
                 dict(ctx, reduced=None, serve_runs=0)) is None


def test_least_bytes_of_the_cell_by_hand():
    want = 4096 * 2048 * 3 / 8 * 1.5 + 3 * 4096 * 8
    assert exchange.field_bits(3) == 3
    assert exchange.exchange_bytes(trials=4096, dim=2048, m_tx=3,
                                   chips=4) == pytest.approx(want)
    assert want == pytest.approx(4.8e6, rel=0.01)
    least = exchange.least_time(want, peaks("TPU v5 lite"))
    assert least == pytest.approx(want / 200e9)
    assert least == pytest.approx(24e-6, rel=0.01)
    assert exchange.exchange_bytes(trials=4096, dim=2048, m_tx=3,
                                   chips=1) == 0


def test_share_is_100_when_measured_equals_least():
    nbytes = exchange.exchange_bytes(trials=4096, dim=2048, m_tx=3, chips=4)
    least_ns = exchange.least_time(nbytes, peaks("TPU v5 lite")) * 1e9
    red = _trace([lambda s: [Event("all-reduce.2", s, least_ns)]] * 4)
    share = _read("collective_roofline_share", _ctx(red))["value"]
    assert share == pytest.approx(100.0) and share <= 100.0 + 1e-9
