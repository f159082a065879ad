"""The reduction from trace to metrics, on synthetic events and on a trace
recorded here."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import pytest

import trace_reduce as tr
from trace_reduce import Event, Reduced, Trace

MS = 1e6   # ns


def synthetic():
    """Two chips, a 100 ms window, two serve steps of 30 ms on each chip."""
    host = [Event("bench.window", 0, 100 * MS),
            Event("bench.admit", 0, 10 * MS),
            Event("bench.dispatch", 10 * MS, 2 * MS),
            Event("bench.collect", 12 * MS, 38 * MS),
            Event("bench.admit", 50 * MS, 10 * MS),
            Event("bench.collect", 62 * MS, 38 * MS)]
    ops, mods = {}, {}
    for d, skew in (("/device:TPU:0", 0), ("/device:TPU:1", 1 * MS)):
        ops[d], mods[d] = [], []
        for s in (12 * MS, 62 * MS):
            s += skew
            mods[d].append(Event("jit_body", s, 30 * MS))
            ops[d] += [Event("add_xor_fusion", s, 20 * MS),
                       Event("all-reduce.2", s + 20 * MS, 2 * MS),
                       Event("hamming_topk_banked_pallas.1", s + 22 * MS,
                             8 * MS)]
        ops[d].append(Event("outside", 150 * MS, 5 * MS))
    return Trace(ops, mods, host)


def test_busy_and_window():
    red = Reduced(synthetic())
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s() == pytest.approx(0.06)
    assert red.devices == ["/device:TPU:0", "/device:TPU:1"]


def test_module_kernel_and_collective_times():
    red = Reduced(synthetic())
    assert len(red.module_runs("jit_body")) == 2
    assert red.module_s("jit_body") == pytest.approx([0.06, 0.06])
    assert red.op_s("topk_banked") == pytest.approx([0.016, 0.016])
    assert red.op_s("all-reduce") == pytest.approx([0.004, 0.004])
    assert red.op_s("xor_fusion") == pytest.approx([0.04, 0.04])
    assert red.module_runs("jit_bod") == []


def test_breakdown_names_gaps_by_host_span():
    bd = Reduced(synthetic()).breakdown()
    assert bd["device_ops"][0] == ["add_xor_fusion", pytest.approx(0.04)]
    assert bd["idle_gaps"] == [["bench.admit", pytest.approx(0.020)],
                               ["bench.admit", pytest.approx(0.012)],
                               ["bench.collect", pytest.approx(0.008)]]
    assert len(bd["device_ops"]) <= 10


def test_short_names_of_tpu_ops_and_modules():
    assert tr._short("%hamming_topk_banked_pallas.1 = (s32[2048,64,1]) "
                     "custom-call(u32[2048,64,16] %or_xor_fusion)") == \
        "hamming_topk_banked_pallas.1"
    assert tr._short("jit_body(15919126606600707592)") == "jit_body"
    assert tr._short("%fusion.3 = u32[8] fusion(%all-reduce.1)") == "fusion.3"


def test_no_window_span_raises():
    with pytest.raises(ValueError):
        Reduced(Trace({}, {}, [Event("bench.admit", 0, 1)]))


def test_recorded_trace_without_a_device(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            y.block_until_ready()
    jax.profiler.stop_trace()
    red = Reduced(tr.load(str(tmp_path)))
    assert red.window_s > 0
    assert [h.name for h in red.host].count("bench.dispatch") == 3
    assert red.devices == [] and red.busy_s() is None
    assert red.breakdown() == {"device_ops": [], "idle_gaps": []}
