"""The program's own scopes and spans: the serve step's ops map to the four
device scopes, the reduction of scoped op time, device idle time under the
program's spans and the slot counter, on synthetic events, on a compiled
step and on a trace recorded here; and the benchmark's own reduction reads
the same with the program's spans present."""
import bench_path  # noqa: F401  (must precede the benchmark's modules)
import pytest

import program_trace as pt
import trace_reduce as tr
from trace_reduce import Event, Reduced, Trace

MS = 1e6   # ns
SERVE, ADMIT_MODULE = "jit_body", "jit__admit_many_impl"
SERVE_OPS = [  # (name, offset ms into the run, duration ms)
    ("copy.1", 0, 2), ("add_xor_fusion", 2, 18), ("fusion.1", 20, 1),
    ("hamming_topk_banked_pallas.1", 21, 8), ("reduce.1", 29, 0.5),
    ("all-gather.1", 29.5, 0.5)]
SCOPES = pt.ScopeMap(
    {"copy.1": "search", "add_xor_fusion": "rx_copies", "fusion.1": "ota_bundle",
     "hamming_topk_banked_pallas.1": "search", "reduce.1": "search",
     "all-gather.1": "top1_gather"}, {"copy.1"})


def program_spans():
    """Two scheduler steps of 50 ms: admission (with its scatter), dispatch,
    collection (fetch, barrier); the window runs 10 ms past the last."""
    out = []
    for s in (0, 50):
        out += [Event("scheduler.step", s * MS, 50 * MS),
                Event("scheduler.admit", s * MS, 10 * MS),
                Event("hdc.admit_scatter", (s + 6) * MS, 3 * MS),
                Event("scheduler.dispatch", (s + 10) * MS, 2 * MS),
                Event("scheduler.collect", (s + 12) * MS, 38 * MS),
                Event("hdc.fetch", (s + 12) * MS, 36 * MS),
                Event("hdc.barrier", (s + 48) * MS, 1 * MS)]
    return out


def synthetic(with_program_spans: bool) -> Trace:
    """One chip, a 110 ms window: per step an admission program at 9 ms
    (whose op shares a name with a serve op) and a 30 ms serve run at 12."""
    host = [Event("bench.window", 0, 110 * MS),
            Event("bench.admit", 1 * MS, 9 * MS),
            Event("bench.collect", 12 * MS, 38 * MS)]
    if with_program_spans:
        host += program_spans()
    ops, mods = [], []
    for s in (0, 50):
        mods += [Event(ADMIT_MODULE, (s + 9) * MS, 1 * MS),
                 Event(SERVE, (s + 12) * MS, 30 * MS)]
        ops.append(Event("copy.1", (s + 9) * MS, 1 * MS))
        ops += [Event(n, (s + 12 + o) * MS, d * MS) for n, o, d in SERVE_OPS]
    return Trace({"/device:TPU:0": ops}, {"/device:TPU:0": mods}, host)


def layers() -> pt.ProgramLayers:
    return pt.ProgramLayers(Reduced(synthetic(True)), program_spans(), SERVE,
                            SCOPES)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(body)/ota_bundle/psum", "ota_bundle"),
    ("jit(body)/vmap(rx_copies)/vmap()/xor", "rx_copies"),
    ("jit(body)/search/jit(hamming_topk_banked_pallas)/pallas_call", "search"),
    ("jit(body)/top1_gather/all_gather", "top1_gather"),
    ("jit(body)/vmap()/vmap(jit(_threefry_fold_in))/slice", None),
    ("jit(f)/jit(_local_search)/dot_general", None),
    ("store", None),
])
def test_scope_of_op_name(op_name, scope):
    assert pt.scope_of(op_name) == scope


HLO = """\
HloModule jit_body, entry_computation_layout={()}

%fused_computation (param_0.1: u32[4]) -> u32[4] {
  %param_0.1 = u32[4]{0} parameter(0)
  ROOT %xor.3 = u32[4]{0} xor(%param_0.1, %param_0.1), metadata={op_name="jit(body)/vmap(rx_copies)/xor"}
}

ENTRY %main.9 (store.1: u32[4,8], queries.1: u32[4]) -> (s32[4], s32[4]) {
  %store.1 = u32[4,8]{1,0} parameter(0), metadata={op_name="store"}
  %queries.1 = u32[4]{0} parameter(1), metadata={op_name="queries"}
  %copy.2 = u32[4]{0} copy(%queries.1)
  %xor_fusion = u32[4]{0} fusion(%copy.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(body)/vmap(rx_copies)/xor"}
  %copy.7 = u32[4,8]{0,1:T(8,128)} copy(%store.1), metadata={op_name="store"}
  %pallas.1 = (s32[4]{0:T(128)}, s32[4]{0:T(128)S(1)}) custom-call(%xor_fusion, %copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(body)/search/jit(hamming_topk_banked_pallas)/pallas_call"}
  %get-tuple-element.1 = s32[4]{0} get-tuple-element(%pallas.1), index=0
  %get-tuple-element.2 = s32[4]{0} get-tuple-element(%pallas.1), index=1
  ROOT %tuple.3 = (s32[4]{0}, s32[4]{0}) tuple(%get-tuple-element.1, %get-tuple-element.2)
}
"""


def test_scope_map_reads_metadata_then_consumers_then_producers():
    m = pt.scope_map(HLO)
    assert m.get("xor_fusion") == "rx_copies"
    assert m.get("pallas.1") == "search"
    # the argument's layout copy feeds the kernel: it is the search's
    assert m.get("copy.7") == "search" and m.get("copy.2") == "rx_copies"
    # outputs take the scope of what produced them
    assert m.get("get-tuple-element.1") == "search"
    assert m.get("tuple.3") == "search"
    assert "xor.3" not in m.scope and "param_0.1" not in m.scope  # fused
    assert m.inherited == {"store.1", "queries.1", "copy.2", "copy.7",
                           "get-tuple-element.1", "get-tuple-element.2",
                           "tuple.3"}


def test_scoped_op_time_per_serve_run():
    lay = layers()
    assert lay.runs == 2
    got = {s: lay.scope_ms_per_run(s) for s in
           pt.SCOPES + ("search_prep", "search_kernel", "inherited", None)}
    assert got == pytest.approx({
        "ota_bundle": 1.0, "rx_copies": 18.0, "search": 10.5,
        "top1_gather": 0.5, "search_prep": 2.5, "search_kernel": 8.0,
        "inherited": 2.0, None: 0.0})
    # the admission program's op of the same name is not the serve step's
    assert len(lay.serve_ops("/device:TPU:0")) == 2 * len(SERVE_OPS)


def test_idle_time_inside_spans():
    lay = layers()
    assert lay.idle_in_s("scheduler.admit") == pytest.approx(0.018)
    assert lay.idle_in_s("scheduler.collect") == pytest.approx(0.016)
    assert lay.idle_in_s("no.such.span") is None


def test_idle_under_innermost_spans_sums_to_the_idle_time():
    lay = layers()
    by = lay.idle_by_span_s()
    assert by == pytest.approx({
        "scheduler.admit": 0.012, "hdc.admit_scatter": 0.006,
        "scheduler.dispatch": 0.004, "hdc.fetch": 0.012, "hdc.barrier": 0.002,
        "scheduler.collect": 0.002, None: 0.010})
    red = lay.red
    assert sum(by.values()) == pytest.approx(red.window_s - red.busy_s())
    assert lay.gap_spans(3) == [[None, pytest.approx(0.018)],
                                ["scheduler.admit", pytest.approx(0.017)],
                                ["scheduler.admit", pytest.approx(0.009)]]


def test_benchmark_reduction_unchanged_by_program_spans():
    with_p, without = Reduced(synthetic(True)), Reduced(synthetic(False))
    assert with_p.host == without.host
    assert with_p.breakdown() == without.breakdown()
    assert with_p.busy_s() == without.busy_s()


def test_a_program_without_scopes_or_spans_reads_nothing():
    red = Reduced(synthetic(False))
    lay = pt.ProgramLayers(red, [], SERVE, pt.ScopeMap({}, set()))
    assert all(lay.scope_ms_per_run(s) is None
               for s in pt.SCOPES + ("search_prep",))
    assert lay.idle_in_s("scheduler.admit") is None
    assert lay.idle_by_span_s() == {None: pytest.approx(red.window_s
                                                        - red.busy_s())}
    assert lay.gap_spans(1) == [[None, pytest.approx(0.018)]]


def _mesh():
    import jax

    from repro.compat import make_mesh

    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


@pytest.mark.parametrize("multi_tenant", [True, False],
                         ids=["make_mt_ota_serve", "make_ota_serve"])
def test_compiled_serve_step_carries_every_scope(multi_tenant):
    """At a rehearsal size: each scope is in the compiled step's metadata,
    and every fusion and copy of the step maps to a scope."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import phy
    from repro.core import scaleout

    mesh = _mesh()
    cfg = scaleout.ScaleOutConfig(
        n_classes=256, dim=512, m_tx=3, n_rx_cores=8, batch=16,
        representation="packed", collective="psum_packed", channel="bsc",
        noise="bitplane")
    rep = NamedSharding(mesh, P())

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=rep)

    state = jax.tree.map(lambda s: sds(s.shape, s.dtype),
                         phy.state_shape_structs(cfg.n_rx_cores, cfg.m_tx))
    w = cfg.words
    if multi_tenant:
        lowered = scaleout.make_mt_ota_serve(mesh, cfg).lower(
            sds((4, 256, w), jnp.uint32), sds((3, 16, 1, 3, w), jnp.uint32),
            sds((3,), jnp.int32), state, sds((3, 2), jnp.uint32))
    else:
        lowered = scaleout.make_ota_serve(mesh, cfg).lower(
            sds((256, w), jnp.uint32), sds((16, 1, 3, w), jnp.uint32), state,
            sds((2,), jnp.uint32))
    hlo = lowered.compile().as_text()
    named = {pt.scope_of(o) for o in pt._OP_NAME.findall(hlo)}
    assert set(pt.SCOPES) <= named
    m = pt.scope_map(hlo)
    entry = hlo[hlo.index("\nENTRY"):]
    ops = [pt._INSTR.match(line) for line in entry.splitlines()[1:]]
    kinds = {mm.group(1): pt._parse(mm.group(2))[0] for mm in ops if mm}
    moved = [n for n, k in kinds.items() if k in ("fusion", "copy")]
    assert moved and all(m.get(n) in pt.SCOPES for n in moved), \
        [n for n in moved if m.get(n) is None]


def test_recorded_scheduler_steps(tmp_path):
    """A trace of HDCScheduler steps here: each program span once a step,
    inside its ``scheduler.step``; the slot counter sums the running slots."""
    import jax
    import jax.numpy as jnp

    from repro import phy
    from repro.core import classifier, hypervector as hv, scaleout
    from repro.serving import HDCEngine, HDCScheduler

    cfg = scaleout.ScaleOutConfig(n_classes=40, dim=512, m_tx=3, n_rx_cores=4,
                                  batch=8, use_kernels=False, noise="exact")
    state = phy.state_from_ber(jnp.zeros((cfg.n_rx_cores,)), cfg.m_tx)
    eng = HDCEngine(_mesh(), cfg, state, num_slots=4, max_tenants=2)
    books = classifier.make_tenant_codebooks(
        jax.random.PRNGKey(0),
        classifier.HDCTaskConfig(n_classes=cfg.n_classes, dim=cfg.dim), 2)
    for name, book in zip("ab", books):
        eng.registry.onboard(name, hv.pack(book) if cfg.packed else book)
    _, q = scaleout.make_queries(jax.random.PRNGKey(3), cfg, books[0], 1)
    warm = HDCScheduler(eng)
    for n in (4, 1):   # compile both admission sizes outside the trace
        for i in range(n):
            warm.submit("a", q)
        warm.run(timeout=600)
    sched = HDCScheduler(eng)
    for i in range(5):
        sched.submit("a" if i % 2 else "b", q)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        sched.run(timeout=600)
    jax.profiler.stop_trace()
    assert sched.steps == 2
    assert sched.slot_steps == 4 + 1 and sched.computed_slot_steps == 2 * 4

    spans = pt.load_spans(str(tmp_path))
    names = [e.name for e in spans]
    for name in ("scheduler.step", "scheduler.admit", "scheduler.dispatch",
                 "scheduler.collect", "hdc.admit_scatter", "hdc.fetch",
                 "hdc.barrier"):
        assert names.count(name) == 2, (name, names)

    def inside(e, name):
        return any(p.name == name and p.start_ns <= e.start_ns
                   and e.end_ns <= p.end_ns for p in spans)

    parent = {"hdc.admit_scatter": "scheduler.admit",
              "hdc.fetch": "scheduler.collect",
              "hdc.barrier": "scheduler.collect"}
    for e in spans:
        if e.name != "scheduler.step":
            assert inside(e, "scheduler.step"), e.name
        if e.name in parent:
            assert inside(e, parent[e.name]), e.name
    # the benchmark's own reduction reads none of them
    assert Reduced(tr.load(str(tmp_path))).host == []


def test_layers_rehearsal_reads_the_counter(capsys):
    """``layers.py`` end to end at toy sizes on the CPU: a traced and an
    untraced window a seed; with no device plane the device readings are
    empty, and the closed loop fills every slot it computes."""
    import json

    import layers

    assert layers.main(["--workload", "table1-closed", "--seeds",
                        str(2**33 + 7), "--seconds", "0.3",
                        "--rehearse"]) == 0
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["steps"] > 0 and row["serve_runs"] == 0
    assert row["slot_fill_share"] == 100.0
    assert row["rx_copies_ms_per_step"] is None
