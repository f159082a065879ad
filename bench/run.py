"""Benchmark of the multi-tenant HDC similarity service: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in BENCHMARK.json) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``, whose ``process`` names its arrival process,
``bench/traffic/<process>.py``). The run builds the deployment from the seed,
warms every shape the traffic uses, then drives ``HDCScheduler.step()`` over
an ``HDCEngine`` for ``--seconds`` with the mix's requests. Afterwards it
compares a seeded sample of the answers with the plain reference
(``reference.py``) and prints, as the last line of standard output, one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, each read by
``bench/metrics/<name>.py``), ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each number compared with its limit. The same checks
are the last lines of standard error.

It exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for. ``--rehearse`` runs the cell at the configuration's toy
``rehearsal`` sizes on any backend and then exits 3 without a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import dataclasses
import gc
import heapq
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np

import deploy
import spec
import traffic

TRACE_SECONDS = 5.0       # longest traced window
DRAIN_SECONDS = 60.0      # how long past the window's close an answer may come
# Largest relative gap of the service's per-core BER from the plain
# precharacterization: float32 reads 1.2e-4 (64 cores) and 1.7e-4 (1,024),
# the table in bfloat16 (the control) 3.1e-3 and 3.4e-3 (PERF.md).
LINK_BER_GAP_LIMIT = 7e-4
AXES = ("data", "model")


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Counts programs lowered by JAX (one per compilation, whether or not
    the persistent cache then supplies the binary)."""

    _instance = None

    def __init__(self):
        self.n = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            from jax import monitoring

            cls._instance = cls()
            monitoring.register_event_duration_secs_listener(
                cls._instance._on_event)
        return cls._instance

    def _on_event(self, name, secs, **kw):
        if name.endswith("jaxpr_to_mlir_module_duration"):
            self.n += 1


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


class Spans:
    """The benchmark's host spans around its calls into the service's
    layers: admission (``HDCEngine.admit_many``), the step's dispatch
    (``HDCEngine.step``) and the collection of its results (the scheduler's
    ``_collect``, which waits for the device). Each call's host time is kept,
    by span."""

    def __init__(self):
        self.s: dict[str, list[float]] = {"admit": [], "dispatch": [],
                                          "collect": []}

    def _timed(self, name: str, fn):
        kept = self.s[name]

        def call(*a, **kw):
            t = time.perf_counter()
            with _span(f"bench.{name}"):
                out = fn(*a, **kw)
            kept.append(time.perf_counter() - t)
            return out

        return call

    def wrap_engine(self, eng) -> None:
        eng.admit_many = self._timed("admit", eng.admit_many)
        eng.step = self._timed("dispatch", eng.step)

    def wrap_scheduler(self, sched) -> None:
        sched._collect = self._timed("collect", sched._collect)

    def clear(self) -> None:
        for v in self.s.values():
            v.clear()


class GCWatch:
    """Host time in Python's garbage collector while on (a stall on the host
    that no span of the benchmark names)."""

    def __init__(self):
        self.on = False
        self.pauses: list[float] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def check_devices(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise NoChip(f"no TPU found (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


class Cell:
    """One cell's deployment, set up from the seed."""

    def __init__(self, workload: str, seed: int, *, rehearse: bool,
                 control: bool = False):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.compat import make_mesh
        from repro.serving import HDCEngine

        bench = spec.load_benchmark()
        self.cell = spec.workload(bench, workload)
        self.bench = bench
        self.conf = spec.load_config(self.cell["config"])
        self.mix = spec.load_traffic(self.cell["traffic"])
        if rehearse:
            self.mix.update(self.mix.get("rehearsal", {}))
        self.seed = seed
        self.rehearse = rehearse
        self.s = s = deploy.sizes(self.conf, rehearse)
        self.devs = check_devices(self.cell["chips"], rehearse)
        self.model_size = s["mesh"][1]
        n_mesh = s["mesh"][0] * s["mesh"][1]
        self.mesh = make_mesh(s["mesh"], AXES, devices=self.devs[:n_mesh])

        self.chan, t_chan, hit = deploy.channel_state(s)
        log(f"setup: channel state of {s['n_rx_cores']} cores "
            f"{'read from the cache' if hit else 'precharacterized'} in "
            f"{t_chan:.3f} s")
        ctl = self.conf["control"] if control else {}
        if "ber_dtype" in ctl:
            ber = jax.numpy.asarray(self.chan.ber, ctl["ber_dtype"])
            self.chan = dataclasses.replace(self.chan,
                                            ber=np.asarray(ber, np.float32))
        self.cfg = deploy.service_config(s, noise_planes=ctl.get("noise_planes"))
        state = jax.device_put(jax.tree.map(np.asarray, self.chan),
                               NamedSharding(self.mesh, P()))
        self.eng = HDCEngine(self.mesh, self.cfg, state, num_slots=s["slots"],
                             max_tenants=s["tenants"])
        t0 = time.perf_counter()
        banks = deploy.make_banks(seed, s)
        for t in range(s["tenants"]):
            self.eng.registry.onboard(t, banks[t])
        jax.block_until_ready(self.eng.registry.store)
        t1 = time.perf_counter()
        classes, queries = deploy.make_payloads(seed, s, banks, self.model_size)
        self.classes = np.asarray(classes)
        self.pool = [[queries[t, p] for p in range(s["payloads_per_tenant"])]
                     for t in range(s["tenants"])]
        del banks, queries
        jax.block_until_ready(self.pool)
        log(f"setup: {s['tenants']} tenants onboarded in {t1 - t0:.3f} s, "
            f"payload pool in {time.perf_counter() - t1:.3f} s")
        self.keys = deploy.KeyStream(seed)
        self.spans = Spans()
        self.spans.wrap_engine(self.eng)

    def scheduler(self):
        from repro.serving import HDCScheduler

        sched = HDCScheduler(self.eng, clock=time.perf_counter)
        self.spans.wrap_scheduler(sched)
        return sched

    def payload(self, key: np.ndarray) -> int:
        """Which of its tenant's payloads a request carries (from its key)."""
        return int(key[0]) % self.s["payloads_per_tenant"]

    def warm_up(self, admit_sizes: list[int]) -> None:
        """Compile and run every shape the traffic uses: each admission batch
        size K it can produce, and the serve step at the cell's slot count
        (twice over the scheduler's whole path)."""
        import jax

        n = self.s["slots"]
        key = np.zeros(2, np.uint32)
        for k in admit_sizes:
            st = self.eng.admit_many(self.eng.init_state(), [self.pool[0][0]] * k,
                                     [0] * k, list(range(k)), [key] * k)
            jax.block_until_ready(st)
        for _ in range(2):
            sched = self.scheduler()
            for i in range(n):
                sched.submit(i % self.s["tenants"], self.pool[0][0], key=key)
            sched.run(timeout=1200)
        self.spans.clear()

    def serve_module(self) -> str:
        """The name of the serve step's program, as the profiler names it;
        logs the HBM the compiled program asks for besides its arguments
        (the device's allocator statistics do not count it)."""
        import re

        store, chan = self.eng.params
        st = self.eng.init_state()
        lowered = self.eng._serve.lower(store, st["queries"], st["row"], chan,
                                        st["key"])
        mem = lowered.compile().memory_analysis()
        if mem is not None:
            log(f"serve step program: {mem.temp_size_in_bytes} bytes of "
                f"temporaries, {mem.argument_size_in_bytes} bytes of arguments")
        return re.search(r"module @(\S+)", lowered.as_text()).group(1)


class Window:
    """What one measured window produced."""

    def __init__(self):
        self.reqs: dict[int, tuple] = {}   # rid -> (tenant, payload, key, due)
        self.t0 = 0.0
        self.t_end = 0.0
        self.lateness: list[float] = []
        self.step_s: list[float] = []
        self.steps = 0
        self.results: dict = {}
        self.compiles = 0


def drive(c: Cell, proc, seconds: float) -> Window:
    """Run the traffic of ``proc`` (an arrival process of ``traffic.py``)
    for ``seconds`` and follow every request due in the window to its
    completion, for at most ``DRAIN_SECONDS`` past the window's close (a
    request still unanswered then counts as such)."""
    w = Window()
    sched = c.scheduler()
    sent = 0
    owner: dict[int, int] = {}
    plan = [(due, i, tenant, client)
            for i, (due, tenant, client) in enumerate(proc.start())]
    heapq.heapify(plan)
    n_planned = len(plan)

    def submit(tenant: int, due: float) -> int:
        nonlocal sent
        key = c.keys[sent]
        sent += 1
        p = c.payload(key)
        rid = sched.submit(tenant, c.pool[tenant][p], key=key)
        w.reqs[rid] = (tenant, p, key, due)
        return rid

    counter = CompileCounter.get()
    before = counter.n
    clock = time.perf_counter
    w.t0 = clock()
    w.t_end = w.t0 + seconds
    while (plan or sched.pending or sched.running) and \
            clock() < w.t_end + DRAIN_SECONDS:
        now = clock() - w.t0
        while plan and plan[0][0] <= now:
            due, _, tenant, client = heapq.heappop(plan)
            owner[submit(tenant, w.t0 + due)] = client
            w.lateness.append(now - due)
        if sched.pending or sched.running:
            t = clock()
            finished = sched.step()
            now = clock()
            w.step_s.append(now - t)
            if now < w.t_end:
                for done in finished:
                    for due, tenant, client in proc.on_done(owner.pop(done.rid),
                                                           now - w.t0):
                        heapq.heappush(plan, (due, n_planned, tenant, client))
                        n_planned += 1
        elif plan:
            with _span("bench.wait"):
                time.sleep(max(0.0, w.t0 + plan[0][0] - clock()))
    w.compiles = counter.n - before
    w.steps = sched.steps
    w.results = sched.results
    return w


def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats()
        if stats:
            peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def compare(c: Cell, w: Window) -> tuple[dict, int]:
    """Every request due in the window must be answered, by its own tenant;
    a seeded sample of the answers must equal the plain reference's,
    trial by trial, with each core's flip threshold from the per-core BER the
    service was given; and that BER must agree with the plain
    precharacterization (``linkref.py``) to within ``LINK_BER_GAP_LIMIT``
    (relative, against the 2^-planes floor). Returns the checks and the
    number of trials compared."""
    import jax.numpy as jnp

    import reference

    s = c.s
    b = s["trials_per_request"]
    unanswered = misrouted = 0
    ok = []
    for rid, (tenant, _, _, _) in w.reqs.items():
        done = w.results.get(rid)
        if done is None or done.status != "ok":
            unanswered += 1
            continue
        if done.tenant != tenant or np.shape(done.pred) != (b,):
            misrouted += 1
            continue
        ok.append(rid)
    import linkref

    ref_ber = linkref.per_core_ber(s["m_tx"], s["n_rx_cores"], s["snr_db"])
    floor = 2.0 ** -s["noise_planes"]
    link_gap = float(np.max(np.abs(np.asarray(c.chan.ber, np.float64) - ref_ber)
                            / np.maximum(ref_ber, floor)))
    rng = deploy.host_rng(c.seed, deploy.STREAM_CHECK)
    sample = sorted(rng.choice(ok, min(s["check_requests"], len(ok)),
                               replace=False).tolist()) if ok else []
    banks = deploy.make_banks(c.seed, s)
    thr = jnp.asarray(reference.flip_threshold(c.chan.ber, s["noise_planes"]))
    pred_bad = sim_bad = 0
    for rid in sample:
        tenant, p, key, _ = w.reqs[rid]
        ref_pred, ref_sim = reference.serve(
            banks[tenant], jnp.asarray(c.classes[tenant, p]), jnp.asarray(key),
            thr, n_cores=s["n_rx_cores"], planes=s["noise_planes"],
            chunk=s["reference_core_chunk"])
        done = w.results[rid]
        pred_bad += int(np.sum(np.asarray(ref_pred) != done.pred))
        sim_bad += int(np.sum(np.asarray(ref_sim) != done.maxsim))
    checks = {
        "pred_mismatch": {"value": pred_bad, "limit": 0},
        "maxsim_mismatch": {"value": sim_bad, "limit": 0},
        "unanswered": {"value": unanswered, "limit": 0},
        "misrouted": {"value": misrouted, "limit": 0},
        "link_ber_gap": {"value": link_gap, "limit": LINK_BER_GAP_LIMIT},
    }
    return checks, len(sample) * b


def _ms(xs) -> str:
    if not len(xs):
        return "none"
    q = np.percentile(np.asarray(xs) * 1e3, [50, 100])
    return f"p50 {q[0]:.3f} ms, max {q[1]:.3f} ms"


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             rehearse: bool = False, control: bool = False, tamper=None,
             mix_update: dict | None = None) -> dict:
    """One run of one cell; returns the result object. ``control`` runs
    the service at the lower precisions of the configuration's ``control``
    block; ``tamper(engine)`` lets a test break the timed path underneath;
    ``mix_update`` replaces parameters of the traffic mix (the rate sweep)."""
    import jax

    if not rehearse:
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    c = Cell(workload, seed, rehearse=rehearse, control=control)
    c.mix.update(mix_update or {})
    if tamper is not None:
        tamper(c.eng)
    window = min(seconds, TRACE_SECONDS) if trace else seconds
    proc = traffic.process(c.mix, seed, window, c.s["slots"], c.s["tenants"])
    t_warm = time.perf_counter()
    c.warm_up(proc.admit_sizes())
    log(f"setup: warm-up in {time.perf_counter() - t_warm:.3f} s "
        f"(process start to here {time.perf_counter() - T_START:.3f} s)")
    module = c.serve_module() if trace else None
    notes: list[str] = []
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    gcw = GCWatch()
    t_setup = time.perf_counter() - T_START
    gcw.on = True
    with _span("bench.window"):
        w = drive(c, proc, window)
    gcw.on = False
    gcw.close()
    if trace:
        jax.profiler.stop_trace()
    spans = {k: list(v) for k, v in c.spans.s.items()}

    used = c.devs[:c.s["mesh"][0] * c.s["mesh"][1]]
    device = {"platform": c.devs[0].platform, "kind": c.devs[0].device_kind,
              "count": len(c.devs), "memory_peak_bytes": memory_peak(used)}
    c.eng = c.pool = None
    gc.collect()

    b = c.s["trials_per_request"]
    done = [(rid, w.results[rid]) for rid in w.reqs if rid in w.results]
    ok = [(rid, d) for rid, d in done if d.status == "ok"]
    ctx = {
        "cell": workload, "sizes": c.s, "model_size": c.model_size,
        "device_kind": device["kind"], "notes": notes,
        "setup_s": t_setup, "t0": w.t0,
        "t_last_finish": max((d.t_finish for _, d in ok), default=w.t0),
        "trials_done": b * len(ok),
        "latencies": [d.t_finish - w.reqs[rid][3] for rid, d in ok],
        "completions": [(w.reqs[rid][3], d.t_admit, d.t_finish) for rid, d in ok],
        "steps": w.steps, "admit_s": spans["admit"],
        "reduced": None, "serve_module": module, "serve_runs": 0,
    }
    breakdown = None
    if trace:
        import trace_reduce

        red = trace_reduce.Reduced(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        if red.devices:
            ctx["reduced"] = red
            ctx["serve_runs"] = len(red.module_runs(module))
            busy = red.busy_s()
            if busy:
                device["busy_s"] = busy
                device["window_s"] = red.window_s
            breakdown = red.breakdown()
        notes.append(f"trace: {len(red.devices)} device planes, serve module "
                     f"{module!r} ran {ctx['serve_runs']} times, "
                     f"{w.steps} scheduler steps")
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(c.bench, workload, kind):
        val = spec.load_reader(m["name"])(ctx)
        if val is not None:
            metrics[m["name"]] = val

    checks, n_cmp = compare(c, w)
    unanswered = checks["unanswered"]["value"]
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    notes.append(f"window: {window} s, {len(w.reqs)} requests, {w.steps} "
                 f"steps, {w.compiles} compilations inside it")
    notes.append(f"generator lateness (submit - due): {_ms(w.lateness)}")
    notes.append(f"scheduler steps on the host clock: {_ms(w.step_s)}; "
                 + "; ".join(f"{k} {_ms(v)}" for k, v in spans.items()))
    notes.append(f"garbage collector in the window: {len(gcw.pauses)} "
                 f"pauses, {_ms(gcw.pauses)}, {sum(gcw.pauses) * 1e3:.3f} ms "
                 "in all")
    notes.append(f"drain: last completion "
                 f"{(ctx['t_last_finish'] - w.t_end) * 1e3:.3f} ms after the "
                 "window closed")
    notes.append(f"checked {n_cmp} trials of the sample against the reference")
    result = {"correct": correct, "attempted": len(w.reqs),
              "failed": unanswered + checks["misrouted"]["value"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for n in notes:
        log(n)
    for name, v in checks.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; exits 3 with no result")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), rehearse=args.rehearse)
    except NoChip as e:
        log(f"no result: {e}")
        return 2
    if args.rehearse:
        log("rehearsal done: " + json.dumps(result["checks"]))
        log("rehearsal: not a chip run, no result")
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
