"""The program's own layers in one cell, read from its named scopes, host
spans and slot counter (``program_trace.py``). All seeds in one process.

    python3 bench/layers.py --workload whype-closed --seeds 4000,4001,4002

For each seed it builds the cell as ``run.py`` does, runs an untraced window
and then a traced one of ``--seconds`` (5 by default, ``run.py``'s traced
window), and prints one JSON line: steps per second in both windows, the
per-layer readings below, and beside them the accepted readers' own
(``fanout_ms_per_step``, ``search_ms_per_step``, ``device_idle_share``) from
the same trace, each per serve-module run or per scheduler step:

- ``ota_bundle_ms_per_step``, ``rx_copies_ms_per_step``,
  ``top1_gather_ms_per_step``: ops under each scope;
  ``search_prep_ms_per_step``: ops under ``search`` other than the
  ``topk_banked`` kernel; ``unscoped_ms_per_step``: serve ops of no scope;
  ``inherited_ms_per_step``: serve ops whose scope came from a neighbour;
- ``idle_in_admit_ms_per_step``, ``idle_in_collect_ms_per_step``: device
  idle time inside ``scheduler.admit``, ``scheduler.collect`` spans;
- ``idle_by_span_ms_per_step``: idle time under each innermost program span
  (``null``: under none, the load generator's own work);
- ``slot_fill_share``: ``slot_steps`` over ``computed_slot_steps``, in %;
- ``gaps``: the ten longest idle gaps, each with its innermost span;
- ``scope_of``: the scope of each of the ten ops that took most time.

``--rehearse`` runs at the configuration's toy sizes on any backend.
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import shutil
import sys
import tempfile
import time

import run
import program_trace as pt
import spec
import trace_reduce
import traffic


def serve_program(c: run.Cell) -> tuple[str, str]:
    """The serve step's module name, as the profiler names it, and the text
    of its compiled HLO."""
    store, chan = c.eng.params
    st = c.eng.init_state()
    lowered = c.eng._serve.lower(store, st["queries"], st["row"], chan,
                                 st["key"])
    name = re.search(r"module @(\S+)", lowered.as_text()).group(1)
    return name, lowered.compile().as_text()


def window(c: run.Cell, seed: int, seconds: float, trace_dir: str | None):
    """One window of the cell's traffic; returns (Window, scheduler, wall
    seconds of the drive)."""
    import jax

    made = []
    make = c.scheduler
    c.scheduler = lambda: made.append(make()) or made[-1]
    proc = traffic.process(c.mix, seed, seconds, c.s["slots"], c.s["tenants"])
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t = time.perf_counter()
    with run._span("bench.window"):
        w = run.drive(c, proc, seconds)
    wall = time.perf_counter() - t
    if trace_dir:
        jax.profiler.stop_trace()
    c.scheduler = make
    return w, made[-1], wall


def readings(c: run.Cell, seed: int, seconds: float) -> dict:
    proc = traffic.process(c.mix, seed, seconds, c.s["slots"], c.s["tenants"])
    c.warm_up(proc.admit_sizes())
    module, hlo = serve_program(c)
    scopes = pt.scope_map(hlo)
    w0, _, wall0 = window(c, seed, seconds, None)
    trace_dir = tempfile.mkdtemp(prefix="bench-layers-")
    try:
        w, sched, wall = window(c, seed, seconds, trace_dir)
        red = trace_reduce.Reduced(trace_reduce.load(trace_dir))
        spans = pt.load_spans(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    lay = pt.ProgramLayers(red, spans, module, scopes)
    steps = sched.steps
    ms = lambda v: None if v is None else v / steps * 1e3  # noqa: E731
    out = {
        "seed": seed, "module": module, "serve_runs": lay.runs,
        "steps": steps, "window_s": red.window_s,
        "steps_per_s_untraced": w0.steps / wall0,
        "steps_per_s_traced": w.steps / wall,
    }
    for s in pt.SCOPES[:2] + ("search_prep", "top1_gather", "search_kernel",
                              "inherited"):
        out[f"{s}_ms_per_step"] = lay.scope_ms_per_run(s)
    out["unscoped_ms_per_step"] = lay.scope_ms_per_run(None)
    out["idle_in_admit_ms_per_step"] = ms(lay.idle_in_s("scheduler.admit"))
    out["idle_in_collect_ms_per_step"] = ms(lay.idle_in_s("scheduler.collect"))
    out["idle_by_span_ms_per_step"] = {k: ms(v) for k, v
                                       in lay.idle_by_span_s().items()}
    if sched.computed_slot_steps:
        out["slot_fill_share"] = (100.0 * sched.slot_steps
                                  / sched.computed_slot_steps)
    out["gaps"] = lay.gap_spans()
    out["scope_of"] = {name: scopes.get(name) for name, _ in red.top_ops()}
    ctx = {"reduced": red, "serve_runs": lay.runs, "serve_module": module,
           "notes": []}
    for m in ("fanout_ms_per_step", "search_ms_per_step", "device_idle_share"):
        v = spec.load_reader(m)(ctx)
        out[m] = v and v["value"]
    busy = red.busy_s()
    out["idle_ms_per_step"] = ms(red.window_s - busy) if busy else None
    if lay.runs:
        per_run = lambda v: sum(v) / len(v) / lay.runs * 1e3  # noqa: E731
        module_ms = per_run(red.module_s(module))
        out["module_ms_per_step"] = module_ms
        out["all_reduce_ms_per_step"] = per_run(red.op_s("all-reduce"))
        scoped = sum(out[f"{s}_ms_per_step"] or 0.0
                     for s in ("ota_bundle", "rx_copies", "search_prep",
                               "search_kernel", "top1_gather"))
        out["scoped_pct_of_module"] = 100.0 * scoped / module_ms
        out["scoped_own_pct_of_module"] = 100.0 * (
            scoped - (out["inherited_ms_per_step"] or 0.0)) / module_ms
    return out


def main(argv=None) -> int:
    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=run.TRACE_SECONDS)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if not args.rehearse:
        from repro.launch.cache import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            c = run.Cell(args.workload, seed, rehearse=args.rehearse)
        except run.NoChip as e:
            run.log(f"no result: {e}")
            return 2
        print(json.dumps({"workload": args.workload,
                          **readings(c, seed, args.seconds)}), flush=True)
        c.eng = c.pool = None
        del c
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
