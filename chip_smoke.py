"""Bring-up check of the HDC similarity service on a TPU, through its entry points.

One process, no children. With no arguments it needs one chip and runs, at the
WHYPE scale-out cell of ``launch/dryrun.py`` (C=102,400 classes, d=2048, M=3
transmitters, 1,024 RX cores, 100 classes per core; packed representation,
``psum_packed`` vote collective):

1. ``mt_serve`` — the multi-tenant serve through ``HDCEngine`` +
   ``HDCScheduler``: 8 tenants resident, 512 trials per slot, ``bsc`` channel
   at the precharacterized per-core BER; a few dozen requests, all collected.
2. ``reference`` — the standalone ``make_ota_serve`` at ``channel="ideal"``
   against ``scaleout.serve_reference`` on 256 trials: every prediction must
   match.

With ``--chips 4`` it runs only the four-chip path: a (1, 4) ``("data",
"model")`` mesh, so the vote collective spans the chips; the ``ideal`` serve
for ``psum``, ``psum_packed`` and ``rs_ag`` against ``serve_reference``, and the
``bsc`` serve on (1, 4) against the same serve on (1, 1) on the first chip.

Lines tagged ``[bring-up]`` are bring-up readings, not metrics. The last line
of standard output is ``{"ok": true, "device": {...}}``, printed only after
every phase passed on a TPU. The script exits non-zero, with no such line,
when no TPU is found, a prediction mismatches the reference, or the search is
not a Pallas kernel.

  python chip_smoke.py                 # one chip
  python chip_smoke.py --chips 4       # four chips
  JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # CPU rehearsal: every phase
      # at a toy size with the kernels in interpret mode; then exits 1 (no TPU)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

M_TX = 3
# the WHYPE scale-out cell (arXiv 2303.08067), as in launch/dryrun.py. Two
# slots: the compiled ring step takes ~4.8 GB of HBM temporaries per
# 512-trial slot (the bitplane BSC masks of 1,024 cores), so 8 slots (36 GB)
# and 4 slots (18 GB) do not fit one 16 GB v5e.
FULL = dict(classes=102_400, dim=2048, rx_cores=1024, tenants=8, slots=2,
            trials=512, requests=24, ref_trials=256)
TINY = dict(classes=1024, dim=256, rx_cores=8, tenants=8, slots=2,
            trials=8, requests=6, ref_trials=16)


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[bring-up] {msg}", flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def pallas_search_in(hlo_text: str) -> bool:
    """Whether a compiled program runs a fused top-1 search as a Mosaic
    kernel (interpret mode and the streamed fallback lower to plain HLO)."""
    return any("tpu_custom_call" in line and "topk_banked" in line
               for line in hlo_text.splitlines())


def place(tree, mesh):
    """Commit every leaf to `mesh`, replicated; the serve reshards."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(tree, NamedSharding(mesh, P()))


def channel_state(cfg):
    """Per-core link state from the paper's offline precharacterization (EM
    channel + OTA phase search), run on the host like the CST/MATLAB step."""
    import jax

    from repro.core import scaleout

    t0 = time.perf_counter()
    with jax.default_device(jax.devices("cpu")[0]):
        state = jax.block_until_ready(scaleout.precharacterize_state(cfg))
    say(f"precharacterize: {cfg.n_rx_cores} RX cores in "
        f"{time.perf_counter() - t0:.1f} s on the host")
    return state


def base_cfg(size, collective="psum_packed", **kw):
    from repro.core import scaleout

    return scaleout.ScaleOutConfig(
        n_classes=size["classes"], dim=size["dim"], m_tx=M_TX,
        n_rx_cores=size["rx_cores"], representation="packed",
        collective=collective, noise="bitplane", **kw,
    )


def codebook(key, size):
    from repro.core import classifier

    tcfg = classifier.HDCTaskConfig(n_classes=size["classes"], dim=size["dim"])
    return classifier.make_codebook(key, tcfg)


def mt_serve_phase(size, mesh, state, key) -> bool:
    """The multi-tenant ring through HDCEngine + HDCScheduler; returns
    whether its compiled step searches with the Pallas kernel."""
    import jax
    import numpy as np

    from repro.core import hypervector as hv, scaleout
    from repro.serving import HDCEngine, HDCScheduler

    cfg = base_cfg(size, batch=size["trials"], channel="bsc")
    state = place(state, mesh)
    eng = HDCEngine(mesh, cfg, state, num_slots=size["slots"],
                    max_tenants=size["tenants"])
    n_req = size["requests"]
    tenant_of = [r % size["tenants"] for r in range(n_req)]
    queries, classes = {}, {}
    for t in range(size["tenants"]):
        book = codebook(jax.random.fold_in(key, t), size)
        eng.registry.onboard(t, hv.pack(book))
        for r in range(n_req):
            if tenant_of[r] == t:
                cls, q = scaleout.make_queries(
                    jax.random.fold_in(key, 1000 + r), cfg, book, 1)
                queries[r], classes[r] = q, np.asarray(cls)

    init = eng.init_state()
    t0 = time.perf_counter()
    compiled = scaleout.make_mt_ota_serve(mesh, cfg).lower(
        eng.registry.store, init["queries"], init["row"], state, init["key"]
    ).compile()
    compile_s = time.perf_counter() - t0
    kernel = pallas_search_in(compiled.as_text())

    t0 = time.perf_counter()
    warm = HDCScheduler(eng)
    for _ in range(size["slots"]):
        warm.submit(0, queries[0])
    warm.run(timeout=900)
    first_s = time.perf_counter() - t0

    sched = HDCScheduler(eng)
    rids = {sched.submit(tenant_of[r], queries[r],
                         key=jax.random.fold_in(key, 2000 + r)): r
            for r in range(n_req)}
    t0 = time.perf_counter()
    results = sched.run(timeout=900)
    warm_s = time.perf_counter() - t0

    check(len(results) == n_req, f"{len(results)} of {n_req} requests came back")
    hits = 0
    for rid, r in rids.items():
        done = results[rid]
        check(done.status == "ok", f"request {rid}: status {done.status}")
        check(done.pred.shape == (cfg.batch,), f"request {rid}: pred {done.pred.shape}")
        check(bool(np.all((done.pred >= 0) & (done.pred < cfg.n_classes))),
              f"request {rid}: prediction out of range")
        check(bool(np.all(np.isfinite(done.maxsim))), f"request {rid}: non-finite maxsim")
        hits += int(np.sum(np.any(done.pred[:, None] == classes[r], axis=1)))
    hit_rate = hits / (n_req * cfg.batch)
    say(f"mt_serve: compile {compile_s:.1f} s (serve step, AOT), first ring "
        f"pass {first_s:.1f} s (admit + serve compile + run), warm "
        f"{warm_s:.2f} s for {n_req} requests x {cfg.batch} trials over "
        f"{sched.steps} steps ({n_req * cfg.batch / warm_s:.0f} trials/s, "
        "bring-up reading)")
    say(f"mt_serve: {size['tenants']} tenants resident, {size['slots']} slots; "
        f"bsc top-1 hit rate {hit_rate:.4f} (pred among the {M_TX} bundled "
        f"classes); Pallas search in compiled HLO: {kernel}")
    return kernel


def ideal_vs_reference(size, mesh, state, key, collective) -> bool:
    """Standalone serve at channel="ideal" against the single-device oracle;
    returns whether its compiled program searches with the Pallas kernel."""
    import jax
    import numpy as np

    from repro.core import hypervector as hv, scaleout

    cfg = base_cfg(size, batch=size["ref_trials"], channel="ideal",
                   collective=collective)
    model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
    book = codebook(jax.random.fold_in(key, 7), size)
    _, q = scaleout.make_queries(jax.random.fold_in(key, 8), cfg, book,
                                 model_size)
    protos = hv.pack(book)
    serve = scaleout.make_ota_serve(mesh, cfg)
    args = place((protos, q, state, jax.random.fold_in(key, 9)), mesh)
    t0 = time.perf_counter()
    compiled = serve.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    kernel = pallas_search_in(compiled.as_text())
    t0 = time.perf_counter()
    pred, maxsim = jax.block_until_ready(serve(*args))
    run_s = time.perf_counter() - t0
    ref_pred, ref_maxsim = scaleout.serve_reference(cfg, protos, q)
    mism = int(np.sum(np.asarray(pred) != np.asarray(ref_pred)))
    dsim = float(np.max(np.abs(np.asarray(maxsim) - np.asarray(ref_maxsim))))
    say(f"reference[{collective}, mesh {tuple(mesh.devices.shape)}]: compile "
        f"{compile_s:.1f} s, run {run_s:.3f} s; {mism} of {cfg.batch} "
        f"predictions differ from serve_reference (max |maxsim diff| "
        f"{dsim:.3g}); Pallas search in compiled HLO: {kernel}")
    check(mism == 0, f"{mism} predictions differ from serve_reference "
                     f"({collective}, mesh {tuple(mesh.devices.shape)})")
    return kernel


def bsc_placement(size, mesh4, mesh1, state, key) -> None:
    """bsc serve on (1, 4) against (1, 1): per-core noise folds the global
    core id into the key, so placement must not change a prediction."""
    import jax
    import numpy as np

    from repro.core import hypervector as hv, scaleout

    book = codebook(jax.random.fold_in(key, 11), size)
    protos = hv.pack(book)
    preds = {}
    for tier in ("bsc", "ideal"):
        cfg = base_cfg(size, batch=size["ref_trials"], channel=tier)
        for mesh in (mesh4, mesh1):
            s = mesh.axis_sizes[mesh.axis_names.index("model")]
            _, q = scaleout.make_queries(jax.random.fold_in(key, 12), cfg,
                                         book, s)
            args = place((protos, q, state, jax.random.fold_in(key, 13)), mesh)
            pred, _ = scaleout.make_ota_serve(mesh, cfg)(*args)
            preds[tier, s] = np.asarray(pred)
    diff = int(np.sum(preds["bsc", 4] != preds["bsc", 1]))
    if diff:
        stage = ("the OTA bundle or the search" if
                 np.any(preds["ideal", 4] != preds["ideal", 1])
                 else "the PHY fan-out (per-core noise)")
        say(f"bsc (1,4) vs (1,1): {diff} of {size['ref_trials']} predictions "
            f"differ; the stage that depends on placement is {stage}")
    else:
        say(f"bsc (1,4) vs (1,1): all {size['ref_trials']} predictions equal")


def device_memory(devs) -> None:
    """Peak bytes per device: a path that only ran on fake CPU devices may
    have put everything on device 0."""
    for i, d in enumerate(devs):
        stats = d.memory_stats()
        if stats is None:
            say(f"device {i}: memory stats not reported by this backend")
            continue
        peak = stats.get("peak_bytes_in_use", 0)
        say(f"device {i}: peak {peak} bytes in use")
        check(peak > 0, f"device {i} held nothing")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="toy size; runs every phase on any backend, then "
                         "refuses to report ok without a TPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    from repro.compat import make_mesh
    from repro.launch.cache import enable_compile_cache

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    say(f"device: platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu" and not args.tiny:
        print("no TPU found: nothing to bring up", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 1
    say(f"compile cache: {enable_compile_cache()}")
    size = TINY if args.tiny else FULL
    key = jax.random.PRNGKey(args.seed)
    mesh1 = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    state = channel_state(base_cfg(size))

    try:
        if args.chips == 1:
            found = [mt_serve_phase(size, mesh1, state, key),
                     ideal_vs_reference(size, mesh1, state, key, "psum_packed")]
        else:
            mesh4 = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
            found = [ideal_vs_reference(size, mesh4, state, key, c)
                     for c in ("psum", "psum_packed", "rs_ag")]
            bsc_placement(size, mesh4, mesh1, state, key)
            device_memory(devs[:4])
        if dev["platform"] == "tpu":
            check(all(found), "a serve's search is not a Pallas kernel")
    except (SmokeFailure, TimeoutError) as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    if dev["platform"] != "tpu":
        print("rehearsal passed, but no TPU: no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
