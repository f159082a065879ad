import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture × input-shape) cell on
the production meshes and record roofline inputs.

The lines above run before ANY other import — jax locks the platform and the
device count on first init, and the dry-run needs 512 placeholder CPU devices
to build the (2, 16, 16) multi-pod mesh. The dry-run is a CPU tool: pinning
the platform keeps it (and every child of ``--all``, which inherits the
environment) off an attached TPU, which one process at a time may hold.
Smoke tests and benchmarks must NOT import this module (they see the real
single CPU device).

Per cell this produces benchmarks/artifacts/dryrun/<mesh>/<arch>__<cell>.json:
  * compiled.memory_analysis()  — bytes/device (proves the sharding fits or not);
  * normalized_cost_analysis()  — raw XLA numbers (scan bodies counted once);
  * analysis.hlo_cost.analyze() — trip-count-scaled per-device FLOPs / HBM bytes /
    collective bytes by type (the §Roofline inputs);
  * params, MODEL_FLOPS, timings.

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --cell train_4k --multi-pod
  python -m repro.launch.dryrun --all --jobs 8          # full 40-cell sweep, both meshes
  python -m repro.launch.dryrun --arch hdc-scaleout --cell serve   # paper system
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "benchmarks", "artifacts", "dryrun")


def lower_cell(arch: str, cell_name: str, multi_pod: bool, opt_kind: str = "adamw",
               flash_vjp: bool = True, uneven_heads: bool = False,
               capacity_factor: float | None = None, expand_kv: bool = False) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.models import layers as _layers
    _layers.FLASH_CUSTOM_VJP = flash_vjp
    _layers.EXPAND_KV_EARLY = expand_kv
    _layers.FLASH_P_BF16 = bool(int(os.environ.get("REPRO_FLASH_P_BF16", "0")))
    _layers.REDUCE_BF16 = bool(int(os.environ.get("REPRO_REDUCE_BF16", "0")))
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import compat, configs
    from repro.analysis import hlo_cost, roofline
    from repro.configs.shapes import CELLS, cell_applicable, input_specs
    from repro.distributed.sharding import spec_for_shape, tree_shardings, use_rules
    from repro.launch.mesh import make_production_mesh
    from repro.models import get_model
    from repro.models.base import count_params, param_axes, param_shapes
    from repro.train.loop import build_train_fns, merged_rules
    from repro.train.optimizer import OptConfig

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    if arch in ("hdc-scaleout", "hdc_scaleout"):
        return _lower_hdc(cell_name, mesh, chips, t0)

    cfg = configs.get_config(arch)
    model = get_model(cfg)
    cell = CELLS[cell_name]
    ok, why = cell_applicable(cfg, cell)
    if not ok:
        return {"arch": arch, "cell": cell_name, "status": "skipped", "why": why}

    if capacity_factor is not None and cfg.moe is not None:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, capacity_factor=capacity_factor))
        model = get_model(cfg)
    kind, shapes, axes = input_specs(cfg, cell)
    rules = merged_rules(cfg)
    rules_act = rules
    if uneven_heads:
        # uneven (padded) sharding is legal for with_sharding_constraint inside
        # the program but not for jit in_shardings -> only activations get it.
        rules_act = dict(rules) | {"__uneven__": ("heads",)}
    p_shapes = param_shapes(model.specs)
    p_axes = param_axes(model.specs)
    n_params = count_params(model.specs)

    with compat.set_mesh(mesh), use_rules(rules_act):
        p_sh = tree_shardings(mesh, p_shapes, p_axes, rules)
        b_sh = {
            k: NamedSharding(mesh, spec_for_shape(axes[k], shapes[k].shape, rules, mesh))
            for k in shapes
        }
        if kind == "train":
            state_dtype = jnp.bfloat16 if n_params > 2e11 else jnp.float32
            opt = OptConfig(kind=opt_kind, state_dtype=state_dtype)
            fns = build_train_fns(model, mesh, opt, jit=False)
            key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
            _, o_struct = jax.eval_shape(fns.init, key_s)
            o_sh = fns.opt_shardings
            jitted = jax.jit(
                fns.step,
                in_shardings=(fns.param_shardings, o_sh, b_sh, NamedSharding(mesh, P())),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(p_shapes, o_struct, shapes, key_s)
        elif kind == "prefill":
            jitted = jax.jit(model.prefill_fn, in_shardings=(p_sh, b_sh))
            lowered = jitted.lower(p_shapes, shapes)
        else:  # decode
            cache_shapes, cache_axes = model.cache_specs_fn(cell.batch, cell.seq)
            c_sh = tree_shardings(mesh, cache_shapes, cache_axes, rules)
            tok_sh = NamedSharding(mesh, spec_for_shape(("batch",), (cell.batch,), rules, mesh))
            jitted = jax.jit(
                model.decode_fn,
                in_shardings=(p_sh, c_sh, tok_sh, NamedSharding(mesh, P())),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(
                p_shapes, cache_shapes,
                jax.ShapeDtypeStruct((cell.batch,), jnp.int32),
                jax.ShapeDtypeStruct((), jnp.int32),
            )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compat.normalized_cost_analysis(compiled)
    hc = hlo_cost.analyze(compiled.as_text())
    mf = roofline.model_flops(cfg, cell, n_params)
    rl = roofline.roofline_terms(hc.flops, hc.hbm_bytes, hc.coll_total, chips=1)  # per-device
    rec = {
        "arch": arch,
        "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "chips": chips,
        "params": n_params,
        "memory_analysis": {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_in_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_size_in_bytes": getattr(mem, "alias_size_in_bytes", None),
            "generated_code_size_in_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "cost_analysis_raw": {
            "flops": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        },
        "hlo_per_device": {
            "flops": hc.flops,
            "hbm_bytes": hc.hbm_bytes,
            "collective": hc.collective,
            "raw_flops_single_trip": hc.raw_flops,
        },
        "model_flops_global": mf,
        "roofline_s": {
            "compute": rl.compute_s,
            "memory": rl.memory_s,
            "collective": rl.collective_s,
            "dominant": rl.dominant,
        },
        "useful_flops_ratio": mf / max(hc.flops * chips, 1.0),
        "t_lower_s": round(t_lower, 1),
        "t_compile_s": round(t_compile, 1),
    }
    return rec


def _lower_hdc(cell_name: str, mesh, chips: int, t0: float) -> dict:
    """Paper-system dry-run: OTA serve (+wired baseline) and HDC one-shot train."""
    import jax
    import jax.numpy as jnp

    from repro import phy
    from repro.analysis import hlo_cost
    from repro.core import scaleout

    packed = cell_name.endswith("_packed")
    base = cell_name[: -len("_packed")] if packed else cell_name
    collective = {"serve_rsag": "rs_ag", "serve_psumpacked": "psum_packed"}.get(
        base, "psum"
    )
    # multi-tenant serve: 8 resident tenants x 8 slots of 512 trials each —
    # the same 4096-trial wire load as the single-tenant serve cells, issued
    # as ONE banked launch
    SLOTS = TENANTS = 8
    mt = base == "serve_hdc_multitenant"
    # ultra-sparse serve: million-dimension HVs at ~0.2% density — queries are
    # k_max sorted int32 index lists, the wire is the index_ag all-gather, the
    # prototype store stays packed. There is no _packed variant: sparse IS its
    # own representation (and its prototypes are always packed words).
    sparse_cell = base == "serve_sparse"
    cfg = scaleout.ScaleOutConfig(
        n_classes=102_400, dim=1_048_576 if sparse_cell else 2048,
        m_tx=3, n_rx_cores=1024,
        batch=512 if mt else 4096,
        use_kernels=False,
        collective="index_ag" if sparse_cell else collective,
        representation=("sparse" if sparse_cell
                        else "packed" if packed else "unpacked"),
        k_max=2048 if sparse_cell else 0,
        noise="bitplane",
        channel="symbol" if base in ("serve_symbol", "serve_adaptive")
        else "bsc",
        # coarse-to-fine at WHYPE scale: c_core=100 rows/core screened as 10
        # strict-majority group summaries, exact rescore on the best 4 groups
        **({"coarse_group": 10, "coarse_keep": 4} if base == "serve_topk"
           else {}),
    )
    model_size = mesh.axis_sizes[mesh.axis_names.index("model")]
    e_per = -(-cfg.m_tx // model_size)
    hv_last = cfg.words if packed else cfg.dim
    hv_dtype = jnp.uint32 if packed else jnp.uint8
    n_trials = cfg.batch * (SLOTS if mt else 1)
    if mt:
        fn = scaleout.make_mt_ota_serve(mesh, cfg)
        args = (
            jax.ShapeDtypeStruct((TENANTS, cfg.n_classes, hv_last), hv_dtype),
            jax.ShapeDtypeStruct(
                (SLOTS, cfg.batch, model_size, e_per, hv_last), hv_dtype
            ),
            jax.ShapeDtypeStruct((SLOTS,), jnp.int32),
            phy.state_shape_structs(cfg.n_rx_cores, cfg.m_tx),
            jax.ShapeDtypeStruct((SLOTS, 2), jnp.uint32),
        )
    elif base == "serve_adaptive":
        # living-channel serve: one ChannelProcess tick (phase drift + guard
        # monitor) fused ahead of the symbol-tier serve under shard_map — the
        # cell that catches ProcessState sharding-spec regressions at the
        # production 1024-core scale
        fn = scaleout.make_ota_serve(
            mesh, cfg, process=phy.PhaseDriftProcess(guard_dims=64)
        )
        args = (
            jax.ShapeDtypeStruct((cfg.n_classes, hv_last), hv_dtype),
            jax.ShapeDtypeStruct((cfg.batch, model_size, e_per, hv_last), hv_dtype),
            phy.pstate_shape_structs(cfg.n_rx_cores, cfg.m_tx),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
    elif base == "serve_faulty":
        # fault-injected serve: one static-fault tick + erasure-aware votes +
        # stuck-at masks + the serve_rows failover gather fused under
        # shard_map — the cell that catches FaultState sharding-spec
        # regressions at the production 1024-core scale
        from repro import faults
        fn = scaleout.make_ota_serve(
            mesh, cfg, faults=faults.StaticFaults()
        )
        m_slots = model_size * e_per
        args = (
            jax.ShapeDtypeStruct((cfg.n_classes, hv_last), hv_dtype),
            jax.ShapeDtypeStruct((cfg.batch, model_size, e_per, hv_last), hv_dtype),
            phy.state_shape_structs(cfg.n_rx_cores, cfg.m_tx),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            faults.fstate_shape_structs(cfg.n_rx_cores, m_slots, cfg.words),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
    elif base == "serve_sparse":
        if packed:
            return {"arch": "hdc-scaleout", "cell": cell_name,
                    "status": "skipped",
                    "why": "serve_sparse has no _packed variant — sparse is "
                           "its own representation (packed prototype words, "
                           "int32 index-list queries)"}
        fn = scaleout.make_ota_serve(mesh, cfg)
        args = (
            jax.ShapeDtypeStruct((cfg.n_classes, cfg.words), jnp.uint32),
            jax.ShapeDtypeStruct(
                (cfg.batch, model_size, e_per, cfg.k_max), jnp.int32
            ),
            phy.state_shape_structs(cfg.n_rx_cores, cfg.m_tx),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
    elif base in ("serve", "serve_wired", "serve_rsag", "serve_psumpacked",
                  "serve_symbol", "serve_topk"):
        fn = (scaleout.make_wired_serve if base == "serve_wired"
              else scaleout.make_ota_serve)(mesh, cfg)
        args = (
            jax.ShapeDtypeStruct((cfg.n_classes, hv_last), hv_dtype),
            jax.ShapeDtypeStruct((cfg.batch, model_size, e_per, hv_last), hv_dtype),
            phy.state_shape_structs(cfg.n_rx_cores, cfg.m_tx),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
    elif base == "train":
        fn = scaleout.make_hdc_train(mesh, cfg)
        args = (
            jax.ShapeDtypeStruct((cfg.batch, hv_last), hv_dtype),
            jax.ShapeDtypeStruct((cfg.batch,), jnp.int32),
        )
    else:
        return {"arch": "hdc-scaleout", "cell": cell_name, "status": "skipped",
                "why": "cells: serve | serve_psumpacked | serve_rsag |"
                       " serve_symbol | serve_topk | serve_adaptive |"
                       " serve_faulty | serve_wired | serve_hdc_multitenant |"
                       " train (each also as <cell>_packed) | serve_sparse"}
    lowered = fn.lower(*args)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    hc = hlo_cost.analyze(compiled.as_text())
    return {
        "arch": "hdc-scaleout", "cell": cell_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "status": "ok", "chips": chips,
        "config": {"classes": cfg.n_classes, "dim": cfg.dim, "m_tx": cfg.m_tx,
                   "rx_cores": cfg.n_rx_cores, "batch": cfg.batch,
                   "representation": cfg.representation,
                   "collective": cfg.collective,
                   "channel": cfg.channel,
                   **({"k_max": cfg.k_max} if cfg.sparse else {}),
                   **({"coarse_group": cfg.coarse_group,
                       "coarse_keep": cfg.coarse_keep}
                      if cfg.coarse_group else {}),
                   **({"slots": SLOTS, "tenants": TENANTS} if mt else {})},
        "memory_analysis": {
            "argument_size_in_bytes": getattr(mem, "argument_size_in_bytes", None),
            "temp_size_in_bytes": getattr(mem, "temp_size_in_bytes", None),
        },
        "hlo_per_device": {
            "flops": hc.flops, "hbm_bytes": hc.hbm_bytes, "collective": hc.collective,
            "collective_bytes": hc.coll_total,
            "collective_bytes_per_trial": hc.coll_total / n_trials,
            "hbm_bytes_per_trial": hc.hbm_bytes / n_trials,
        },
        "t_lower_s": round(t_lower, 1), "t_compile_s": round(t_compile, 1),
    }


def _out_path(arch, cell, multi_pod, tag=""):
    mesh = ("pod2" if multi_pod else "pod1") + (f"-{tag}" if tag else "")
    d = os.path.abspath(os.path.join(ARTIFACTS, mesh))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{arch.replace('/', '_')}__{cell}.json")


def run_one(arch, cell, multi_pod, force=False, tag="", flash_vjp=True,
            uneven_heads=False, capacity_factor=None, expand_kv=False):
    path = _out_path(arch, cell, multi_pod, tag)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    try:
        rec = lower_cell(arch, cell, multi_pod, flash_vjp=flash_vjp,
                         uneven_heads=uneven_heads, capacity_factor=capacity_factor,
                         expand_kv=expand_kv)
    except Exception as e:  # a failure here is a bug in the sharding config
        rec = {"arch": arch, "cell": cell, "status": "error",
               "mesh": "2x16x16" if multi_pod else "16x16",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true", help="all archs x cells x both meshes")
    ap.add_argument("--jobs", type=int, default=4)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="", help="artifact subdir suffix (perf variants)")
    ap.add_argument("--flash-vjp", type=int, default=1)
    ap.add_argument("--uneven-heads", type=int, default=0)
    ap.add_argument("--capacity-factor", type=float, default=None)
    ap.add_argument("--expand-kv", type=int, default=0)
    args = ap.parse_args()

    if not args.all:
        rec = run_one(args.arch, args.cell, args.multi_pod, force=args.force,
                      tag=args.tag, flash_vjp=bool(args.flash_vjp),
                      uneven_heads=bool(args.uneven_heads),
                      capacity_factor=args.capacity_factor,
                      expand_kv=bool(args.expand_kv))
        print(json.dumps({k: v for k, v in rec.items() if k != "traceback"}, indent=1))
        if rec["status"] == "error":
            print(rec.get("traceback", ""), file=sys.stderr)
            sys.exit(1)
        return

    from repro import configs as _c
    from repro.configs.shapes import CELLS as _cells

    jobs = []
    for multi_pod in (False, True):
        for arch in _c.ARCHS:
            for cell in _cells:
                jobs.append((arch.replace("_", "-"), cell, multi_pod))
        for cell in ("serve", "serve_psumpacked", "serve_rsag", "serve_symbol",
                     "serve_topk", "serve_adaptive", "serve_faulty",
                     "serve_wired", "serve_hdc_multitenant",
                     "train", "serve_packed", "serve_psumpacked_packed",
                     "serve_rsag_packed", "serve_symbol_packed",
                     "serve_topk_packed", "serve_adaptive_packed",
                     "serve_faulty_packed", "serve_wired_packed",
                     "serve_hdc_multitenant_packed", "train_packed",
                     "serve_sparse"):
            jobs.append(("hdc-scaleout", cell, multi_pod))

    pending = [j for j in jobs if args.force or not os.path.exists(_out_path(*j, tag=args.tag))]
    print(f"{len(jobs)} cells total, {len(pending)} to run, jobs={args.jobs}")
    procs: list[tuple[subprocess.Popen, tuple]] = []
    results = []
    while pending or procs:
        while pending and len(procs) < args.jobs:
            arch, cell, mp = pending.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch, "--cell", cell,
                   "--flash-vjp", str(args.flash_vjp)]
            if args.tag:
                cmd += ["--tag", args.tag]
            if mp:
                cmd.append("--multi-pod")
            if args.force:
                cmd.append("--force")
            p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
            procs.append((p, (arch, cell, mp)))
        for p, meta in procs[:]:
            if p.poll() is not None:
                procs.remove((p, meta))
                arch, cell, mp = meta
                path = _out_path(arch, cell, mp, tag=args.tag)
                status = "?"
                if os.path.exists(path):
                    with open(path) as f:
                        status = json.load(f).get("status")
                results.append((meta, status))
                print(f"[{len(results)}/{len(jobs)}] {arch} {cell} {'pod2' if mp else 'pod1'}: {status}")
        time.sleep(1.0)
    bad = [r for r in results if r[1] not in ("ok", "skipped")]
    print(f"done: {len(results)} ran, {len(bad)} errors")
    for meta, st in bad:
        print("  ERROR:", meta)


if __name__ == "__main__":
    main()
