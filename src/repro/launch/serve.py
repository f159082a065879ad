"""Serving launcher: static one-shot generation or continuous-batching replay.

  # static batch, one compiled generate per prompt shape
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --batch 4 --prompt-len 64 --max-new 16

  # continuous batching: replay a synthetic Poisson request trace through the
  # scheduler (mixed prompt lengths, step-granular admission/eviction)
  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --stream --requests 32 --rate 8 --slots 4 --max-new 16

  # HDC-as-a-service: multi-tenant continuous batching over the OTA serve
  # path (tenant-tagged Poisson arrivals, one banked launch per step)
  PYTHONPATH=src python -m repro.launch.serve --hdc \
      --requests 64 --rate 200 --slots 8 --tenants 4 --hdc-batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def build_batch(cfg, key, batch_size: int, prompt_len: int) -> dict:
    batch = {"tokens": jax.random.randint(key, (batch_size, prompt_len), 0, cfg.vocab)}
    if cfg.kind == "encdec":
        batch["frames"] = 0.02 * jax.random.normal(
            key, (batch_size, cfg.enc_seq, cfg.d_model), cfg.dtype
        )
    if cfg.kind == "vlm":
        from repro.models import vlm as vlm_lib
        sv = 16
        batch["patch_embeds"] = 0.02 * jax.random.normal(
            key, (batch_size, sv, cfg.d_model), cfg.dtype
        )
        batch["positions"] = vlm_lib.default_positions(batch_size, sv, prompt_len, (4, 4))
    return batch


def run_static(args, cfg, model, params, key):
    from repro.serving import Engine, ServeConfig

    batch = build_batch(cfg, key, args.batch, args.prompt_len)
    eng = Engine(model, ServeConfig(max_new=args.max_new, temperature=args.temperature))
    t0 = time.time()
    toks = jax.block_until_ready(eng.generate(params, batch, key))
    t1 = time.time()
    toks2 = jax.block_until_ready(eng.generate(params, batch, key))  # warm
    t2 = time.time()
    print(f"generated {toks.shape} tokens; compile+run {t1-t0:.2f}s, warm {t2-t1:.3f}s "
          f"({args.batch*args.max_new/(t2-t1):.1f} tok/s)")
    print("sample:", jnp.asarray(toks2[0][:12]).tolist())


def run_stream(args, cfg, model, params):
    """Replay a synthetic Poisson trace through the continuous-batching path."""
    from repro.serving import ContinuousEngine, Scheduler, ServeConfig

    if args.prompt_lens:
        lengths = tuple(int(x) for x in args.prompt_lens.split(","))
    else:
        lengths = tuple(sorted({max(4, args.prompt_len // 2), args.prompt_len,
                                args.prompt_len * 2}))
    rng = np.random.default_rng(args.seed)
    req_lens = rng.choice(lengths, size=args.requests)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    prompts = [jnp.asarray(rng.integers(0, cfg.vocab, (int(L),)), jnp.int32)
               for L in req_lens]

    eng = ContinuousEngine(
        model, ServeConfig(max_new=args.max_new, temperature=args.temperature),
        num_slots=args.slots, max_prompt_len=max(lengths),
    )

    # Warm every compiled program (one prefill per length bucket, admit, step)
    # on a throwaway scheduler so the replay measures execution, not compiles.
    t0 = time.time()
    warm = Scheduler(eng, params)
    for L in lengths:
        warm.submit(jnp.zeros((int(L),), jnp.int32), max_new=min(2, args.max_new))
    warm.run(timeout=600)
    print(f"warmup: {len(eng._prefill_sigs)} prefill buckets + step/admit compiled "
          f"in {time.time()-t0:.1f}s")

    sched = Scheduler(eng, params)
    t0 = time.monotonic()
    nxt = 0
    while len(sched.results) < args.requests:
        now = time.monotonic() - t0
        while nxt < args.requests and arrivals[nxt] <= now:
            sched.submit(prompts[nxt])
            nxt += 1
        if sched.pending or sched.running:
            sched.step()
        elif nxt < args.requests:
            time.sleep(min(arrivals[nxt] - now, 0.01))
    wall = time.monotonic() - t0

    done = list(sched.results.values())
    n_tok = sum(len(c.tokens) for c in done)
    lat = np.asarray([c.latency for c in done])
    print(f"{args.requests} requests (lens {lengths}, rate {args.rate}/s, "
          f"{args.slots} slots): {wall:.2f}s wall, {n_tok} tokens, "
          f"{n_tok/wall:.1f} tok/s, {sched.steps} decode steps")
    print(f"request latency p50 {np.percentile(lat, 50)*1e3:.0f}ms  "
          f"p95 {np.percentile(lat, 95)*1e3:.0f}ms  max {lat.max()*1e3:.0f}ms")


def run_hdc_stream(args):
    """Multi-tenant HDC serving: tenant-tagged Poisson arrivals through the
    slot-ring ``HDCScheduler`` — every step one banked OTA serve launch."""
    from repro import phy
    from repro.compat import make_mesh
    from repro.core import classifier, hypervector as hv, scaleout
    from repro.serving import HDCEngine, HDCScheduler

    rep = "unpacked" if args.unpacked else "packed"
    cfg = scaleout.ScaleOutConfig(
        n_classes=args.classes, dim=args.dim, m_tx=3, n_rx_cores=8,
        batch=args.hdc_batch, representation=rep,
        noise="exact",
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    tcfg = classifier.HDCTaskConfig(n_classes=args.classes, dim=args.dim)
    books = classifier.make_tenant_codebooks(
        jax.random.PRNGKey(0), tcfg, args.tenants
    )
    state = phy.state_from_ber(jnp.full((cfg.n_rx_cores,), 0.02), cfg.m_tx)
    eng = HDCEngine(mesh, cfg, state, num_slots=args.slots,
                    max_tenants=args.tenants)
    for t in range(args.tenants):
        eng.registry.onboard(t, hv.pack(books[t]) if cfg.packed else books[t])

    rng = np.random.default_rng(args.seed)
    tenant_of = rng.integers(0, args.tenants, args.requests)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, size=args.requests))
    queries = [
        scaleout.make_queries(jax.random.PRNGKey(100 + i), cfg,
                              books[int(t)], 1)[1]
        for i, t in enumerate(tenant_of)
    ]

    # warm the serve step and the full-ring batched admit before replaying
    t0 = time.time()
    warm = HDCScheduler(eng)
    for _ in range(args.slots):
        warm.submit(0, queries[0])
    warm.run(timeout=600)
    print(f"warmup: mt serve + K={args.slots} admit compiled in "
          f"{time.time()-t0:.1f}s")

    sched = HDCScheduler(eng)
    t0 = time.monotonic()
    nxt = 0
    while len(sched.results) < args.requests:
        now = time.monotonic() - t0
        while nxt < args.requests and arrivals[nxt] <= now:
            sched.submit(int(tenant_of[nxt]), queries[nxt])
            nxt += 1
        if sched.pending or sched.running:
            sched.step()
        elif nxt < args.requests:
            time.sleep(min(arrivals[nxt] - now, 0.01))
    wall = time.monotonic() - t0

    lat = np.asarray([c.latency for c in sched.results.values()])
    n_trials = args.requests * cfg.batch
    print(f"{args.requests} requests x {cfg.batch} trials, {args.tenants} "
          f"tenants ({rep}, rate {args.rate}/s, {args.slots} slots): "
          f"{wall:.2f}s wall, {n_trials/wall:.0f} trials/s, "
          f"{sched.steps} serve steps")
    print(f"request latency p50 {np.percentile(lat, 50)*1e3:.0f}ms  "
          f"p95 {np.percentile(lat, 95)*1e3:.0f}ms  max {lat.max()*1e3:.0f}ms")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", help="LM architecture (required unless --hdc)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--stream", action="store_true",
                    help="continuous batching: replay a Poisson request trace")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=8.0, help="arrivals per second")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-lens", default="",
                    help="comma-separated prompt-length buckets (default: derived "
                         "from --prompt-len)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--hdc", action="store_true",
                    help="multi-tenant HDC serving over the OTA wire path")
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--hdc-batch", type=int, default=4,
                    help="(--hdc) trials per request")
    ap.add_argument("--classes", type=int, default=128)
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--unpacked", action="store_true",
                    help="(--hdc) elementwise representation instead of packed")
    args = ap.parse_args()

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.hdc:
        run_hdc_stream(args)
        return
    if not args.arch:
        raise SystemExit("--arch is required unless --hdc")

    from repro import configs
    from repro.models import get_model, init_params

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    model = get_model(cfg)
    key = jax.random.PRNGKey(0)
    params = init_params(key, model.specs)

    if args.stream:
        if cfg.kind != "decoder":
            raise SystemExit("--stream replay drives text prompts only (kind=decoder)")
        run_stream(args, cfg, model, params)
    else:
        run_static(args, cfg, model, params, key)


if __name__ == "__main__":
    main()
