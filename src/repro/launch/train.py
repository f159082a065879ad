"""Training launcher.

Single-host usage (CPU CI / smoke):
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b --smoke \
      --steps 50 --batch 8 --seq 256

Multi-host production notes (TPU pods; simulated single-process here):
* Each host runs this entrypoint under a supervisor (GKE/Borg restart policy).
  jax.distributed.initialize() wires hosts; the mesh comes from
  launch.mesh.make_production_mesh(multi_pod=...).
* **Fault tolerance**: checkpoints are atomic + keep-k (checkpoint/ckpt.py);
  on restart every host calls latest_step() and resumes; the data pipeline
  skips ahead in O(1) (data/pipeline.py — batch is a pure function of step).
  A lost host therefore costs at most `ckpt_every` steps of recompute.
* **Elasticity**: restore re-shards against whatever mesh the restarted job
  has (checkpoint stores dtypes/shapes; placement uses the rules engine), so
  the job can come back on fewer/more pods.
* **Straggler mitigation**: the supervisor enforces a per-step deadline
  (expected step time × 3); a host that misses it is killed and restarted —
  with synchronous SPMD collectives this is detected at the NCCL/ICI timeout.
  The sign_majority mode additionally shrinks the DP payload 32×, which bounds
  the collective window in which a straggler can stall the step.
* **Gradient compression**: --opt sign_majority enables the paper's OTA
  majority collective on gradients (optionally --ota-ber to inject the
  measured wireless error rate; see DESIGN.md).
"""
from __future__ import annotations

import argparse

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--opt", default="adamw", choices=["adamw", "sign_majority"])
    ap.add_argument("--ota-ber", type=float, default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    from repro import compat, configs
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import get_model
    from repro.train.loop import Trainer, TrainerConfig, build_train_fns
    from repro.train.optimizer import OptConfig

    enable_compile_cache()
    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    model = get_model(cfg)
    mesh = make_host_mesh()
    print(f"arch={cfg.name} devices={len(jax.devices())} mesh={dict(zip(mesh.axis_names, mesh.devices.shape))}")

    opt = OptConfig(kind=args.opt, lr=args.lr, warmup=10, total_steps=args.steps)
    fns = build_train_fns(model, mesh, opt, microbatch=args.microbatch, ota_ber=args.ota_ber)
    pipe = SyntheticLM(DataConfig(vocab=cfg.vocab, seq=args.seq, global_batch=args.batch))
    trainer = Trainer(
        fns, pipe,
        TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every, ckpt_dir=args.ckpt_dir),
        mesh,
    )
    with compat.set_mesh(mesh):
        _, _, losses = trainer.run(jax.random.PRNGKey(0))
    if losses:
        print(f"final loss {losses[-1]:.4f} (start {losses[0]:.4f})")
    else:
        print(f"nothing to do: checkpoint already at step {args.steps}")


if __name__ == "__main__":
    main()
