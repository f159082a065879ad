"""Distributed training loop: GSPMD train step + fault-tolerant host runner.

Two gradient-synchronization modes:

* "dense"          — standard: autodiff over the globally-sharded loss; GSPMD
                     inserts the f32/bf16 gradient all-reduces implied by the
                     parameter shardings. Grad-accum microbatching via lax.scan.
* "sign_majority"  — the paper's OTA collective applied to training: per-device
                     gradients are computed inside a shard_map over the data/pod
                     axes (model axes stay auto/GSPMD), 1-bit sign-quantized and
                     majority-voted (`sign_allreduce`), optionally through the
                     OTA BER channel. 32× less DP traffic; parameters are kept
                     replicated across dp axes in this mode (FSDP rules are
                     stripped — the honest trade, see DESIGN.md).

The host-level `Trainer` adds checkpoint/restart (atomic keep-k), O(1)
data skip-ahead on resume, and a failure-injection hook used by the
fault-tolerance tests. Straggler mitigation and multi-host watchdog behaviour
are documented in launch/train.py (single-process simulation here).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.distributed import collectives
from repro.distributed.sharding import (
    DEFAULT_RULES,
    spec_for_shape,
    tree_shardings,
    use_rules,
)
from repro.models.base import init_params, param_axes, param_shapes
from repro.train import optimizer as opt_lib


def merged_rules(cfg) -> dict:
    return dict(DEFAULT_RULES) | dict(getattr(cfg, "rules_override", {}) or {})


def _strip_dp(rules: dict) -> dict:
    """Remove pod/data mesh axes from every rule (sign_majority mode: params and
    therefore grads must be identical along dp axes up to the batch shard)."""
    def strip(v):
        if v is None:
            return None
        axes = (v,) if isinstance(v, str) else tuple(v)
        kept = tuple(a for a in axes if a not in ("pod", "data"))
        return kept[0] if len(kept) == 1 else (kept or None)
    out = {k: strip(v) for k, v in rules.items()}
    out["batch"] = ("pod", "data")       # batch stays data-parallel
    out["moe_groups"] = ("pod", "data")
    out["fsdp"] = None
    return out


def _dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


@dataclasses.dataclass
class TrainFns:
    step: Callable          # (params, opt_state, batch, key) -> (params, opt_state, metrics)
    init: Callable          # (key) -> (params, opt_state)
    param_shardings: Any
    opt_shardings: Any
    batch_spec: Callable    # shapes dict -> shardings dict
    rules: dict


def build_train_fns(
    model,
    mesh: Mesh,
    opt_cfg: opt_lib.OptConfig,
    *,
    microbatch: int = 1,
    ota_ber: float | None = None,
    jit: bool = True,
) -> TrainFns:
    cfg = model.cfg
    rules = merged_rules(cfg)
    if opt_cfg.kind == "sign_majority":
        rules = _strip_dp(rules)
    p_axes = param_axes(model.specs)
    p_shapes = param_shapes(model.specs)
    param_shardings = tree_shardings(mesh, p_shapes, p_axes, rules)
    dp = _dp_axes(mesh)

    def batch_sharding(shapes: dict, axes: dict):
        return {
            k: NamedSharding(mesh, spec_for_shape(axes[k], shapes[k].shape, rules, mesh))
            for k in shapes
        }

    # ---------------- loss/grad ----------------
    def loss_fn(params, batch):
        loss, metrics = model.loss_fn(params, batch)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def accumulate(params, batch):
        """Microbatch grad accumulation via lax.scan over the batch split."""
        if microbatch == 1:
            (loss, metrics), grads = grad_fn(params, batch)
            return loss, metrics, grads
        def split(x):
            b = x.shape[0]
            assert b % microbatch == 0
            return x.reshape((microbatch, b // microbatch) + x.shape[1:])
        mb = jax.tree.map(split, batch)
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, xs):
            g_acc, l_acc = acc
            (loss, metrics), grads = grad_fn(params, xs)
            g_acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32) / microbatch, g_acc, grads)
            return (g_acc, l_acc + loss / microbatch), metrics

        (grads, loss), metrics = jax.lax.scan(body, (zeros, 0.0), mb)
        metrics = jax.tree.map(lambda m: m[-1], metrics)
        return loss, metrics, grads

    # ---------------- step ----------------
    if opt_cfg.kind == "adamw":
        def step(params, opt_state, batch, key):
            del key
            with use_rules(rules):
                loss, metrics, grads = accumulate(params, batch)
                new_params, new_state, om = opt_lib.adamw_update(opt_cfg, grads, opt_state, params)
            return new_params, new_state, {"loss": loss, **metrics, **om}

        def init(key):
            params = init_params(key, model.specs)
            return params, opt_lib.adamw_init(opt_cfg, params)

        opt_state_axes = {
            "m": opt_lib.zero1_axes(p_axes),
            "v": opt_lib.zero1_axes(p_axes),
            "step": (),
        }
    elif opt_cfg.kind == "sign_majority":
        # The dp axes go manual; model axes stay auto (GSPMD shards the
        # per-device gradient compute). In-body activation constraints may
        # name only auto axes, so the batch axes resolve to replicated there.
        dp_spec = P(dp if len(dp) > 1 else dp[0])
        body_rules = rules | {"batch": None, "moe_groups": None}

        def per_device(params, batch, key):
            with use_rules(body_rules):
                loss, metrics, grads = accumulate(params, batch)
            votes = jax.tree.map(
                lambda g: collectives.sign_allreduce(g, dp, key=key, ber=ota_ber),
                grads,
            )
            loss = jax.lax.pmean(loss, dp)
            return votes, loss, metrics

        def step(params, opt_state, batch, key):
            batch_specs = jax.tree.map(lambda x: dp_spec, batch)
            votes, loss, metrics = compat.shard_map(
                per_device,
                mesh=mesh,
                in_specs=(P(), batch_specs, P()),
                out_specs=(P(), P(), P()),
                axis_names=set(dp),
                check_vma=False,
            )(params, batch, key)
            with use_rules(rules):
                new_params, new_state, om = opt_lib.sign_update(opt_cfg, votes, opt_state, params)
            return new_params, new_state, {"loss": loss, **metrics, **om}

        def init(key):
            params = init_params(key, model.specs)
            return params, opt_lib.sign_init(opt_cfg, params)

        opt_state_axes = {"mom": opt_lib.zero1_axes(p_axes), "step": ()}
    else:
        raise ValueError(opt_cfg.kind)

    opt_shardings = {
        k: (tree_shardings(mesh, p_shapes, v, rules) if k != "step" else NamedSharding(mesh, P()))
        for k, v in opt_state_axes.items()
    }

    if jit:
        step = jax.jit(step, donate_argnums=(0, 1))
    return TrainFns(step, init, param_shardings, opt_shardings, batch_sharding, rules)


# ---------------------------------------------------------------------------
# fault-tolerant host runner
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 25
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    log_every: int = 10


class Trainer:
    """Single-process simulation of the multi-host runner.

    On a real cluster each host runs this loop under a watchdog (see
    launch/train.py): a crashed/straggling host is restarted and rejoins at the
    latest checkpoint; the data pipeline skips ahead in O(1).
    """

    def __init__(self, fns: TrainFns, pipeline, tcfg: TrainerConfig, mesh: Mesh):
        self.fns = fns
        self.pipeline = pipeline
        self.tcfg = tcfg
        self.mesh = mesh

    def run(self, key: jax.Array, fail_at: int | None = None, quiet: bool = False):
        from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint

        tcfg = self.tcfg
        start = latest_step(tcfg.ckpt_dir)
        if start is not None:
            like = jax.eval_shape(lambda k: self.fns.init(k), key)
            shardings = (self.fns.param_shardings, self.fns.opt_shardings)
            (params, opt_state), extra = restore_checkpoint(
                tcfg.ckpt_dir, start, like, shardings
            )
            step0 = int(extra["data_step"])
        else:
            params, opt_state = self.fns.init(key)
            step0 = 0

        losses = []
        for step in range(step0, tcfg.steps):
            if fail_at is not None and step == fail_at:
                raise RuntimeError(f"injected failure at step {step}")
            batch = self.pipeline.batch(step)
            params, opt_state, metrics = self.fns.step(params, opt_state, batch, key)
            losses.append(float(metrics["loss"]))
            if not quiet and (step % tcfg.log_every == 0 or step == tcfg.steps - 1):
                print(f"step {step:5d}  loss {losses[-1]:.4f}  lr {float(metrics['lr']):.2e}")
            if (step + 1) % tcfg.ckpt_every == 0 or step == tcfg.steps - 1:
                save_checkpoint(
                    tcfg.ckpt_dir, step + 1, (params, opt_state),
                    extra={"data_step": step + 1}, keep=tcfg.keep,
                )
        return params, opt_state, losses
