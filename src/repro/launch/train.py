"""Fault-tolerant training driver.

``python -m repro.launch.train --arch <id> [--smoke] --steps N``

The loop is restart-safe: state lives in step-atomic checkpoints
(repro.ckpt); on start it resumes from the newest manifest; the data
pipeline is a pure function of (seed, step) so no data-state needs saving.
``--simulate-failure K`` aborts the process at step K (used by the FT test
to prove a restart continues bit-exactly).  ``--mesh dxm`` picks the device
mesh; on restart with a different mesh the checkpoint re-shards (elastic).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import jax
import numpy as np
from jax.sharding import AxisType, PartitionSpec as P

from repro.ckpt import latest_step, restore_checkpoint, save_checkpoint
from repro.configs import ARCH_NAMES, get_config
from repro.data import DataConfig, synthetic_batch
from repro.models import build_model, set_mesh
from repro.models.common import named_sharding
from repro.optim import OptConfig, adamw_init
from repro.train import build_train_step


def shardings_for(mesh, specs_tree, value_tree):
    return jax.tree.map(
        lambda s, v: named_sharding(mesh, s, v.shape), specs_tree, value_tree,
        is_leaf=lambda s: isinstance(s, P))


def run(arch: str, steps: int, smoke: bool, mesh_shape, batch: int,
        seq: int, ckpt_dir: str, simulate_failure: int = 0,
        microbatch: int = 0, log_every: int = 10, lr: float = 3e-4):
    # Auto axes: the model's sharding constraints name mesh axes, which
    # jax.make_mesh's default Explicit axes refuse
    mesh = jax.make_mesh(mesh_shape, ("data", "model")[: len(mesh_shape)]
                         if len(mesh_shape) > 1 else ("data",),
                         axis_types=(AxisType.Auto,) * len(mesh_shape))
    logical = {"data": ("data",), "model": ("model",)
               if "model" in mesh.axis_names else ()}
    if "model" not in mesh.shape:
        logical["model"] = ()
    set_mesh(mesh, logical)

    cfg = get_config(arch, smoke=smoke)
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params, specs = model.init(rng)
    opt_cfg = OptConfig(lr=lr, factored=cfg.params_count() > 60e9,
                        master_fp32=cfg.params_count() <= 60e9,
                        warmup=min(100, steps // 10 + 1))
    opt_state, ospecs = adamw_init(params, specs, opt_cfg)

    pshard = shardings_for(mesh, specs, params)
    oshard = shardings_for(mesh, ospecs, opt_state)
    params = jax.device_put(params, pshard)
    opt_state = jax.device_put(opt_state, oshard)

    start = 0
    if ckpt_dir and latest_step(ckpt_dir) is not None:
        state, start = restore_checkpoint(
            ckpt_dir, {"params": params, "opt": opt_state}, mesh=mesh,
            sharding_tree={"params": pshard, "opt": oshard})
        params, opt_state = state["params"], state["opt"]
        print(f"[train] resumed from step {start}", flush=True)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch)
    step_fn = jax.jit(
        build_train_step(model, opt_cfg, microbatch=microbatch),
        in_shardings=(pshard, oshard, None),
        out_shardings=(pshard, oshard, None),
        donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    for step in range(start, steps):
        np_batch = synthetic_batch(dcfg, step)
        batch_j = {k: jax.device_put(v) for k, v in np_batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch_j)
        if simulate_failure and step + 1 == simulate_failure:
            # checkpoint then die hard: the restart path must resume
            save_checkpoint(ckpt_dir, step + 1,
                            {"params": params, "opt": opt_state})
            print(f"[train] simulated failure at step {step + 1}", flush=True)
            os._exit(17)
        if (step + 1) % log_every == 0 or step + 1 == steps:
            loss = float(metrics["loss"])
            losses.append((step + 1, loss))
            dt = time.time() - t0
            print(f"[train] step {step + 1:5d} loss {loss:.4f} "
                  f"({dt / max(1, step + 1 - start):.2f}s/step)", flush=True)
        if ckpt_dir and ((step + 1) % 50 == 0 or step + 1 == steps):
            save_checkpoint(ckpt_dir, step + 1,
                            {"params": params, "opt": opt_state})
    return params, opt_state, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--mesh", default="1")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--simulate-failure", type=int, default=0)
    args = ap.parse_args()
    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    run(args.arch, args.steps, args.smoke, mesh_shape, args.batch, args.seq,
        args.ckpt_dir, args.simulate_failure, args.microbatch, lr=args.lr)


if __name__ == "__main__":
    main()
