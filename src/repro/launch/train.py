"""End-to-end training driver.

Wires the full stack: DeepFlow planner (CrossFlow-predicted sharding plan)
-> NamedShardings -> jit'd train step (loss + grad + AdamW, optional int8
error-feedback gradient compression + remat) -> sharded synthetic data
pipeline with prefetch -> async atomic checkpointing -> preemption handler
+ straggler watchdog.

CLI:
    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
        --steps 100 --batch 8 --seq 128 --mesh 1x1 --ckpt-dir /tmp/ckpt
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import devices, optim
from repro.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig, ShapeCell, get_config, reduced
from repro.core import planner as planner_lib
from repro.data import DataConfig, PrefetchIterator
from repro.launch import mesh as mesh_lib
from repro.models import build_model
from repro.parallel import sharding as shard_lib
from repro.runtime import PreemptionHandler, StragglerWatchdog, compress, \
    decompress, init_error_state


@dataclasses.dataclass
class TrainConfig:
    arch: str = "qwen1.5-0.5b"
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    mesh_shape: Tuple[int, ...] = (1, 1)
    lr: float = 3e-4
    warmup: int = 20
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    remat: bool = False
    grad_compression: str = "none"      # none | int8
    use_reduced_config: bool = False
    seed: int = 0


class TrainState:
    def __init__(self, params, opt_state, err_state=None):
        self.params = params
        self.opt_state = opt_state
        self.err_state = err_state

    def as_tree(self):
        t = {"params": self.params, "opt": self.opt_state._asdict()}
        if self.err_state is not None:
            t["err"] = self.err_state
        return t

    @staticmethod
    def from_tree(t):
        return TrainState(t["params"], optim.AdamWState(**t["opt"]),
                          t.get("err"))


def make_train_step(model, cfg: ArchConfig, opt_cfg: optim.AdamWConfig,
                    rules, mesh, remat: bool, compression: str,
                    grad_shardings=None):
    def step_fn(params, opt_state, err_state, batch):
        def loss_of(p):
            loss, metrics = model.loss_fn(p, batch, rules=rules, mesh=mesh,
                                          remat=remat)
            return loss, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_of,
                                                    has_aux=True)(params)
        if grad_shardings is not None:
            # pin wgrads to the param layout: GSPMD can then reduce-scatter
            # at the producer instead of AR-ing the full tensor + slicing
            grads = jax.tree.map(jax.lax.with_sharding_constraint, grads,
                                 grad_shardings)
        if compression == "bf16":
            # halve the DP all-reduce volume; optimizer math stays fp32
            grads = jax.tree.map(
                lambda g: g.astype(jnp.bfloat16).astype(jnp.float32), grads)
        if compression == "int8":
            comp, err_state = compress(grads, err_state)
            grads = decompress(comp, grads)
        params, opt_state, om = optim.apply(opt_cfg, opt_state, params,
                                            grads)
        metrics = dict(metrics, loss=loss, **om)
        return params, opt_state, err_state, metrics

    return step_fn


def setup(tc: TrainConfig):
    cfg = get_config(tc.arch)
    if tc.use_reduced_config:
        cfg = reduced(cfg)
    model = build_model(cfg)
    mesh = mesh_lib.make_mesh(tc.mesh_shape)
    cell = ShapeCell("train", tc.seq_len, tc.global_batch, "train")
    plan = planner_lib.plan(cfg, cell, tc.mesh_shape, mesh.axis_names)
    rules = shard_lib.resolve_rules(plan, mesh)
    p_shardings = shard_lib.param_shardings(model, plan, mesh)
    b_shardings = shard_lib.batch_shardings(cfg, cell, plan, mesh)
    return cfg, model, mesh, plan, rules, p_shardings, b_shardings


def train(tc: TrainConfig) -> Dict[str, Any]:
    cfg, model, mesh, plan, rules, p_shardings, b_shardings = setup(tc)
    opt_cfg = optim.AdamWConfig(lr=tc.lr, warmup_steps=tc.warmup,
                                total_steps=max(tc.steps, 1))

    with mesh:
        params = jax.jit(
            lambda k: model.init(k),
            out_shardings=p_shardings)(jax.random.PRNGKey(tc.seed))
    opt_state = optim.init(params)
    err_state = (init_error_state(params)
                 if tc.grad_compression == "int8" else None)
    state = TrainState(params, opt_state, err_state)

    ckpt = CheckpointManager(tc.ckpt_dir) if tc.ckpt_dir else None
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        tree = ckpt.restore(like=state.as_tree())
        state = TrainState.from_tree(tree)
        start_step = int(state.opt_state.step)
        print(f"[train] resumed from step {start_step}")

    step_fn = make_train_step(model, cfg, opt_cfg, rules, mesh, tc.remat,
                              tc.grad_compression)
    jit_step = jax.jit(step_fn, donate_argnums=(0, 1, 2))

    data_cfg = DataConfig(global_batch=tc.global_batch, seq_len=tc.seq_len,
                          seed=tc.seed)
    it = PrefetchIterator(data_cfg, cfg, start_step=start_step)
    preempt = PreemptionHandler()
    watchdog = StragglerWatchdog()
    history = []
    t_prev = time.time()
    try:
        with mesh:
            for step, batch in it:
                if step >= tc.steps:
                    break
                state.params, state.opt_state, state.err_state, metrics = \
                    jit_step(state.params, state.opt_state, state.err_state,
                             batch)
                loss = float(metrics["loss"])
                now = time.time()
                watchdog.observe(step, now - t_prev)
                t_prev = now
                history.append(loss)
                if step % tc.log_every == 0:
                    print(f"[train] step {step:5d} loss {loss:.4f} "
                          f"lr {float(metrics['lr']):.2e} "
                          f"gnorm {float(metrics['grad_norm']):.3f}")
                if ckpt and step and step % tc.ckpt_every == 0:
                    ckpt.save(step, state.as_tree())
                if preempt.preempted:
                    print("[train] preemption: saving and exiting")
                    if ckpt:
                        ckpt.save(step, state.as_tree(), block=True)
                    break
    finally:
        it.close()
        if ckpt:
            ckpt.wait()
    if ckpt and not preempt.preempted:
        ckpt.save(tc.steps, state.as_tree(), block=True)
    return {"history": history, "final_loss": history[-1] if history else
            float("nan"), "stragglers": watchdog.events, "state": state,
            "plan": plan}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1",
                    help="e.g. 1x1, 2x2, 2x16x16")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--compression", default="none",
                    choices=["none", "int8"])
    ap.add_argument("--reduced", action="store_true",
                    help="use the smoke-scale config of the arch family")
    args = ap.parse_args()
    devices.enable_compilation_cache()
    tc = TrainConfig(arch=args.arch, steps=args.steps,
                     global_batch=args.batch, seq_len=args.seq,
                     mesh_shape=tuple(int(x) for x in args.mesh.split("x")),
                     lr=args.lr, ckpt_dir=args.ckpt_dir, remat=args.remat,
                     grad_compression=args.compression,
                     use_reduced_config=args.reduced)
    out = train(tc)
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"({len(out['history'])} steps, "
          f"{len(out['stragglers'])} straggler events)")


if __name__ == "__main__":
    main()
