"""Batched serving driver: prefill + decode loop with a KV cache.

CLI:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
        --reduced --batch 4 --prompt-len 32 --gen 16
"""

from __future__ import annotations

import argparse
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import devices
from repro.configs.base import ShapeCell, get_config, reduced
from repro.core import planner as planner_lib
from repro.launch import mesh as mesh_lib
from repro.models import build_model
from repro.parallel import sharding as shard_lib


def serve(arch: str, batch: int = 4, prompt_len: int = 32, gen: int = 16,
          mesh_shape: Tuple[int, ...] = (1, 1), use_reduced: bool = True,
          seed: int = 0, greedy: bool = True) -> Dict:
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    model = build_model(cfg)
    if model.decode_step is None:
        raise ValueError(f"{arch} has no decode path")
    mesh = mesh_lib.make_mesh(mesh_shape)
    cell = ShapeCell("serve", prompt_len + gen, batch, "decode")
    plan = planner_lib.plan(cfg, cell, mesh_shape, mesh.axis_names)
    rules = shard_lib.resolve_rules(plan, mesh)

    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (batch, prompt_len)).astype(np.int32))

    with mesh:
        params = model.init(jax.random.PRNGKey(seed))
        max_len = prompt_len + gen
        caches = model.init_cache(batch, max_len)
        decode = jax.jit(lambda p, c, t, pos: model.decode_step(
            p, c, t, pos, rules=rules, mesh=mesh))

        # prefill by stepping the prompt (robust across all families)
        t0 = time.time()
        logits = None
        for t in range(prompt_len):
            logits, caches = decode(params, caches, prompts[:, t:t + 1],
                                    jnp.asarray(t, jnp.int32))
        prefill_s = time.time() - t0

        out_tokens = []
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        t0 = time.time()
        for t in range(gen):
            out_tokens.append(np.asarray(cur))
            logits, caches = decode(params, caches, cur,
                                    jnp.asarray(prompt_len + t, jnp.int32))
            cur = jnp.argmax(logits[:, -1], axis=-1).astype(
                jnp.int32)[:, None]
        decode_s = time.time() - t0

    tokens = np.concatenate(out_tokens, axis=1)
    return {"tokens": tokens,
            "prefill_s": prefill_s,
            "decode_s": decode_s,
            "tok_per_s": batch * gen / max(decode_s, 1e-9),
            "plan": plan.strategy.name}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()
    devices.enable_compilation_cache()
    out = serve(args.arch, args.batch, args.prompt_len, args.gen,
                tuple(int(x) for x in args.mesh.split("x")),
                use_reduced=args.reduced)
    print(f"[serve] strategy {out['plan']}: prefill {out['prefill_s']:.2f}s, "
          f"decode {out['decode_s']:.2f}s "
          f"({out['tok_per_s']:.1f} tok/s)")
    print("[serve] sample tokens:", out["tokens"][0][:12])


if __name__ == "__main__":
    main()
