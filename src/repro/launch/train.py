"""End-to-end training driver with Scavenger-backed fault tolerance.

CPU-runnable with the smoke/small configs; the same driver lowers onto the
production mesh on TPU.  Demonstrates:
  * incremental checkpointing into the KV-separated store under a disk
    quota (old steps = garbage; Scavenger GC reclaims),
  * crash / restart (--fail-at-step N aborts mid-run; rerunning with the
    same --ckpt-dir resumes from the last durable step),
  * deterministic resumable data (pipeline state is a cold checkpoint key).

Example (examples/train_lm.py wraps this):
  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m --smoke \
      --steps 30 --batch 4 --seq 64 --ckpt-dir /tmp/ckpt --ckpt-every 10
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.pytree import (drop_steps, load_pytree, save_pytree,
                                     steps_available)
from repro.checkpoint.store import CheckpointStore
from repro.configs import get_config
from repro.data.pipeline import PipelineConfig, TokenPipeline
from repro.launch.cache import use_compile_cache
from repro.models.model import build_model
from repro.train.trainer import (TrainConfig, init_opt_state,
                                 make_train_step)


def make_batch_for(cfg, tokens):
    if cfg.enc_dec:
        b, s = tokens.shape
        rng = np.random.default_rng(int(tokens[0, 0]))
        return {"frames": jnp.asarray(
                    rng.standard_normal((b, s, cfg.d_model)), jnp.float32),
                "tokens": jnp.asarray(tokens)}
    if cfg.modality == "vlm":
        b, s = tokens.shape
        p = min(cfg.n_patches, max(1, s // 4))
        rng = np.random.default_rng(int(tokens[0, 0]))
        return {"patches": jnp.asarray(
                    rng.standard_normal((b, p, cfg.d_model)), jnp.float32),
                "tokens": jnp.asarray(tokens[:, :s - 0])}
    return {"tokens": jnp.asarray(tokens)}


def run(args) -> dict:
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    tcfg = TrainConfig(lr=args.lr, accum_steps=args.accum)
    step_fn = jax.jit(make_train_step(model, tcfg))

    pipe = TokenPipeline(PipelineConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed))

    store = None
    start_step = 0
    params = opt_state = None
    if args.ckpt_dir:
        store = CheckpointStore(
            args.ckpt_dir, engine=args.ckpt_engine,
            quota_bytes=args.quota_mb * (1 << 20) if args.quota_mb else None,
            log_target=args.log_target_kb << 10)
        have = steps_available(store, "train")
        for cand in reversed(have if not args.fresh else []):
            try:        # newest complete checkpoint wins; torn ones skipped
                params = load_pytree(store, "train", cand,
                                     model.abstract_params())
                params = jax.tree.map(jnp.asarray, params)
                opt_abs = jax.eval_shape(
                    lambda p: init_opt_state(p, tcfg), params)
                opt_state = load_pytree(store, "train", cand, opt_abs)
                opt_state = jax.tree.map(jnp.asarray, opt_state)
                meta = json.loads(store.get(f"meta/{cand}/state"))
                pipe.restore(meta["pipeline"])
                start_step = cand
                print(f"[train] resuming from checkpoint step {cand}")
                break
            except KeyError:
                params = opt_state = None
                continue
    if params is None:
        params = model.init_params(jax.random.key(args.seed))
        opt_state = init_opt_state(params, tcfg)
        pipe.step = 0

    losses = []
    t0 = time.time()
    for step in range(start_step, args.steps):
        tokens = next(pipe)["tokens"]
        batch = make_batch_for(cfg, tokens)
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % max(1, args.log_every) == 0:
            dt = time.time() - t0
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({dt:.1f}s)", flush=True)
        if args.fail_at_step is not None and step + 1 == args.fail_at_step:
            print(f"[train] injected failure at step {step + 1}",
                  flush=True)
            os._exit(42)
        if store and (step + 1) % args.ckpt_every == 0:
            save_pytree(store, "train", step + 1, params, hot=True)
            save_pytree(store, "train", step + 1, opt_state, hot=True)
            store.put(f"meta/{step + 1}/state", json.dumps(
                {"pipeline": pipe.state(), "loss": loss}).encode(),
                hot=False)
            store.flush()          # durable before old steps become garbage
            drop_steps(store, "train", keep_last=args.keep_last)
            drop_steps(store, "meta", keep_last=args.keep_last)
            store.run_gc()
            store.flush()
    result = {"final_loss": losses[-1] if losses else None,
              "losses": losses, "steps_run": len(losses),
              "resumed_from": start_step}
    if store:
        result["store"] = store.stats()
        store.close()
    print(f"[train] done: {json.dumps(result['store'] if store else {})}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-engine", default="scavenger",
                    choices=["scavenger", "naive"])
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--keep-last", type=int, default=2)
    ap.add_argument("--quota-mb", type=int, default=None)
    ap.add_argument("--log-target-kb", type=int, default=1024)
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--fresh", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
