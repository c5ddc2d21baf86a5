"""Batched serving driver: continuous batching + Scavenger-paged KV cache.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \
      --requests 8 --max-new 12
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.cache import use_compile_cache
from repro.models.model import build_model
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the paged-cache metadata store N ways")
    ap.add_argument("--shard-policy", choices=("hash", "range"),
                    default="hash")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    params = model.init_params(jax.random.key(args.seed))
    engine = ServeEngine(model, params, batch_slots=args.slots,
                         cache_len=args.cache_len,
                         meta_shards=args.shards,
                         meta_shard_policy=args.shard_policy)
    rng = np.random.default_rng(args.seed)
    t0 = time.time()
    for rid in range(args.requests):
        plen = int(rng.integers(4, 16))
        prompt = rng.integers(0, cfg.vocab, plen).tolist()
        engine.submit(Request(rid=rid, prompt=prompt,
                              max_new=args.max_new, hot=rid % 4 != 0))
    engine.run()
    dt = time.time() - t0
    toks = args.requests * args.max_new
    print(f"[serve] {args.requests} requests, {toks} tokens "
          f"in {dt:.1f}s ({toks / dt:.1f} tok/s)")
    print("[serve] pager:", json.dumps(engine.stats()))


if __name__ == "__main__":
    main()
