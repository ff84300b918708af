"""Serving driver: batched requests through the continuous-batching engine.

    python -m repro.launch.serve --arch smollm-135m --reduced \
        --num-requests 8 --max-new-tokens 16

Parameters are fp32, made from ``--seed``; prompts are random tokens with
lengths drawn uniformly from ``--prompt-len MIN MAX`` (MAX exclusive).
"""

from __future__ import annotations

import argparse
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import get_model
from repro.serve import Engine, EngineConfig, Request


def main(argv: Optional[List[str]] = None) -> List[Request]:
    """Serve the requests to completion; returns them in finishing order."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, nargs=2, default=(4, 24),
                    metavar=("MIN", "MAX"))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch, reduced=args.reduced)
    model = get_model(cfg)
    rng = jax.random.PRNGKey(args.seed)
    params = model.init_params(rng, cfg, dtype=jnp.float32)
    engine = Engine(cfg, params,
                    EngineConfig(max_batch=args.max_batch,
                                 max_seq=args.max_seq, seed=args.seed),
                    dtype=jnp.float32)
    rs = np.random.RandomState(args.seed)
    t0 = time.monotonic()
    for i in range(args.num_requests):
        plen = int(rs.randint(*args.prompt_len))
        prompt = rs.randint(0, cfg.vocab_size, size=plen).astype(np.int32)
        engine.submit(Request(uid=i, prompt=prompt,
                              max_new_tokens=args.max_new_tokens))
    done = engine.run_until_drained()
    dt = time.monotonic() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)")
    for r in sorted(done, key=lambda r: r.uid)[:4]:
        print(f"  req {r.uid}: prompt[:4]={list(r.prompt[:4])} "
              f"out[:8]={r.out_tokens[:8]}")
    return done


if __name__ == "__main__":
    main()
