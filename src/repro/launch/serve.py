"""Serving driver: batched decode with the continuous-batching engine, on
the devices present.

    python -m repro.launch.serve --arch granite-3-2b --smoke --requests 6

Exits non-zero for an architecture the engine cannot serve (embedding-input
configs) and when a request does not complete.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer
    from repro.serving.engine import ServingEngine

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    if cfg.input_kind != "tokens":
        print(f"[serve] {args.arch} takes embeddings, not tokens; the engine "
              "serves token-input archs only", file=sys.stderr)
        return 2
    enable_compile_cache()
    params = transformer.init_params(cfg, jax.random.PRNGKey(args.seed))
    eng = ServingEngine(cfg, params, batch_slots=args.slots, max_len=64)
    rng = np.random.default_rng(args.seed)
    for i in range(args.requests):
        plen = int(rng.integers(1, 6))
        eng.submit(rng.integers(0, cfg.vocab_size, plen), max_new_tokens=args.max_new)
    done = eng.run()
    for r in sorted(done, key=lambda r: r.uid):
        print(f"[serve] req {r.uid}: prompt {r.prompt.tolist()} -> {r.generated}")
    print(f"[serve] completed {len(done)}/{args.requests} requests")
    return 0 if len(done) == args.requests else 1


if __name__ == "__main__":
    raise SystemExit(main())
