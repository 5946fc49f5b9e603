"""Serve a small model with batched requests: prefill + lock-step decode
with per-request lengths, greedy and sampled decoding.

  PYTHONPATH=src python examples/serve_lm.py [--arch gemma3-1b]
"""
import argparse

import numpy as np
import jax

from repro import configs
from repro.launch.serve import Request, serve_batch
from repro.models import transformer
from repro.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    args = ap.parse_args()

    cfg = configs.get_smoke_config(args.arch)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    reqs = [Request(rid=i,
                    prompt=rng.integers(
                        1, cfg.vocab_size,
                        int(rng.integers(4, 32))).astype(np.int32),
                    max_new=int(rng.integers(8, args.max_new + 1)))
            for i in range(args.batch)]
    print(f"{len(reqs)} requests, prompt lens "
          f"{[len(r.prompt) for r in reqs]}, max_new "
          f"{[r.max_new for r in reqs]}")

    reqs, stats = serve_batch(cfg, params, reqs, max_seq=64, greedy=True)
    for r in reqs:
        print(f"  req {r.rid}: generated {len(r.out)} tokens "
              f"{r.out[:10]}{'...' if len(r.out) > 10 else ''}")
    print(f"prefill {stats['prefill_s']*1e3:.0f} ms, "
          f"decode {stats['decode_s']*1e3:.0f} ms "
          f"({stats['tokens_per_s']:.1f} tok/s on CPU)")


if __name__ == "__main__":
    main()
