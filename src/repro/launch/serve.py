"""Serving launcher: batched decode for any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m [--reduced]

The published config by default; ``--reduced`` serves the small float32
config that the CPU tests use.
"""
import argparse
import dataclasses
import sys
import time

import numpy as np


def build(arch: str = "smollm-135m", *, reduced: bool = False,
          batch: int = 4, max_len: int = 256, seed: int = 0):
    """The launcher's server over random weights from ``seed``: returns
    ``(server, cfg)``."""
    import jax

    from repro.models import build_model, get_model, reduced_config
    from repro.runtime import Server

    _, cfg = get_model(arch)
    if reduced:
        cfg = dataclasses.replace(reduced_config(cfg), dtype="float32")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    return Server(model, params, batch=batch, max_len=max_len), cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced float32 config (CPU-friendly)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compilation_cache
    from repro.runtime import Request
    enable_compilation_cache()
    server, cfg = build(args.arch, reduced=args.reduced, batch=args.batch,
                        max_len=args.max_len)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, 8),
                    max_new_tokens=args.new_tokens)
            for i in range(args.requests)]
    t0 = time.perf_counter()
    done = server.run(reqs)
    dt = time.perf_counter() - t0
    tokens = sum(len(v) for v in done.values())
    print(f"{cfg.name}: {tokens} tokens, {len(done)} requests, "
          f"{tokens/dt:.1f} tok/s (host clock, compilation included)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
