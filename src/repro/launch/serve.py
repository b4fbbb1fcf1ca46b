"""Serving entry point: continuous batching over a seeded request stream with
SLO accounting.  Serves the published config (weights generated on the
device from ``--seed``); ``--tiny`` swaps in the reduced config for the CPU.

    PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --tiny \
        --requests 12 --slots 4 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_tiny_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving import Request, ServingEngine


def make_requests(cfg: ModelConfig, n: int, *, min_len: int, max_len: int,
                  max_new: int, seed: int) -> list[Request]:
    """``n`` requests with prompt lengths drawn from [min_len, max_len)."""
    rng = np.random.default_rng(seed)
    reqs = []
    for rid in range(n):
        plen = int(rng.integers(min_len, max_len))
        prompt = rng.integers(0, cfg.vocab_size, plen).tolist()
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    return reqs


def serve(cfg: ModelConfig, requests: list[Request], *, slots: int,
          cache_len: int, seed: int = 0):
    """Serve ``requests`` to completion on weights generated on the device
    from ``seed``.  Returns (engine, finished requests, wall seconds)."""
    params = jax.block_until_ready(
        jax.jit(Model(cfg).init)(jax.random.PRNGKey(seed)))
    engine = ServingEngine(cfg, params, slots=slots, cache_len=cache_len)
    t0 = time.perf_counter()
    for req in requests:
        engine.submit(req)
    finished = engine.run_until_drained()
    return engine, finished, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b", choices=list(ARCH_IDS))
    ap.add_argument("--tiny", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ttft-slo-ms", type=float, default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_tiny_config(args.arch) if args.tiny else get_config(args.arch)
    requests = make_requests(cfg, args.requests, min_len=4,
                             max_len=args.prompt_len, max_new=args.max_new,
                             seed=args.seed)
    _, finished, wall = serve(cfg, requests, slots=args.slots,
                              cache_len=args.cache_len, seed=args.seed)

    toks = sum(len(r.tokens) for r in finished)
    ttfts = [r.ttft_s * 1e3 for r in finished if r.ttft_s is not None]
    print(f"served {len(finished)}/{args.requests} requests, {toks} tokens, "
          f"{wall*1e3:.0f} ms wall ({toks/wall:.1f} tok/s)")
    print(f"TTFT ms: p50={np.percentile(ttfts, 50):.1f} "
          f"p95={np.percentile(ttfts, 95):.1f} max={max(ttfts):.1f}")
    if args.ttft_slo_ms is not None:
        ok = sum(t <= args.ttft_slo_ms for t in ttfts)
        print(f"TTFT SLO {args.ttft_slo_ms} ms: {ok}/{len(ttfts)} met")
    return finished


if __name__ == "__main__":
    main()
