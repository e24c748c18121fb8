"""Serving-path demo: greedy decode with a KV cache on any assigned arch.

    PYTHONPATH=src python examples/serve_lm.py --arch smollm-135m --tokens 24

Uses the reduced (smoke) config on CPU; the full configs serve on the pod
meshes via launch/dryrun.py's serve_step lowering. Demonstrates batched
requests, prefill-by-decode, and the ring cache for SWA archs.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_arch
from repro.models import transformer as T
from repro.utils.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=24)
    args = ap.parse_args()
    enable_compile_cache()

    spec = get_arch(args.arch, reduced=True)
    if spec.kind == "whisper":
        raise SystemExit("use the LM archs for this demo")
    cfg = spec.lm
    params = spec.init_params(jax.random.PRNGKey(0))

    B = args.batch
    cache_len = (min(cfg.sliding_window, 64) if cfg.sliding_window
                 else args.prompt_len + args.tokens)
    cache = T.init_cache(cfg, B, cache_len)
    step = jax.jit(lambda p, c, t: T.decode_step(p, cfg, c, t))

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=(B, args.prompt_len))
    out = [prompt[:, i] for i in range(args.prompt_len)]

    # prefill by stepping the prompt through the cache, then greedy decode
    logits = None
    for i in range(args.prompt_len):
        logits, cache = step(params, cache, jnp.asarray(prompt[:, i : i + 1]))
    for _ in range(args.tokens):
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(nxt)[:, 0])
        logits, cache = step(params, cache, nxt)

    seqs = np.stack(out, axis=1)
    print(f"{args.arch} (reduced) generated {args.tokens} tokens x {B} requests")
    for b in range(B):
        print(f"  req{b}: {seqs[b].tolist()}")


if __name__ == "__main__":
    main()
