"""Serving launcher: batched inference with results returned as record
batches over the Thallus transport.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \\
        --reduced --requests 8 --max-new 12

Requests are grouped into aligned cohorts (see serving.batcher), prefilled
once, decoded in lockstep; completions leave as a columnar record batch via
the zero-copy transport (the serving direction of the paper's protocol).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..core import Fabric, ThallusTransport
from ..models import decode as decode_fn
from ..models import init_params, make_rules, mesh_context, prefill
from ..serving import Batcher, Request, completions_to_batch
from .cache import enable_compile_cache
from .mesh import make_host_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("serve example covers LM families; vlm/audio need "
                         "frontend inputs — see examples/")

    mesh = make_host_mesh()
    rules = make_rules(cfg, mesh)
    with mesh, mesh_context(mesh, rules):
        params = init_params(cfg, jax.random.PRNGKey(0))

        def prefill_fn(tokens):
            return prefill(cfg, params, {"tokens": tokens}, remat="none")

        def decode_step(cache, tokens, position):
            return decode_fn(cfg, params, cache, tokens, position)

        batcher = Batcher(jax.jit(prefill_fn), jax.jit(decode_step),
                          batch_size=args.batch_size)
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            plen = int(rng.integers(4, args.prompt_len + 1))
            batcher.submit(Request(
                request_id=i,
                prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
                max_new_tokens=args.max_new))
        t0 = time.time()
        completions = batcher.run()
        dt = time.time() - t0

    out_batch = completions_to_batch(completions)
    transport = ThallusTransport(Fabric())
    delivered, stats = transport.send_batch(out_batch)
    total_tokens = sum(len(c.tokens) for c in completions)
    print(f"served {len(completions)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/max(dt,1e-9):.1f} tok/s)")
    print(f"response batch: {delivered.num_rows} rows, "
          f"{delivered.nbytes} bytes, transport {stats.total_s*1e6:.1f}us "
          f"(zero serialize copies: {stats.serialize_s == 0.0})")
    for c in completions[:4]:
        print(f"  req {c.request_id}: {c.tokens}")


if __name__ == "__main__":
    main()
