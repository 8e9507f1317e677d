"""Serving driver: a thin CLI over the async serving runtime.

Requests (mixed prompt lengths) are admitted by power-of-two bucket so every
warm bucket hits an already-cached StagedPhysicalPlan, prefilled through the
planned ``prefill_kv`` forward (per-layer K/V are plan outputs that seed the
paged KV pool directly — no prompt replay), and decoded with continuous
batching: requests join/leave the fixed-width decode batch at token
boundaries.

CPU-scale demo:
  python -m repro.launch.serve --arch qwen3-0.6b --smoke --requests 8 \
      --gen 16 --max-batch 4

``serve_request`` / ``planned_prefill`` are the seed's sequential-path
helpers, kept as compatibility wrappers (and as the benchmark baseline).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, get_smoke_config
from ..core.backend import enable_compile_cache
from ..core.executor import plan_and_compile
from ..core.ir import SystemCatalog
from ..models import build_model
from ..models.decode import decode_step, init_cache
from ..models.lm import CATALOG
from ..serving import AsyncServingRuntime, ServeRequest
from ..serving.admission import bucket_len  # compat re-export  # noqa: F401


def planned_prefill(model, syscat, batch: int, prompt_len: int,
                    cache=None, engines=("xla",)):
    """Compile (or fetch from the plan cache) the prefill forward for this
    request's bucket.  Returns (planned_fn, bucket).  (Seed-path compat.)"""
    bucket = bucket_len(prompt_len)
    plan = model.build_plan(batch, bucket, mode="prefill")
    fwd = plan_and_compile(plan, CATALOG, syscat, engines=engines,
                           cache=cache)
    return fwd, bucket


def serve_request(model, cfg, params, dstep, fwd, bucket, prompts, gen: int,
                  *, ring_local: bool = False):
    """One sequential request: planned prefill for the prompt logits, then
    cached token-by-token decode.  (Seed-path compat; the async runtime's
    ``prefill_kv`` path replaces the KV-rebuild replay below.)"""
    b, prompt_len = prompts.shape
    max_seq = prompt_len + gen

    t0 = time.time()
    padded_np = np.zeros((b, bucket), np.int32)
    padded_np[:, :prompt_len] = np.asarray(prompts)
    logits_all = fwd(params, {"tokens": jnp.asarray(padded_np)})
    tok = jnp.argmax(logits_all[:, prompt_len - 1, :cfg.vocab],
                     axis=-1).astype(jnp.int32)[:, None]

    # the replay path: rebuild the KV cache through cached decode — the
    # sequential baseline the async runtime's plan-output seeding removes
    cache = init_cache(model, b, max_seq, ring_local=ring_local)
    for t in range(prompt_len):
        _, cache = dstep(params, cache, prompts[:, t:t + 1], jnp.int32(t))
    t_prefill = time.time() - t0

    out_tokens = []
    t0 = time.time()
    for t in range(prompt_len, max_seq):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, cache = dstep(params, cache, tok, jnp.int32(t))
        tok = jnp.argmax(logits[:, :, :cfg.vocab], axis=-1).astype(jnp.int32)
    t_gen = time.time() - t0
    return np.stack(out_tokens, axis=1), t_prefill, t_gen


def make_trace(rng, cfg, n_requests: int, prompt_lens, gen: int,
               arrival_spacing: float = 0.0) -> list:
    """A mixed-length request trace (round-robin over ``prompt_lens``)."""
    reqs = []
    for i in range(n_requests):
        n = prompt_lens[i % len(prompt_lens)]
        reqs.append(ServeRequest(
            i, tuple(rng.randint(0, cfg.vocab, n).tolist()), gen,
            arrival=i * arrival_spacing))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-lens", default="5,12,8,20,16,3,27,9",
                    help="comma-separated prompt lengths, cycled over "
                         "requests (mixed lengths exercise the buckets)")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="decode-batch width (continuous batching slots)")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV-pool page size (tokens)")
    ap.add_argument("--arrival-spacing", type=float, default=0.0,
                    help="seconds between request arrivals")
    ap.add_argument("--engines", default="xla")
    ap.add_argument("--plan-cache-dir", default=None,
                    help="persist/warm-start the plan cache here")
    ap.add_argument("--explain", action="store_true",
                    help="print one bucket's EXPLAIN report")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.smoke:
        cfg = cfg.replace(dtype="float32")
    model = build_model(cfg)
    params, _ = model.init_params(jax.random.key(args.seed))
    rng = np.random.RandomState(args.seed)
    prompt_lens = [int(x) for x in args.prompt_lens.split(",")]

    rt = AsyncServingRuntime(
        model, params, max_batch=args.max_batch, max_seq=args.max_seq,
        page_size=args.page_size, engines=tuple(args.engines.split(",")),
        plan_cache_dir=args.plan_cache_dir)
    print(f"[serve] arch={cfg.name} mode="
          f"{'prefill_kv (plan-seeded KV)' if rt.kv_mode else 'replay'} "
          f"max_batch={args.max_batch} max_seq={args.max_seq}")

    t0 = time.time()
    rt.warmup(prompt_lens)
    print(f"[serve] warmup (plans + jit) {time.time() - t0:.2f}s; "
          f"buckets {sorted(rt._prefill_fns)}")
    if args.explain:
        fwd, _ = rt._prefill_fns[sorted(rt._prefill_fns)[0]]
        print(fwd.explain())

    reqs = make_trace(rng, cfg, args.requests, prompt_lens, args.gen,
                      args.arrival_spacing)
    t0 = time.time()
    results = rt.serve(reqs)
    wall = time.time() - t0
    toks = sum(len(r.tokens) for r in results)
    print(rt.metrics.report())
    print(f"[serve] {toks} tokens in {wall:.2f}s -> {toks / wall:.1f} tok/s; "
          f"pool {rt.pool.occupancy()}")
    s = rt.pc.stats()
    print(f"[serve] plan cache: {s['hits']} hits / {s['misses']} misses "
          f"(hit rate {s['hit_rate']:.2f})")
    sample = [r.tokens[:8] for r in results[:2]]
    print(f"[serve] sample generations (token ids): {sample}")
    return results


if __name__ == "__main__":
    main()
