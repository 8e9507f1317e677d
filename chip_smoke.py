"""Bring-up check on a TPU: the served tri-model analysis at deployment
size, and full-width qwen3-0.6b serving, each held to a plain reference.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # only the sharded tri-store, 4 chips

One process drives the chip; any failed check exits non-zero.  Phases:

1. device -- the first JAX device must be a TPU whose kind has hardware
   peaks (``core.ir.HARDWARE_BY_KIND``); nothing runs on another backend.
2. analysis -- the ADIL program of ``examples/tri_model_analysis.py`` over
   2^23 tweets, 2^18 Zipf hashtags, 2^20 users and a 2^16-term corpus
   (~7 postings per tweet), planned with the Pallas engine and served as 16
   requests through ``AsyncServingRuntime.run_analyses``: exact twins run
   single-flight and a ``batch_param="q"`` group runs vmapped.  Every chosen
   Pallas node must lower to a compiled TPU kernel, and every score vector
   must match a NumPy composition of ``stores/ref.py``.
3. lm -- qwen3-0.6b at its published widths and configured dtype, random
   weights from ``--seed``, 8 mixed-length requests of 16 tokens through
   ``AsyncServingRuntime.serve`` as ``launch/serve.py`` drives it; one
   request's prefill logits must match a float32 ``jax.numpy`` forward.

``--four-chips`` runs only the sharded tri-store (stores ``with_shards(4)``
on a (data=4, model=1) mesh, planned through ``shard_stores``) and the
one-chip run of the same analysis it is compared with.

The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.adil_parser import parse_adil  # noqa: E402
from repro.core.backend import enable_compile_cache  # noqa: E402
from repro.core.executor import plan_and_compile  # noqa: E402
from repro.core.ir import SystemCatalog, hardware_for, standard_catalog  # noqa: E402,E501
from repro.core.ledger import FlightRecorder  # noqa: E402
from repro.launch.mesh import shard_store_inputs, syscat_for_mesh  # noqa: E402
from repro.launch.serve import make_trace  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.lm import CATALOG  # noqa: E402
from repro.serving import AnalysisRequest, AsyncServingRuntime  # noqa: E402
from repro.stores import ref as R  # noqa: E402
from repro.stores import store_engines  # noqa: E402
from tri_model_analysis import adil_script, build_social_data  # noqa: E402

# the deployment the analysis phase serves (stores ~1 GB resident)
ANALYSIS_SIZES = dict(tweets=1 << 23, hashtags=1 << 18, users=1 << 20,
                      vocab=1 << 16, doc_tokens=(7, 15))
# the ADIL program's constants (examples/tri_model_analysis.py)
HOT_ENGAGEMENT, HOPS, PR_ITERS, DAMPING, TOP_K = 30.0, 2, 8, 0.85, 64
# float32 stores against float64 NumPy: the hub hashtag sums ~3M in-edges
# (over 128 accumulator lanes) in each of 10 SpMV passes, and each hashtag
# sums <= 64 text scores
SCORE_RTOL, SCORE_ATOL_REL = 1e-4, 1e-6
# launch/serve.py's defaults
LM_ARCH, LM_PROMPT_LENS, LM_GEN = "qwen3-0.6b", (5, 12, 8, 20, 16, 3, 27, 9), 16
# bfloat16 activations and weights through 28 layers against float32:
# relative L2 error of one request's prefill logits
LM_REL_TOL = 5e-2
# the Pallas kernel each store impl lowers to (its ``kernel_name``)
KERNEL_OF_IMPL = {
    "graph_expand_pallas": "_scatter_add_kernel",
    "graph_pagerank_pallas": "_scatter_add_kernel",
    "rel_fused_agg_pallas": "_masked_segagg_kernel",
    "text_topk_masked_pallas": "_masked_tfidf_kernel",
    "compact_prefix_pallas": "_compact_kernel",
    "rel_join_probe_pallas": "_join_probe_kernel",
}
STORE_NAMES = ("tweets", "g", "cx")
HIDDEN_FAILURES = ("batch_fallback", "executor_error", "breaker_open")


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def tree_nbytes(tree) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


# --------------------------------------------------------------------------
# the tri-model analysis
# --------------------------------------------------------------------------

def build_analysis(table, graph, corpus, syscat, *, mesh=None):
    """parse_adil -> bind the stores -> Analysis.compile with Pallas."""
    analysis = parse_adil(adil_script(table, graph, corpus),
                          standard_catalog())
    for name, store in zip(STORE_NAMES, (table, graph, corpus)):
        analysis.bind(name, store)
    fn = analysis.compile(syscat, engines=store_engines(pallas=True),
                          mesh=mesh)
    return analysis, fn


def graph_reference(table, graph):
    """The query-independent half: hot-tweet seeds -> 2-hop expansion ->
    personalized PageRank, composed from the NumPy references."""
    n = graph.n_nodes
    hot = table.column("engagement") >= HOT_ENGAGEMENT
    seeds = R.group_agg_ref(None, table.column("hashtag"), n, hot, "count")
    args = (graph.src, graph.indices, graph.weights, n)
    frontier = R.expand_ref(*args, seeds, hops=HOPS)
    return R.pagerank_ref(*args, iters=PR_ITERS, damping=DAMPING,
                          personalization=frontier)


def text_reference(table, corpus, n_nodes, q):
    """Per-hashtag text relevance: TF-IDF top-k -> join to tweets ->
    per-hashtag score sums.  Also returns the k-th score and how many
    unselected docs tie with it (see :func:`analysis_error`)."""
    scores = R.tfidf_scores_ref(corpus.doc_ids, corpus.term_ids, corpus.tf,
                                corpus.doc_len, corpus.idf, q)
    ids, top, valid = R.masked_topk_ref(scores, np.ones(scores.shape, bool),
                                        TOP_K)
    idx, matched = R.hash_join_ref(ids, table.column("doc"))
    trel = R.group_agg_ref(top, table.column("hashtag")[idx], n_nodes,
                           valid & matched, "sum")
    kth = top[-1]
    spare = int((scores.astype(np.float32) == kth).sum()
                - (top == kth).sum())
    return trel, float(kth), spare


def score_error(got, want):
    """Max |got - want| over the allowed tolerance (<= 1 passes)."""
    got = np.asarray(got, np.float64)
    tol = SCORE_RTOL * np.abs(want) + SCORE_ATOL_REL * np.abs(want).max()
    check(got.shape == want.shape and np.isfinite(got).all(),
          f"score vector {got.shape} is not finite of shape {want.shape}")
    return float((np.abs(got - want) / tol).max())


def analysis_error(got, want, kth, spare):
    """``score_error`` of one analysis result, allowing one freedom the
    reference cannot pin down: documents tied at the k-th TF-IDF score
    (common: same terms, counts and length) may be kept in another order
    when float32 sums round them apart.  Such exchanges move whole
    multiples of the k-th score between hashtags, net zero, and draw on at
    most ``spare`` tied documents.  Returns (error, documents exchanged)."""
    err = score_error(got, want)
    if err <= 1.0 or kth <= 0.0:
        return err, 0
    moved = np.rint((np.asarray(got, np.float64) - want) / kth)
    swapped = int(moved[moved > 0].sum())
    if moved.sum() != 0 or swapped > spare:
        return err, 0
    return score_error(np.asarray(got, np.float64) - moved * kth,
                       want), swapped


def pallas_kernels_compiled(fn, inputs):
    """Every chosen Pallas node lowers to its compiled TPU kernel: the
    lowered analysis holds a ``tpu_custom_call`` of that kernel (an
    interpreted kernel lowers to plain HLO instead)."""
    chosen = []

    def visit(plan):
        for node in plan.topo():
            if node.subplan is not None:
                visit(node.subplan)
            if node.impl.endswith("_pallas"):
                chosen.append(node.impl)

    visit(fn.concrete)
    text = jax.jit(lambda ins: fn({}, ins)).lower(inputs).as_text()
    calls = text.count("tpu_custom_call")
    for impl in chosen:
        check(impl in KERNEL_OF_IMPL, f"no kernel name known for {impl}")
        check(f'kernel_name = "{KERNEL_OF_IMPL[impl]}"' in text,
              f"{impl} was chosen but its kernel is not compiled")
    check(calls >= len(chosen),
          f"{len(chosen)} Pallas nodes but {calls} compiled kernels")
    return chosen, calls


def make_stores(seed, sizes):
    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    table, graph, corpus = build_social_data(rng, **sizes)
    queries = [corpus.query_vector(rng.zipf(1.4, 6) % corpus.vocab)
               for _ in range(8)]
    log(f"stores from seed {seed}: {table.rows} tweets, {graph.n_nodes} "
        f"hashtags, {graph.n_edges} co-mention edges, {sizes['users']} "
        f"users, {corpus.n_postings} postings over vocab {corpus.vocab} "
        f"({corpus.n_postings / table.rows:.2f} per tweet); built in "
        f"{time.perf_counter() - t0:.1f}s (set-up)")
    return table, graph, corpus, queries


def analysis_phase(rt, seed, sizes):
    table, graph, corpus, queries = make_stores(seed, sizes)
    stores = dict(zip(STORE_NAMES, (table, graph, corpus)))
    payloads = {n: s.payload() for n, s in stores.items()}
    for name, p in payloads.items():
        log(f"resident {name}: {tree_nbytes(p)} bytes")
    analysis, fn = build_analysis(table, graph, corpus, rt.syscat)
    for r in fn.report:
        if r["pattern"] != "xfer_op":
            costs = ", ".join(f"{c} {s:.3g}s" for c, s in r["costs"].items())
            log(f"explain {r['pattern']} -> {r['chosen']} ({r['engine']}); "
                f"estimates {costs}")
    xfers = [r["chosen"] for r in fn.report if r["pattern"] == "xfer_op"]
    log(f"explain xfer_op -> {sorted(set(xfers))} x{len(xfers)}")

    qs = [jnp.asarray(q) for q in queries]
    chosen, calls = pallas_kernels_compiled(fn, {**payloads, "q": qs[0]})
    log(f"compiled Pallas nodes: {chosen or 'none chosen'}; "
        f"{calls} tpu_custom_call in the lowered analysis")

    # 16 clients: 8 unbatched (q0 x4 and q1 x2 exact twins, q2, q3) and a
    # batch_param="q" group of 4 distinct vectors, each sent twice
    plan_q = [0, 0, 0, 0, 1, 1, 2, 3] + [4, 4, 5, 5, 6, 6, 7, 7]
    versions = analysis.store_versions()
    reqs = [AnalysisRequest(rid=i, planned=fn,
                            inputs={**payloads, "q": qs[k]}, params={},
                            tenant=f"client{i % 4}",
                            batch_param="q" if i >= 8 else None,
                            store_versions=versions)
            for i, k in enumerate(plan_q)]
    t0 = time.perf_counter()
    results = rt.serve_analyses(reqs, timeout_s=900)
    log(f"16 analyses served in {time.perf_counter() - t0:.1f}s, compiles "
        f"included (set-up; no speed is read from this)")
    for r in results:
        check(r.ok, f"analysis {r.rid} failed: {r.error}")
    deduped = sum(r.deduped for r in results)
    batched = sum(r.batched for r in results)
    log(f"single-flight twins {deduped}, vmapped {batched}; "
        f"{rt.metrics.analytics_report()}")
    check_no_hidden_failures(rt.recorder)
    check(deduped > 0, "no exact twin ran single-flight")
    check(batched > 0, "the batch_param group did not run vmapped")

    t0 = time.perf_counter()
    pr = graph_reference(table, graph)
    worst, swapped = 0.0, 0
    for k in sorted(set(plan_q)):
        trel, kth, spare = text_reference(table, corpus, graph.n_nodes,
                                          queries[k])
        for r, kk in zip(results, plan_q):
            if kk == k:
                err, n = analysis_error(r.value, pr + trel, kth, spare)
                worst, swapped = max(worst, err), swapped + n
    log(f"NumPy reference in {time.perf_counter() - t0:.1f}s; worst error "
        f"{worst:.3g} of tolerance (rtol {SCORE_RTOL}, atol "
        f"{SCORE_ATOL_REL} x max|ref|); tied k-th documents exchanged: "
        f"{swapped}")
    check(worst <= 1.0, f"analysis scores off the NumPy reference: "
                        f"{worst:.3g} x tolerance")


def check_no_hidden_failures(recorder):
    check(recorder.dropped == 0, "flight recorder dropped events")
    hidden = []
    for ev in recorder.events():
        kind, info = ev.kind, ev.payload
        if kind == "trip":   # executor_error / breaker_open dumps
            kind, info = info.get("reason"), info.get("detail", {})
        if kind in HIDDEN_FAILURES:
            hidden.append((kind, info.get("error")))
    check(not hidden, f"flight recorder holds {hidden}")


# --------------------------------------------------------------------------
# full-width LM serving
# --------------------------------------------------------------------------

def reference_logits(cfg, params, tokens):
    """Plain float32 jax.numpy forward of the dense decoder family:
    pre-norm blocks of GQA attention (qk-norm, rotary) and a gated SiLU
    MLP, RMS norms scaled by ``1 + scale``, tied embeddings."""
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    h, kvh, d = cfg.heads, cfg.kv_heads, cfg.resolved_head_dim
    s = tokens.shape[0]
    pos = jnp.arange(s, dtype=jnp.float32)
    freqs = cfg.rope_theta ** (-jnp.arange(d // 2, dtype=jnp.float32)
                               / (d // 2))
    ang = pos[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def norm(x, scale):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return x * (1.0 + f32(scale))

    def rope(x):
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    table = f32(params["embed"]["table"])
    x = table[tokens]
    mask = jnp.tril(jnp.ones((s, s), bool))
    layers = params["layers_0"]
    for i in range(cfg.n_layers):
        p = jax.tree.map(lambda a: f32(a[i]), layers)
        a, m = p["b0_attn"], p["b0_mlp"]
        y = norm(x, p["b0_ln1"]["scale"])
        q = rope(norm((y @ a["wq"]).reshape(s, h, d), a["q_norm"]))
        k = rope(norm((y @ a["wk"]).reshape(s, kvh, d), a["k_norm"]))
        v = (y @ a["wv"]).reshape(s, kvh, d)
        k, v = jnp.repeat(k, h // kvh, 1), jnp.repeat(v, h // kvh, 1)
        att = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
        att = jax.nn.softmax(jnp.where(mask, att, -jnp.inf), axis=-1)
        x = x + jnp.einsum("hqk,khd->qhd", att, v).reshape(s, h * d) @ a["wo"]
        y = norm(x, p["b0_ln2"]["scale"])
        x = x + (jax.nn.silu(y @ m["wg"]) * (y @ m["wi"])) @ m["wo"]
    x = norm(x, params["final_norm"]["scale"])
    return (x @ table.T)[:, :cfg.vocab]


def lm_phase(rt, seed):
    cfg = rt.cfg
    t0 = time.perf_counter()
    rt.warmup(LM_PROMPT_LENS)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
        f"{cfg.vocab}, compute {cfg.dtype}; warmup (plans + compiles) "
        f"{time.perf_counter() - t0:.1f}s (set-up)")
    reqs = make_trace(np.random.RandomState(seed), cfg, len(LM_PROMPT_LENS),
                      LM_PROMPT_LENS, LM_GEN)
    results = rt.serve(reqs)
    for r in results:
        check(r.ok and len(r.tokens) == LM_GEN,
              f"request {r.rid}: {r.status}, {len(r.tokens)} tokens, "
              f"{r.error}")
    log(f"{len(results)} requests x {LM_GEN} tokens ok")
    check_no_hidden_failures(rt.recorder)

    # the longest prompt's planned prefill against the float32 forward
    req = max(reqs, key=lambda r: r.prompt_len)
    n, bucket = req.prompt_len, rt.bucket_of(req.prompt_len)
    plan = rt.model.build_plan(
        1, bucket, mode="prefill_kv" if rt.kv_mode else "prefill")
    fwd = plan_and_compile(plan, CATALOG, rt.syscat, engines=rt.engines,
                           cache=rt.pc)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = req.prompt
    outs = jax.jit(lambda p, t: fwd(p, {"tokens": t}))(rt.params,
                                                      jnp.asarray(toks))
    logits = outs[0] if isinstance(outs, tuple) else outs
    got = np.asarray(logits[0, :n, :cfg.vocab], np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(reference_logits, static_argnums=0)(
            cfg, rt.params, jnp.asarray(req.prompt, jnp.int32)))
    check(np.isfinite(got).all(), "prefill logits are not finite")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    first = int(np.argmax(got[n - 1]))
    served = results[reqs.index(req)].tokens[0]
    log(f"prefill logits of a {n}-token prompt vs float32 forward: relative "
        f"L2 error {rel:.3g} (tolerance {LM_REL_TOL}); max |diff| "
        f"{np.abs(got - want).max():.3g} of max |ref| "
        f"{np.abs(want).max():.3g}; argmax agreement "
        f"{float((got.argmax(-1) == want.argmax(-1)).mean()):.2f}")
    check(rel <= LM_REL_TOL, f"prefill logits off the float32 forward: {rel}")
    check(served == first, f"served first token {served} is not the "
                           f"prefill argmax {first}")


# --------------------------------------------------------------------------
# four chips: the sharded tri-store against one chip
# --------------------------------------------------------------------------

def four_chip_phase(seed, devices, sizes):
    table, graph, corpus, queries = make_stores(seed, sizes)
    q = jnp.asarray(queries[0])
    _, fn1 = build_analysis(table, graph, corpus, SystemCatalog())
    one = np.asarray(fn1({}, {"tweets": table.payload(),
                              "g": graph.payload(),
                              "cx": corpus.payload(), "q": q}))

    mesh = jax.sharding.Mesh(np.asarray(devices).reshape(4, 1),
                             ("data", "model"))
    sharded = [s.with_shards(4) for s in (table, graph, corpus)]
    _, fn4 = build_analysis(*sharded, syscat_for_mesh(mesh), mesh=mesh)
    dist = sorted({(n.impl, n.attrs["dist"]) for n in fn4.concrete.topo()
                   if n.attrs.get("dist")})
    kinds = sorted({r["chosen"] for r in fn4.report
                    if r["pattern"] == "xfer_op"})
    log(f"sharded plan: dist nodes {dist}; xfer kinds {kinds}")
    check(dist, "shard_stores placed no node on the data axis")

    inputs = shard_store_inputs(mesh, {
        **{n: s.payload() for n, s in zip(STORE_NAMES, sharded)}, "q": q})
    split_bytes = 0
    for name in STORE_NAMES:
        for path, leaf in jax.tree_util.tree_leaves_with_path(inputs[name]):
            key = jax.tree_util.keystr(path)
            shards = {s.device for s in leaf.addressable_shards}
            if leaf.ndim and (name == "tweets" or "blk_" in key):
                # the row / dst-block / doc-block partitions themselves
                check(leaf.shape[0] % 4 == 0 and len(shards) == 4
                      and leaf.addressable_shards[0].data.shape[0]
                      == leaf.shape[0] // 4,
                      f"{name}{key} {leaf.shape} is not split over 4 "
                      f"devices")
            if len(shards) == 4 and not leaf.sharding.is_fully_replicated:
                split_bytes += int(leaf.nbytes)
    total = sum(tree_nbytes(inputs[n]) for n in STORE_NAMES)
    log(f"store bytes split over 4 devices: {split_bytes} of {total}")

    four = np.asarray(fn4({}, inputs))
    # documents tied at the k-th TF-IDF score may be kept in another order
    _, kth, spare = text_reference(table, corpus, graph.n_nodes, queries[0])
    err, swapped = analysis_error(four, one.astype(np.float64), kth, spare)
    log(f"four chips vs one chip: worst error {err:.3g} of tolerance; tied "
        f"k-th documents exchanged: {swapped}")
    check(err <= 1.0, f"sharded result off the one-chip result: {err:.3g}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0)
              for d in devices]
    log(f"bytes in use per device: {in_use}")
    check(all(b > 0 for b in in_use), "a device of the mesh holds nothing")


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded tri-store against one chip")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX's first device is {dev.platform!r}; "
              f"nothing runs on another backend", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"[smoke] {len(devices)} chip(s) attached, {need} needed",
              file=sys.stderr)
        return 2
    hw = hardware_for(dev)
    log(f"device {dev.device_kind} x{len(devices)}; planner peaks "
        f"{hw.peak_flops:.3g} FLOP/s, {hw.hbm_bw:.3g} B/s; compile cache "
        f"{enable_compile_cache()}")

    if args.four_chips:
        four_chip_phase(args.seed, devices[:4], ANALYSIS_SIZES)
    else:
        cfg = get_config(LM_ARCH)
        model = build_model(cfg)
        params, _ = model.init_params(jax.random.key(args.seed))
        rt = AsyncServingRuntime(model, params, max_batch=4, max_seq=128,
                                 recorder=FlightRecorder(capacity=4096),
                                 subplan_budget=256 << 20)
        analysis_phase(rt, args.seed, ANALYSIS_SIZES)
        lm_phase(rt, args.seed)
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in devices[:need]]
    log(f"peak bytes in use per device: {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
