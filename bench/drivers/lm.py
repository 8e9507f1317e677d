"""Driver of LM serving (configurations whose ``driver`` is ``lm``).

Set-up: the program's model of the configuration's architecture, checked
against the configuration's published sizes; weights made by the benchmark
on the device from the seed (``bench/ref_lm.py``); an
``AsyncServingRuntime`` at the configuration's deployment (slots,
context, page size, prefill batch).  The warm-up compiles every prompt
bucket the traffic's lengths fall in (``warmup``), then serves four
requests at once in each bucket, so the batched prefill and its per-row
cache writes are compiled too.

Window: open-loop arrivals (``bench/generator.py``) through
``AsyncServingRuntime.run``.  The benchmark stamps the host clock itself
when a request is submitted, when its first token joins the decode batch,
and when it finishes.

Correctness: once the window has closed and the runtime is freed, a
sample drawn from the seed of the finished requests, the longest among
them, is run through the float32 reference (prompt + served tokens), and
the widest gap by which a served token's logit lies below the reference's
best is compared with its limit.
"""
from __future__ import annotations

import argparse
import gc

from bench import common, generator, ref_lm
from bench.flops import lm_request_flops

WIDTHS = (("d_model", "hidden_size"), ("n_layers", "num_hidden_layers"),
          ("heads", "num_attention_heads"), ("kv_heads", "num_key_value_heads"),
          ("head_dim", "head_dim"), ("d_ff", "intermediate_size"),
          ("vocab", "vocab_size"), ("rope_theta", "rope_theta"),
          ("tied_embeddings", "tie_word_embeddings"))


def with_deployment(mc, cfg: dict):
    """The program's model config ``mc`` set to serve its parameters in the
    configuration's ``param_dtype`` (the program's own option)."""
    import dataclasses
    return dataclasses.replace(mc,
                               param_dtype=cfg["deployment"]["param_dtype"])


def program_model(cfg: dict):
    """The program's model of ``cfg``'s architecture, with every published
    size checked against the configuration file."""
    from repro.configs import get_config
    from repro.models import build_model
    mc = with_deployment(get_config(cfg["deployment"]["arch"]), cfg)
    for ours, theirs in WIDTHS:
        if getattr(mc, ours) != cfg[theirs]:
            raise common.BenchError(
                f"program's {ours} {getattr(mc, ours)} != {theirs} "
                f"{cfg[theirs]} of the configuration")
    if mc.dtype != cfg["deployment"]["compute_dtype"]:
        raise common.BenchError(f"program computes in {mc.dtype}")
    return build_model(mc)


def check_layout(model, params) -> None:
    """The benchmark's weights have the program's tree, shapes and types."""
    import jax
    want = jax.eval_shape(lambda k: model.init_params(k)[0],
                          jax.random.key(0))
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got != want:
        raise common.BenchError("the program's parameter layout is not the "
                                "one bench/ref_lm.py makes")


class Stamps:
    """Host-clock stamps per request id."""

    def __init__(self, rt):
        self.t = {}
        submit, join, finish = rt.submit, rt.scheduler.join, rt._finish

        def on_submit(req):
            self.t.setdefault(req.rid, {})["submit"] = common.now()
            return submit(req)

        def on_join(req, *a, **k):
            self.t.setdefault(req.rid, {})["first"] = common.now()
            return join(req, *a, **k)

        def on_finish(st, *a, **k):
            out = finish(st, *a, **k)
            self.t.setdefault(st.request.rid, {})["finish"] = common.now()
            return out

        rt.submit, rt.scheduler.join, rt._finish = \
            on_submit, on_join, on_finish


def warm_up(rt, prompt_lens) -> None:
    """Compile every prompt bucket the traffic's lengths fall in, one
    bucket at a time (``warmup`` keeps a bucket's outputs alive while it
    compiles the next), then serve a full prefill batch in each bucket,
    alone, so the batched prefill and each row's cache write compile."""
    from repro.serving.runtime import ServeRequest
    buckets = sorted({rt.bucket_of(p) for p in prompt_lens})
    for b in buckets:
        rt.warmup([b])
    width = min(rt.prefill_batch, rt.max_batch)
    for b in buckets:
        n = next(p for p in prompt_lens if rt.bucket_of(p) == b)
        res = rt.serve([ServeRequest(rid=f"warm{b}.{i}",
                                     prompt=tuple([i] * n), gen=2)
                        for i in range(width)], timeout_s=600)
        bad = [r.status for r in res if r.status != "ok"]
        if bad:
            raise common.BenchError(f"warm-up requests failed: {bad}")
        # ``run`` counts every result the runtime holds as done, those of
        # its earlier calls too, so each call's results go after it
        rt._results.clear()


def run(run: common.Run, ctx: common.Ctx) -> None:
    import jax
    from repro.core.ledger import FlightRecorder
    from repro.serving.runtime import AsyncServingRuntime, ServeRequest

    cfg, traffic, seconds = run.config, run.traffic, ctx.seconds
    dep = cfg["deployment"]
    model = program_model(cfg)
    params = ref_lm.make_params(cfg, generator.seed32(ctx.seed))
    check_layout(model, params)
    rt = AsyncServingRuntime(
        model, params, max_batch=dep["max_batch"], max_seq=dep["max_seq"],
        page_size=dep["page_size"], prefill_batch=dep["prefill_batch"],
        recorder=FlightRecorder(capacity=4096))
    warm_up(rt, [p for _, p, _ in generator.lm_lengths(traffic, seconds)])
    for attr, name in (("submit", "bench.lm_submit"),
                       ("_try_join", "bench.lm_join"),
                       ("_decode_tick", "bench.lm_decode_tick")):
        common.annotate(rt, attr, name)
    stamps = Stamps(rt)
    arrivals = generator.lm_arrivals(traffic, seconds, ctx.seed,
                                     cfg["vocab_size"])
    reqs = [ServeRequest(rid=i, prompt=tuple(a["prompt"]), gen=a["gen"],
                         arrival=a["offset"])
            for i, a in enumerate(arrivals)]
    prof = common.Profiler(min(float(traffic["trace_seconds"]), seconds / 2)) \
        if ctx.trace else None
    gc.collect()
    compiles0 = ctx.counter.programs

    t_start = common.now()
    run.setup_s = t_start - ctx.t_process
    tr = common.WindowTrace(prof, t_start, seconds, len(ctx.devices),
                           traffic)
    tick = rt._decode_tick

    def polled_tick():
        tr.poll()
        return tick()
    rt._decode_tick = polled_tick
    results = rt.serve(reqs, timeout_s=seconds + 150)
    tr.finish()
    t0 = rt._t0      # the run's clock: arrival offsets count from here
    compiles = ctx.counter.programs - compiles0
    run.extra["memory_peak_bytes"] = common.memory_peak(ctx.devices)

    recs = []
    for req, res in zip(reqs, results):
        st = stamps.t.get(req.rid, {})
        ok = res.status == "ok" and "first" in st and "finish" in st \
            and len(res.tokens) == req.gen
        rec = {"due": t0 + req.arrival, "submit": st.get("submit"),
               "done": st.get("finish"), "ok": ok, "prompt": req.prompt,
               "tokens": list(res.tokens), "status": res.status}
        if ok:
            rec["ttft_ms"] = (st["first"] - rec["due"]) * 1e3
            rec["tpot_ms"] = ((st["finish"] - st["first"])
                              / max(len(res.tokens) - 1, 1) * 1e3)
            rec["queue_wait_ms"] = (res.metrics.joined_at - req.arrival) * 1e3
        recs.append(rec)
    ok = [r for r in recs if r["ok"]]
    ttft = [r["ttft_ms"] for r in ok]
    tpot = [r["tpot_ms"] for r in ok]
    late = [(r["submit"] - r["due"]) * 1e3 for r in recs if r["submit"]]
    if ok:
        run.end_to_end.update(common.tails("ttft", ttft))
        run.end_to_end.update(common.tails("tpot", tpot))
        last = max(r["done"] for r in ok)
        flops = sum(lm_request_flops(cfg, len(r["prompt"]), len(r["tokens"]))
                    for r in ok)
        run.extra["model_flops_per_s"] = flops / (last - t0)
    run.requests = recs
    run.counters = {"compiles_in_window": compiles}
    if prof is not None and prof.summary is not None:
        run.trace = prof.summary
        run.extra["traced"] = (prof.t_start, prof.t_stop)
    hidden = [ev.kind for ev in rt.recorder.events()
              if ev.kind in ("prefill_fault", "decode_fault")]
    common.log(
        f"window {seconds} s: {len(recs)} due, {len(ok)} ok "
        f"(statuses {sorted({r['status'] for r in recs})}); ttft p50 "
        f"{common.percentile(ttft, 50)} p95 {common.percentile(ttft, 95)} "
        f"ms; tpot p50 {common.percentile(tpot, 50)} p95 "
        f"{common.percentile(tpot, 95)} ms; generator late p95 "
        f"{common.percentile(late, 95)} ms; programs compiled or loaded in "
        f"the window {compiles}; peak {run.extra['memory_peak_bytes']} B")

    # the runtime goes before the reference runs; the weights stay
    rt.pool.cache = None
    del rt, results
    gc.collect()
    sample = _sample(ok, ctx.seed, traffic["check"]["sample"])
    t_ref = common.now()
    gaps = [ref_lm.served_gap(cfg, params, r["prompt"], r["tokens"])
            for r in sample]
    worst = max(gaps) if gaps else float("inf")
    common.log(f"reference: {len(sample)} requests, "
               f"{sum(len(r['tokens']) for r in sample)} served tokens; "
               f"widest logit gap {worst}; {common.now() - t_ref:.1f} s")
    run.extra["sample"] = sample
    run.extra["params"] = params
    run.checks = [
        common.Check("logit_gap", worst, cfg["check"]["logit_gap"]),
        common.Check("failed", run.failed, 0),
        common.Check("hidden_faults", len(hidden), 0),
    ]
    jax.block_until_ready(params)


def _sample(ok: list, seed: int, n: int) -> list:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    if not ok:
        return []
    longest = max(ok, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in ok if r is not longest]
    rng = generator.run_rng(seed, 5)
    pick = rng.choice(len(rest), min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


# --------------------------------------------------------------------------
# what every driver supplies beside ``run``: its tiny size, its requests
# due in a window, and its control
# --------------------------------------------------------------------------

TINY_LM = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, intermediate_size=128,
               vocab_size=512)


def smoke_model(cfg: dict):
    """The program's smoke-size model of ``cfg``'s architecture (the sizes
    of :data:`TINY_LM`), in the configuration's ``param_dtype``."""
    from repro.configs.base import get_smoke_config
    from repro.models import build_model
    return build_model(with_deployment(
        get_smoke_config(cfg["deployment"]["arch"]), cfg))


def tiny(cfg: dict, traffic: dict) -> dict:
    """Cut a configuration and its traffic, in place, to a size the CPU
    runs in seconds (the benchmark's own tests); returns the functions of
    this module that a tiny run replaces: the program's model is its
    smoke size, which the published widths would refuse."""
    cfg.update(TINY_LM)
    cfg["deployment"].update(max_batch=4, max_seq=128)
    traffic.update(rate_per_s=4.0, max_total=128)
    traffic["prompt"].update(median=16, min=4, max=64)
    traffic["output"].update(median=8, min=2, max=32)
    return {"program_model": smoke_model}


def due_in_window(traffic: dict, seconds: float) -> int:
    """Requests due in a window of ``seconds``."""
    return len(generator.lm_lengths(traffic, seconds))


def control(bench: dict, cell: dict, seed: int, seconds: float) -> dict:
    """One whole run of the cell on ``seed`` (its window of ``seconds``),
    and the control's reading on what it served: the reference forward
    with every matmul operand rounded to scaled float8 (e4m3), the gap of
    its first choice at each served position."""
    from bench.run import execute
    devices = common.accelerator(cell["chips"])
    run = execute(argparse.Namespace(seed=seed, seconds=seconds, trace=0),
                  bench, cell, devices, common.CompileCounter())
    cfg, params = run.config, run.extra["params"]
    gaps = [ref_lm.control_gap(cfg, params, r["prompt"], r["tokens"])
            for r in run.extra["sample"]]
    return {"program_logit_gap": run.checks[0].value,
            "control_logit_gap": max(gaps), "control_gaps": gaps,
            "served_tokens": sum(len(r["tokens"])
                                 for r in run.extra["sample"]),
            "correct": run.correct}
