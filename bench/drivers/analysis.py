"""Driver of the served tri-model analysis (configurations whose
``driver`` is ``analysis``).

Set-up: the data from the seed (``bench/social.py``), the program's three
stores over it, the ADIL program planned with the configuration's engines,
and an ``AsyncServingRuntime`` holding a subplan cache.  The warm-up runs
the single-query path twice, which fills the subplan cache with the
query-independent graph half, as the traffic needs.

Chips: the configuration states them (``deployment.chips``), and the
harness holds them to the cell's (``common.check_chips``).  On one chip
nothing is sharded.  On more, the stores are declared partitioned over
them (``with_shards``), the plan is made for a ``("data", "model")`` mesh
of shape (chips, 1), and the payloads are built on the host and placed
once: row, dst-block and doc-block arrays split along the data axis
(``shard_store_inputs``), the query vectors replicated.

Window: every analysis goes through ``run_analyses``, one call per
request, so concurrent calls share the runtime's tenant scheduler,
single-flight map and vmapped batches.  The traffic is an open loop of
Poisson arrivals over a pool of queries (``bench/generator.py``).

Correctness: once the window has closed, a sample drawn from the seed of
the analyses that completed (up to ``per_route`` from each route a result
can take: own execution, single-flight twin, vmapped batch) is compared
with the NumPy reference (``bench/ref_analysis.py``).
"""
from __future__ import annotations

import asyncio
import gc

import numpy as np

from bench import common, generator, social
from bench.ref_analysis import Reference, analysis_error

HIDDEN_FAILURES = ("batch_fallback", "executor_error", "breaker_open")


def hidden_failures(recorder) -> list:
    """Failures the runtime recovered from in silence (a vmapped batch
    re-run query by query, an executor error, an open breaker), from its
    flight recorder."""
    out = []
    if recorder.dropped:
        out.append(("recorder_dropped", recorder.dropped))
    for ev in recorder.events():
        kind, info = ev.kind, ev.payload
        if kind == "trip":
            kind, info = info.get("reason"), info.get("detail", {})
        if kind in HIDDEN_FAILURES:
            out.append((kind, (info or {}).get("error")))
    return out


def tree_nbytes(tree) -> int:
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree)
               if hasattr(x, "nbytes"))


def placement(payloads: dict) -> list:
    """Where each payload leaf lives: its path, global shape, the devices
    holding a part of it, whether it is split or replicated, the rows of
    its first part, and its bytes on each device."""
    import jax
    out = []
    for name, tree in payloads.items():
        for path, x in jax.tree_util.tree_leaves_with_path(tree):
            shards = getattr(x, "addressable_shards", None)
            if shards is None:
                continue
            per = {}
            for sh in shards:
                per[sh.device.id] = per.get(sh.device.id, 0) \
                    + int(sh.data.nbytes)
            out.append({
                "leaf": name + jax.tree_util.keystr(path),
                "shape": list(x.shape), "devices": len(per),
                "split": not x.sharding.is_fully_replicated,
                "shard_rows": (int(shards[0].data.shape[0]) if x.ndim
                               else None),
                "bytes": per})
    return out


class Cell:
    """The program's side of one run: stores, plan, runtime, requests."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import get_smoke_config
        from repro.core.adil_parser import parse_adil
        from repro.core.ir import standard_catalog
        from repro.core.ledger import FlightRecorder
        from repro.launch.mesh import (replicated_sharding,
                                       shard_store_inputs, syscat_for_mesh)
        from repro.models import build_model
        from repro.serving import AnalysisRequest, AsyncServingRuntime
        from repro.stores import store_engines

        self.jnp, self.Request = jnp, AnalysisRequest
        self.cfg, self.traffic = cfg, traffic
        dep = cfg["deployment"]
        chips = dep["chips"]
        self.mesh = None
        if chips > 1:
            self.mesh = jax.sharding.Mesh(
                np.asarray(devices[:chips]).reshape(chips, 1),
                ("data", "model"))
            self._replicated = replicated_sharding(self.mesh)
        t0 = common.now()
        cut = dep["data"]["cut"]
        self.data = social.build_social_data(
            generator.run_rng(seed), **self._sizes(cfg)).cut(**cut)
        t_data = common.now()
        stores = social.build_stores(self.data)
        names = ("tweets", "g", "cx")
        if self.mesh is None:
            self.payloads = {n: s.payload() for n, s in zip(names, stores)}
            t_built = common.now()
        else:
            stores = [s.with_shards(chips) for s in stores]
            # built on the host's CPU device, then placed once
            with jax.default_device(jax.devices("cpu")[0]):
                built = {n: jax.tree.map(np.asarray, s.payload())
                         for n, s in zip(names, stores)}
            t_built = common.now()
            self.payloads = shard_store_inputs(self.mesh, built)
            del built
        jax.block_until_ready(self.payloads)
        t_placed = common.now()
        self.placement = placement(self.payloads)
        per_chip = {}
        for leaf in self.placement:
            for d, b in leaf["bytes"].items():
                per_chip[d] = per_chip.get(d, 0) + b
        common.log(f"data from the seed: {self.data.tweets} tweets, "
                   f"{stores[1].n_edges} co-mention edges, "
                   f"{stores[2].n_postings} postings; resident "
                   + ", ".join(f"{n} {tree_nbytes(p)} B"
                               for n, p in self.payloads.items())
                   + f"; per chip {[per_chip[d] for d in sorted(per_chip)]}"
                   f" B; data {t_data - t0:.1f} s, stores and layouts "
                   f"{t_built - t_data:.1f} s, transfer "
                   f"{t_placed - t_built:.1f} s")
        lm = build_model(get_smoke_config(dep["runtime_lm"]))
        lm_params, _ = lm.init_params(jax.random.key(0))
        self.rt = AsyncServingRuntime(
            lm, lm_params, recorder=FlightRecorder(capacity=4096),
            subplan_budget=dep["subplan_budget_bytes"],
            analysis_tick=dep["analysis_tick"],
            syscat=None if self.mesh is None else syscat_for_mesh(self.mesh))
        analysis = parse_adil(social.adil_script(*stores), standard_catalog())
        for name, store in zip(names, stores):
            analysis.bind(name, store)
        t_plan = common.now()
        self.fn = analysis.compile(
            self.rt.syscat, mesh=self.mesh,
            engines=store_engines(pallas="pallas" in dep["engines"]))
        self.versions = analysis.store_versions()
        chosen = sorted({r["chosen"] for r in self.fn.report
                         if r["pattern"] != "xfer_op"})
        dist = sorted({(n.impl, n.attrs["dist"])
                       for n in self.fn.concrete.topo()
                       if n.attrs.get("dist")})
        common.log(f"plan: {chosen}; sharded nodes {dist}; planned in "
                   f"{common.now() - t_plan:.1f} s")
        self.rid = 0

    @staticmethod
    def _sizes(cfg: dict) -> dict:
        s = cfg["deployment"]["data"]
        return dict(tweets=s["tweets"], hashtags=s["hashtags"],
                    users=s["users"], vocab=s["vocab"],
                    doc_tokens=tuple(s["doc_tokens"]))

    def query(self, terms):
        """(host vector, device vector) of a query's terms; on a mesh the
        device vector is replicated."""
        v = social.query_vector(terms, self.data.vocab)
        if self.mesh is None:
            return v, self.jnp.asarray(v)
        import jax
        return v, jax.device_put(v, self._replicated)

    def request(self, qdev, *, batch: bool, tenant: int):
        self.rid += 1
        return self.Request(
            rid=self.rid, planned=self.fn, inputs={**self.payloads, "q": qdev},
            params={}, tenant=f"t{tenant}", batch_param="q" if batch else None,
            store_versions=self.versions)

    def serve(self, reqs, timeout_s: float):
        return asyncio.run(self.rt.run_analyses(reqs, timeout_s=timeout_s))


def route(res) -> str:
    if res.deduped:
        return "twin"
    if res.batched:
        return "vmapped"
    return "own"


def warm_up(cell: Cell, warm_rng) -> None:
    """Run the path the window takes, at the shapes it uses: one analysis
    executes the whole plan and leaves the query-independent graph half in
    the subplan cache, the second runs the residual behind that cache.

    Each arrival is a ``run_analyses`` call of its own, and the runtime
    drains its tenant queue as soon as a call enqueues, so the window
    never forms a vmapped batch: no batch width is warmed."""
    vocab = cell.data.vocab
    for i in range(2):
        _, qd = cell.query(generator.query_terms(cell.traffic, warm_rng,
                                                 vocab))
        t0 = common.now()
        res = cell.serve([cell.request(qd, batch=False, tenant=0)], 600)
        if not res[0].ok:
            raise common.BenchError(f"warm-up analysis failed: "
                                    f"{res[0].error}")
        common.log(f"warm-up analysis {i}: {common.now() - t0:.2f} s, "
                   f"{res[0].executed} plan nodes executed")


async def _open_loop(cell, arrivals, pool, recs, t0, seconds, prof, chips):
    tasks = []
    tr = common.WindowTrace(prof, t0, seconds, chips, cell.traffic)

    async def one(rec, req):
        res = (await cell.rt.run_analyses([req], timeout_s=seconds + 60))[0]
        rec["done"] = common.now()
        rec["res"] = res
        tr.poll()

    for a in arrivals:
        due = t0 + a["offset"]
        delay = due - common.now()
        if delay > 0:
            await asyncio.sleep(delay)
        tr.poll()
        rec = {"due": due, "submit": common.now(), "done": None,
               "qhost": pool[a["query"]][0]}
        recs.append(rec)
        req = cell.request(pool[a["query"]][1], batch=a["batch"],
                           tenant=a["tenant"])
        tasks.append(asyncio.ensure_future(one(rec, req)))
    await asyncio.wait(tasks, timeout=max(t0 + seconds - common.now(), 0)
                       + 90)
    tr.finish()


def run(run: common.Run, ctx: common.Ctx) -> None:
    cfg, traffic, seconds = run.config, run.traffic, ctx.seconds
    cell = Cell(cfg, traffic, ctx.seed, ctx.devices)
    for attr, name in (("_analysis_exec", "bench.analysis_exec"),
                       ("_run_batched_group", "bench.vmapped_batch")):
        common.annotate(cell.rt, attr, name)
    warm_rng = generator.run_rng(ctx.seed, 3)
    programs = ctx.counter.programs
    warm_up(cell, warm_rng)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in ctx.devices]
    common.log(f"warm-up: programs compiled or loaded "
               f"{ctx.counter.programs - programs}; bytes in use per chip "
               f"{in_use}")
    run.extra["placement"] = cell.placement

    query_rng = generator.run_rng(ctx.seed, 4)
    vocab = cell.data.vocab
    arrivals = generator.analysis_arrivals(traffic, seconds)
    pool = [cell.query(generator.query_terms(traffic, query_rng, vocab))
            for _ in range(traffic["query_pool"]["size"])]
    prof = common.Profiler(min(float(traffic["trace_seconds"]), seconds / 2)) \
        if ctx.trace else None
    gc.collect()
    compiles0 = ctx.counter.programs
    recs: list = []
    t0 = common.now()
    run.setup_s = t0 - ctx.t_process
    asyncio.run(_open_loop(cell, arrivals, pool, recs, t0, seconds, prof,
                           len(ctx.devices)))
    t_end = t0 + seconds
    compiles = ctx.counter.programs - compiles0
    run.extra["memory_peak_bytes"] = common.memory_peak(ctx.devices)

    for r in recs:
        res = r.get("res")
        r["ok"] = bool(res is not None and res.ok and r["done"] is not None)
        r["route"] = route(res) if r["ok"] else None
        if r["ok"]:
            r["latency_ms"] = (r["done"] - r["due"]) * 1e3
    ok = [r for r in recs if r["ok"]]
    lat = [r["latency_ms"] for r in ok]
    late = [(r["submit"] - r["due"]) * 1e3 for r in recs]
    routes = {k: sum(1 for r in ok if r["route"] == k)
              for k in ("own", "twin", "vmapped")}
    done_in_window = sum(1 for r in ok if r["done"] <= t_end)
    run.requests = recs
    own = [r for r in ok if r["route"] == "own"]
    run.counters = {"routes": routes, "compiles_in_window": compiles,
                    "plan_nodes": len(cell.fn.concrete.nodes),
                    "own": len(own),
                    "executed": sum(r["res"].executed for r in own)}
    run.end_to_end.update(common.tails("analysis", lat))
    hidden = hidden_failures(cell.rt.recorder)
    common.log(
        f"window {seconds} s: {len(recs)} due, {len(ok)} ok, "
        f"{done_in_window} done inside; latency p50 "
        f"{common.percentile(lat, 50)} ms p80 {common.percentile(lat, 80)} "
        f"ms p95 {common.percentile(lat, 95)} "
        f"ms over {len(lat)}; generator late p95 "
        f"{common.percentile(late, 95)} ms; routes {routes}; programs "
        f"compiled or loaded in the window {compiles}; hidden failures "
        f"{hidden}; peak {run.extra['memory_peak_bytes']} B")
    if prof is not None and prof.summary is not None:
        run.trace = prof.summary
        run.extra["traced"] = (prof.t_start, prof.t_stop)

    # the program's state goes before the reference runs
    sample = _sample(ok, ctx.seed, traffic["check"]["per_route"])
    got = [(r, np.asarray(r["res"].value)) for r in sample]
    for r in recs:
        r.pop("res", None)
    data = cell.data
    del cell
    gc.collect()

    t_ref = common.now()
    ref = Reference(data)
    worst = 0.0 if got else float("inf")
    swapped, by_q = 0, {}
    for r, value in got:
        q = r["qhost"]
        key = q.tobytes()
        if key not in by_q:
            by_q[key] = ref.result(q)
        want, kth, spare = by_q[key]
        err, n = analysis_error(value, want, kth, spare)
        worst, swapped = max(worst, err), swapped + n
    common.log(f"reference: {len(got)} results over {len(by_q)} queries, "
               f"routes {sorted({r['route'] for r, _ in got})}; worst "
               f"{worst} of the tolerance; tied k-th documents exchanged "
               f"{swapped}; {common.now() - t_ref:.1f} s")
    limits = cfg["check"]
    run.checks = [
        common.Check("score_err", worst, limits["score_err"]),
        common.Check("failed", run.failed, 0),
        common.Check("hidden_failures", len(hidden), 0),
    ]


def _sample(ok: list, seed: int, per_route: int) -> list:
    """Up to ``per_route`` completed results of each route, drawn from the
    seed."""
    rng = generator.run_rng(seed, 5)
    out = []
    for k in ("own", "twin", "vmapped"):
        rs = [r for r in ok if r["route"] == k]
        if rs:
            pick = rng.choice(len(rs), min(per_route, len(rs)),
                              replace=False)
            out.extend(rs[i] for i in sorted(pick))
    return out


# --------------------------------------------------------------------------
# what every driver supplies beside ``run``: its tiny size, its requests
# due in a window, and its control
# --------------------------------------------------------------------------

TINY_DATA = dict(tweets=1 << 13, hashtags=1 << 8, users=1 << 10,
                 vocab=1 << 9, doc_tokens=[7, 15])
TINY_CUT = dict(postings=56000, edges=6000)


def tiny(cfg: dict, traffic: dict) -> dict:
    """Cut a configuration and its traffic, in place, to a size the CPU
    runs in seconds (the benchmark's own tests); returns the functions of
    this module that a tiny run replaces (none).  Arrivals come at 40/s
    over the chips: the CPU's devices share its cores."""
    cfg["deployment"]["data"] = dict(TINY_DATA, cut=TINY_CUT)
    cfg["deployment"]["analysis_tick"] = 4
    if "rate_per_s" in traffic:
        traffic["rate_per_s"] = 40.0 / cfg["deployment"]["chips"]
    return {}


def due_in_window(traffic: dict, seconds: float) -> int:
    """Analyses due in a window of ``seconds``."""
    return len(generator.analysis_arrivals(traffic, seconds))


def control(bench: dict, cell: dict, seed: int, seconds: float,
            n_queries: int = 8) -> dict:
    """The control's reading on ``seed``: the NumPy reference with every
    intermediate rounded to bfloat16, over the queries a run of the cell
    would draw, compared with the float64 reference as a run compares the
    program (``seconds`` is not used: no window is needed)."""
    from bench.ref_analysis import bf16
    cfg, traffic = common.config_of(bench, cell), common.traffic_of(cell)
    data = social.build_social_data(
        generator.run_rng(seed), **Cell._sizes(cfg)).cut(
            **cfg["deployment"]["data"]["cut"])
    ref, low = Reference(data), Reference(data, rounding=bf16)
    rng = generator.run_rng(seed, 4)
    worst = []
    for _ in range(n_queries):
        q = social.query_vector(
            generator.query_terms(traffic, rng, data.vocab), data.vocab)
        want, kth, spare = ref.result(q)
        worst.append(analysis_error(low.result(q)[0], want, kth, spare)[0])
    # the number a run compares is the worst over its sample
    return {"control_score_err": max(worst), "control_per_query": worst}
