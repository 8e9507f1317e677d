"""Physical-plan executor: walks the chosen physical DAG and emits JAX.

The executor is the AWESOME "execution stage": it receives the optimized
logical plan, generates candidate physical plans (physical.py), asks the
learned cost model to pick each virtual node's winner (§6.3), applies the
partitioned-data-parallelism insertion (§5.2), and then interprets the
resulting DAG as a pure JAX function — jit-able, differentiable, and
shardable on a mesh.

Param binding: nodes carry a ``pp`` attr (param path into the model's param
pytree).  ``scan_layers_xla`` executes its subplan under ``jax.lax.scan``
over the stacked per-layer params (the paper's Map node, with map-fusion
applied at the logical level), with optional rematerialization policy.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field, replace
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from .buffering import BufferingDecision
from .cost_model import CostModel, raw_features
from .engines import dispatch, get_engine, resolve_engines
from .ir import FunctionCatalog, Plan, SystemCatalog
from .physical import PHYS_OPS, PhysPlan
from ..layers import attention as A
from ..layers import embedding as E
from ..layers import mamba as M
from ..layers import mlp as F
from ..layers import moe as X
from ..layers import rwkv as R
from ..layers.common import rmsnorm

P = jax.sharding.PartitionSpec


# --------------------------------------------------------------------------
# sharding rules: semantic dim name -> mesh axes
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ShardingRules:
    """MaxText-style logical-axis rules.  ``param`` maps weight dim names,
    ``act`` maps activation dim names."""

    act: tuple = (
        ("batch", ("pod", "data")),
        ("heads", ("model",)),
        ("kv_heads", ("model",)),
        ("ffn", ("model",)),
        ("vocab", ("model",)),
        ("experts", ("model",)),
    )
    param: tuple = (
        ("embed", ("data",)),          # FSDP / ZeRO-3: shard embed over data
        ("vocab", ("model",)),
        ("ffn", ("model",)),
        ("heads_flat", ("model",)),
        ("kv_flat", ("model",)),
        ("experts", ("model",)),
        ("inner", ("model",)),
        ("inner_cat", ("model",)),
        ("inner_cat2", ("model",)),
    )
    # expert weights already divide 16× over `model` via EP; FSDP-sharding
    # their embed dim over `data` additionally makes every expert matmul a
    # partial-sum + all-reduce of the (E, tokens, ffn) output (measured
    # 1.26e12 B/device on llama4×train_4k).  True ⇒ replicate expert weights
    # over data, killing that all-reduce.
    no_fsdp_experts: bool = False

    def _lookup(self, table, dim, mesh):
        for d, axes in table:
            if d == dim:
                ax = tuple(a for a in axes if a in mesh.axis_names)
                if len(ax) == 1:
                    return ax[0]
                return ax if ax else None
        return None

    def _spec(self, table, dims, mesh, *, is_param=False) -> P:
        # each mesh axis may appear at most once per spec: first dim wins
        used: set = set()
        out = []
        skip_fsdp = (is_param and self.no_fsdp_experts
                     and "experts" in dims)
        for d in dims:
            if skip_fsdp and d == "embed":
                out.append(None)
                continue
            ax = self._lookup(table, d, mesh)
            axes = (ax,) if isinstance(ax, str) else (ax or ())
            if any(a in used for a in axes):
                out.append(None)
                continue
            used.update(axes)
            out.append(ax)
        return P(*out)

    def act_spec(self, dims, mesh) -> P:
        return self._spec(self.act, dims, mesh)

    def param_spec(self, dims, mesh) -> P:
        return self._spec(self.param, dims, mesh, is_param=True)


def params_sharding(specs_tree, mesh, rules: ShardingRules):
    """Map a specs pytree (tuples of dim names) to NamedShardings."""
    def one(spec):
        return jax.sharding.NamedSharding(mesh, rules.param_spec(spec, mesh))
    return jax.tree.map(one, specs_tree,
                        is_leaf=lambda s: isinstance(s, tuple) and all(
                            isinstance(x, str) for x in s))


# --------------------------------------------------------------------------
# execution context
# --------------------------------------------------------------------------

@dataclass
class ExecContext:
    root: Any                       # full param pytree
    scope: Any                      # current scope (layer slice under scan)
    aux: dict = field(default_factory=dict)   # positions, masks, memory, ...
    mesh: Optional[Any] = None
    rules: ShardingRules = ShardingRules()
    tracer: Optional[Any] = None    # core.tracing.Tracer; None = fast path
    faults: Optional[Any] = None    # core.faults.FaultInjector; None = off

    def params_for(self, node):
        path = node.attrs.get("pp")
        if path is None:
            return self.scope
        base = self.root if node.attrs.get("shared") else self.scope
        for k in path:
            base = base[k]
        return base

    def constrain(self, x, dims):
        if self.mesh is None or not hasattr(x, "ndim"):
            return x
        if len(dims) != x.ndim:
            return x
        return jax.lax.with_sharding_constraint(
            x, jax.sharding.NamedSharding(self.mesh,
                                          self.rules.act_spec(dims, self.mesh)))


# --------------------------------------------------------------------------
# impl registration — each engine owns its dispatch table (engines.py)
# --------------------------------------------------------------------------

def impl(*names, engine: str = "xla"):
    """Register a physical-op implementation under a named engine.  The
    executor dispatches each node through the engine that registered its
    impl (the tri-store's per-engine execution, §2)."""
    return get_engine(engine).impl(*names)


@impl("identity", "store")
def _i_identity(ctx, args, node):
    return args[0]


@impl("const")
def _i_const(ctx, args, node):
    return node.attrs["value"]


@impl("partition")
def _i_partition(ctx, args, node):
    x = args[0]
    if ctx.mesh is None or not hasattr(x, "ndim"):
        return x
    dims = [None] * x.ndim
    dims[node.attrs.get("dim_index", 0)] = node.attrs.get("dim", "batch")
    spec = [None] * x.ndim
    spec[node.attrs.get("dim_index", 0)] = tuple(
        a for a in ("pod", "data") if a in ctx.mesh.axis_names) or None
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, P(*spec)))


@impl("merge")
def _i_merge(ctx, args, node):
    x = args[0]
    if ctx.mesh is None or not hasattr(x, "ndim"):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, P(*([None] * x.ndim))))


@impl("embed_gather")
def _i_embed(ctx, args, node):
    p = ctx.params_for(node)
    out = E.embed(p, args[0], scale=node.attrs.get("scale", False))
    out = out.astype(node.attrs.get("dtype", out.dtype))
    return ctx.constrain(out, ("batch", None, None))


@impl("rmsnorm_xla")
def _i_rmsnorm(ctx, args, node):
    p = ctx.params_for(node)
    return rmsnorm(args[0], p["scale"])


@impl("residual_add_xla")
def _i_resid(ctx, args, node):
    return args[0] + args[1]


def _attn_cfg(node):
    a = node.attrs
    return a["heads"], a["kv_heads"], a["head_dim"]


@impl("q_proj_xla")
def _i_qproj(ctx, args, node):
    h, k, d = _attn_cfg(node)
    return A.project_q(ctx.params_for(node), args[0], h, d)


@impl("k_proj_xla")
def _i_kproj(ctx, args, node):
    h, k, d = _attn_cfg(node)
    return A.project_kv(ctx.params_for(node), args[0], k, d)[0]


@impl("v_proj_xla")
def _i_vproj(ctx, args, node):
    h, k, d = _attn_cfg(node)
    return A.project_kv(ctx.params_for(node), args[0], k, d)[1]


@impl("pack_qkv_xla")
def _i_pack(ctx, args, node):
    return tuple(args)


@impl("qkv_proj_fused")
def _i_qkv_fused(ctx, args, node):
    h, k, d = _attn_cfg(node)
    q, kk, vv = A.project_qkv_fused(ctx.params_for(node), args[0], h, k, d)
    model_size = (ctx.mesh.shape.get("model", 1)
                  if ctx.mesh is not None else 1)
    if h % max(model_size, 1) == 0:
        q = ctx.constrain(q, ("batch", None, "heads", None))
    if k % max(model_size, 1) == 0:
        # GQA: constrain kv heads only when divisible; otherwise leave the
        # layout to propagation (kv replicates across excess model shards)
        kk = ctx.constrain(kk, ("batch", None, "kv_heads", None))
        vv = ctx.constrain(vv, ("batch", None, "kv_heads", None))
    return (q, kk, vv)


def _prep(ctx, node, q, k):
    p = ctx.params_for(node)
    pos = ctx.aux.get("positions")
    if pos is None:
        pos = jnp.arange(q.shape[1])[None, :]
    return A.qk_prep(p, q, k, pos, qk_norm=node.attrs.get("qk_norm", False),
                     use_rope=node.attrs.get("rope", True),
                     rope_theta=node.attrs.get("rope_theta", 10000.0))


def _emit_kv(ctx, node, k, v):
    """KV export hook: inside a ``collect_kv`` scan, sdpa impls append their
    prepped K (post qk-norm/RoPE — exactly what the decode cache stores) and
    raw V to the sink the scan body planted in ``ctx.aux``."""
    sink = ctx.aux.get("kv_sink")
    if sink is not None and node.attrs.get("emit_kv"):
        sink.append((k, v))


@impl("sdpa_xla")
def _i_sdpa(ctx, args, node):
    q, k, v = args[0]
    q, k = _prep(ctx, node, q, k)
    _emit_kv(ctx, node, k, v)
    return A.sdpa_full(q, k, v, causal=node.attrs.get("causal", True),
                       window=node.attrs.get("window", 0) or 0)


@impl("sdpa_banded_xla")
def _i_banded(ctx, args, node):
    q, k, v = args[0]
    q, k = _prep(ctx, node, q, k)
    _emit_kv(ctx, node, k, v)
    return A.sdpa_banded(q, k, v, window=node.attrs.get("window", 0) or 0,
                         causal=node.attrs.get("causal", True))


@impl("attn_flash_pallas", engine="pallas")
def _i_flash(ctx, args, node):
    q, k, v = args[0]
    q, k = _prep(ctx, node, q, k)
    _emit_kv(ctx, node, k, v)
    return A.sdpa_flash(q, k, v, causal=node.attrs.get("causal", True),
                        window=node.attrs.get("window", 0) or 0)


@impl("out_proj_xla")
def _i_outproj(ctx, args, node):
    out = A.out_project(ctx.params_for(node), args[0])
    return ctx.constrain(out, ("batch", None, None))


@impl("cross_attention_xla")
def _i_xattn(ctx, args, node):
    x, mem = args
    p = ctx.params_for(node)
    h, k, d = _attn_cfg(node)
    q = A.project_q(p, x, h, d)
    kk, vv = A.project_kv(p, mem, k, d)
    out = A.sdpa_full(q, kk, vv, causal=False)
    return A.out_project(p, out)


@impl("ffn_up_xla")
def _i_ffn_up(ctx, args, node):
    return F.ffn_up(ctx.params_for(node), args[0])


@impl("ffn_gate_xla")
def _i_ffn_gate(ctx, args, node):
    return F.ffn_gate(ctx.params_for(node), args[0])


@impl("ffn_glu_xla")
def _i_ffn_glu(ctx, args, node):
    return F.ffn_glu(args[0], args[1], node.attrs.get("act", "silu"))


@impl("ffn_act_xla")
def _i_ffn_act(ctx, args, node):
    return F.ffn_act(args[0], node.attrs.get("act", "gelu"))


@impl("ffn_down_xla")
def _i_ffn_down(ctx, args, node):
    out = F.ffn_down(ctx.params_for(node), args[0])
    return ctx.constrain(out, ("batch", None, None))


@impl("mlp_fused_xla")
def _i_mlp(ctx, args, node):
    out = F.mlp_fused(ctx.params_for(node), args[0],
                      gated=node.attrs.get("gated", True),
                      act=node.attrs.get("act"))
    return ctx.constrain(out, ("batch", None, None))


@impl("moe_dense_onehot")
def _i_moe_dense(ctx, args, node):
    a = node.attrs
    return X.moe_dense(ctx.params_for(node), args[0], top_k=a["top_k"],
                       experts=a["experts"], act=a.get("act", "silu"),
                       capacity_factor=a.get("capacity_factor", 2.0),
                       constrain=ctx.constrain if a.get("pin_moe") else None)


@impl("moe_dropping")
def _i_moe_drop(ctx, args, node):
    a = node.attrs
    return X.moe_dropping(ctx.params_for(node), args[0], top_k=a["top_k"],
                          experts=a["experts"], act=a.get("act", "silu"),
                          constrain=ctx.constrain if a.get("pin_moe") else None)


@impl("moe_gmm_pallas", engine="pallas")
def _i_moe_gmm(ctx, args, node):
    a = node.attrs
    return X.moe_gmm(ctx.params_for(node), args[0], top_k=a["top_k"],
                     experts=a["experts"], act=a.get("act", "silu"),
                     constrain=ctx.constrain if a.get("pin_moe") else None)


@impl("wkv6_scan_xla")
def _i_wkv_xla(ctx, args, node):
    a = node.attrs
    return R.rwkv_time_mix(ctx.params_for(node), args[0], heads=a["heads"],
                           head_dim=a["head_dim"], use_kernel=False)


@impl("wkv6_pallas", engine="pallas")
def _i_wkv_pl(ctx, args, node):
    a = node.attrs
    return R.rwkv_time_mix(ctx.params_for(node), args[0], heads=a["heads"],
                           head_dim=a["head_dim"], use_kernel=True)


@impl("ssd_chunked_xla")
def _i_ssd_xla(ctx, args, node):
    a = node.attrs
    cfg = {"embed": a["embed"], "state": a["state"],
           "expand": a.get("expand", 2), "head_dim": a["head_dim"]}
    return M.mamba2_block(ctx.params_for(node), args[0], cfg,
                          use_kernel=False)


@impl("ssd_pallas", engine="pallas")
def _i_ssd_pl(ctx, args, node):
    a = node.attrs
    cfg = {"embed": a["embed"], "state": a["state"],
           "expand": a.get("expand", 2), "head_dim": a["head_dim"]}
    return M.mamba2_block(ctx.params_for(node), args[0], cfg,
                          use_kernel=True)


@impl("rwkv_channel_mix")
def _i_rwkv_cm(ctx, args, node):
    return R.rwkv_channel_mix(ctx.params_for(node), args[0])


@impl("unembed_matmul")
def _i_unembed(ctx, args, node):
    out = E.unembed(ctx.params_for(node), args[0])
    true_v = node.attrs.get("true_vocab")
    if true_v and true_v < out.shape[-1]:
        out = E.mask_padded_logits(out, true_v)
    return ctx.constrain(out, ("batch", None, "vocab"))


@impl("softmax_xent_xla")
def _i_xent(ctx, args, node):
    return E.softmax_xent(args[0], args[1])


@impl("concat_seq")
def _i_concat_seq(ctx, args, node):
    a, b = args
    return jnp.concatenate([a.astype(b.dtype), b], axis=node.attrs.get("axis", 1))


@impl("scan_layers_xla")
def _i_scan(ctx, args, node):
    carry0 = args[0]
    extras = args[1:]                      # broadcast inputs (enc-dec memory)
    p_stack = ctx.params_for(node)
    sub = node.subplan
    in_names = list(sub.inputs.keys())
    extra_env = dict(zip(in_names[1:], extras))
    remat = node.attrs.get("remat", "none")
    collect_kv = bool(node.attrs.get("collect_kv"))

    def body(carry, layer_p):
        # a fresh sink per trace: emit_kv sdpa impls append (K, V) in subplan
        # topo order; lax.scan stacks them over layers as ys
        sink: list = []
        aux = {**ctx.aux, "kv_sink": sink} if collect_kv else ctx.aux
        ctx2 = replace(ctx, scope=layer_p, aux=aux)
        outs = run_plan(sub, ctx2, {in_names[0]: carry, **extra_env})
        return outs[0], (tuple(sink) if collect_kv else None)

    if remat and remat != "none":
        policy = {
            "full": None,
            "dots": jax.checkpoint_policies.checkpoint_dots,
            "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        }.get(remat)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    y, ys = jax.lax.scan(body, carry0, p_stack,
                         unroll=node.attrs.get("unroll", 1))
    if collect_kv:
        # (carry, ((K, V), ...)) — K/V stacked to (layers, B, S, KV, D),
        # exactly the decode cache layout; tuple_get nodes project the pair
        return (y, ys)
    return y


@impl("tuple_get_xla")
def _i_tuple_get(ctx, args, node):
    return args[0][node.attrs["index"]]


@impl("map")
def _i_map(ctx, args, node):
    sub = node.subplan
    (in_name,) = sub.inputs.keys()
    return [run_plan(sub, ctx, {in_name: v})[0] for v in args[0]]


@impl("reduce")
def _i_reduce(ctx, args, node):
    fn = node.attrs["fn"]
    vals = args[0]
    acc = vals[0]
    for v in vals[1:]:
        acc = fn(acc, v) if callable(fn) else acc + v
    return acc


@impl("filter")
def _i_filter(ctx, args, node):
    pred = node.attrs["predicate"]
    return [v for v in args[0] if pred(v)]


# --------------------------------------------------------------------------
# plan execution
# --------------------------------------------------------------------------

def run_plan(pplan: PhysPlan, ctx: ExecContext, values: dict) -> tuple:
    tracer = ctx.tracer
    traced = tracer is not None and tracer.enabled
    if not traced and ctx.faults is None:
        # the untouched fast path: tracing and fault injection both off
        # means zero per-op overhead
        env = dict(values)
        for n in pplan.topo():
            opdef = PHYS_OPS.get(n.impl)
            fn = dispatch(n.impl, opdef.backend if opdef else None)
            if fn is None:
                raise NotImplementedError(
                    f"no engine implements {n.impl!r}")
            env[n.id] = fn(ctx, [env[i] for i in n.inputs], n)
        return tuple(env[o] for o in pplan.outputs)
    if traced:
        return _run_plan_traced(pplan, ctx, values)
    return _run_plan_faulted(pplan, ctx, values)


def run_plan_subset(pplan: PhysPlan, ctx: ExecContext, values: dict,
                    node_ids) -> dict:
    """Execute only ``node_ids`` of a physical plan (in plan topo order),
    seeding the environment from ``values`` — plan inputs *plus* any
    already-materialized node outputs.  The cross-query MQO pass
    (``core/mqo.py``) splits a plan at its subplan-cache-hit frontier and
    runs just the residual suffix through this; the op dispatch is the same
    fast path as :func:`run_plan`.  Returns the full environment so the
    caller can both extract the plan outputs and insert fresh
    intermediates into the cache."""
    wanted = set(node_ids)
    env = dict(values)
    for n in pplan.topo():
        if n.id not in wanted:
            continue
        opdef = PHYS_OPS.get(n.impl)
        fn = dispatch(n.impl, opdef.backend if opdef else None)
        if fn is None:
            raise NotImplementedError(f"no engine implements {n.impl!r}")
        env[n.id] = fn(ctx, [env[i] for i in n.inputs], n)
    return env


def _fault_site(n) -> tuple:
    """Site key for a physical node: xfer/collective nodes get their own
    category (the "sharded" failure class), everything else is "node"."""
    if n.impl.startswith("xfer_"):
        return ("xfer", n.id, n.impl)
    return ("node", n.id, n.impl)


def _run_plan_faulted(pplan: PhysPlan, ctx: ExecContext,
                      values: dict) -> tuple:
    """run_plan with a FaultInjector at every node boundary.  Impl
    exceptions (injected or real) are wrapped into the ExecError taxonomy
    with their site attached, so the resilience layer can classify and the
    breaker can pick a fallback class."""
    from .resilience import classify
    faults = ctx.faults
    env = dict(values)
    for n in pplan.topo():
        opdef = PHYS_OPS.get(n.impl)
        fn = dispatch(n.impl, opdef.backend if opdef else None)
        if fn is None:
            raise NotImplementedError(f"no engine implements {n.impl!r}")
        engine = (opdef.backend or "xla") if opdef else "xla"
        try:
            faults.check(_fault_site(n))
            env[n.id] = fn(ctx, [env[i] for i in n.inputs], n)
        except Exception as exc:
            raise classify(exc, node=n, engine=engine) from exc
    return tuple(env[o] for o in pplan.outputs)


def _run_plan_traced(pplan: PhysPlan, ctx: ExecContext, values: dict) -> tuple:
    """run_plan with one span per physical op.  Span durations are dispatch
    times (JAX async dispatch); the caller device-syncs once per run.
    Device-side observations (BoundedRel counts, overflow flags) are
    *deferred* into the tracer and fetched in one transfer at resolve()."""
    from .tracing import tree_bytes, xfer_wire_bytes
    tracer = ctx.tracer
    n_data = 1
    if ctx.mesh is not None and "data" in getattr(ctx.mesh, "axis_names", ()):
        n_data = int(ctx.mesh.shape["data"])
    env = dict(values)
    for n in pplan.topo():
        opdef = PHYS_OPS.get(n.impl)
        fn = dispatch(n.impl, opdef.backend if opdef else None)
        if fn is None:
            raise NotImplementedError(f"no engine implements {n.impl!r}")
        attrs = {"impl": n.impl,
                 "engine": (opdef.backend or "xla") if opdef else "xla"}
        if "dist" in n.attrs:
            attrs["dist"] = n.attrs["dist"]
        with tracer.span(n.id, "op", **attrs) as sp:
            if ctx.faults is not None:
                ctx.faults.check(_fault_site(n))
            out = fn(ctx, [env[i] for i in n.inputs], n)
            if n.impl.startswith("xfer_"):
                kind = n.impl[len("xfer_"):]
                payload = tree_bytes(out)
                sp.attrs["xfer_kind"] = kind
                sp.attrs["payload_bytes"] = payload
                sp.attrs["wire_bytes"] = xfer_wire_bytes(kind, payload,
                                                         n_data)
            # duck-typed BoundedRel (avoids a core -> stores import): its
            # count/overflow are device scalars — defer, don't fetch
            if hasattr(out, "cols") and hasattr(out, "valid"):
                tracer.defer("count", out.count)
                tracer.defer("overflow", out.overflow)
                sp.attrs["capacity"] = int(out.capacity)
        env[n.id] = out
    return tuple(env[o] for o in pplan.outputs)


# --------------------------------------------------------------------------
# end-to-end: logical plan -> planned jittable function
# --------------------------------------------------------------------------

def _drain_counts(resolved, feedback) -> None:
    """Fold already-resolved count-sink entries into a feedback store."""
    for site, count, capacity in resolved:
        if site and site[0] == "compact_overflow":
            # a capacity bound dropped rows: flag the originating
            # predicate site so re-planning backs off from compacting it
            if count > 0:
                feedback.note_overflow(tuple(site[1]))
            continue
        feedback.record(site, count, capacity)


@dataclass
class PlannedFunction:
    """A cached-able staged plan bound to one runtime context.

    The planning product itself (logical_opt / candidates / concrete plan /
    choices / buffering / EXPLAIN trace) lives in the StagedPhysicalPlan —
    the unit the plan cache stores; this wrapper adds the runtime-only
    bindings (mesh, sharding rules) plus legacy field access
    for existing callers."""

    logical: Plan
    pplan: PhysPlan                  # with virtual nodes (pre-choice)
    concrete: PhysPlan               # chosen + data-parallelized
    choices: dict
    report: list
    buffering: BufferingDecision
    syscat: SystemCatalog
    rules: ShardingRules
    mesh: Optional[Any] = None
    plan_id: str = ""
    staged: Optional[Any] = None     # StagedPhysicalPlan
    faults: Optional[Any] = None     # core.faults.FaultInjector; None = off
    last_run_trace: Optional[Any] = None   # RunTrace of the last analyze()
    _predicted: Optional[dict] = None      # node id -> (seconds, features)

    @classmethod
    def from_staged(cls, staged, syscat: SystemCatalog, *,
                    rules: "ShardingRules" = None,
                    mesh=None) -> "PlannedFunction":
        return cls(staged.logical, staged.pplan, staged.concrete,
                   staged.choices, staged.report, staged.buffering,
                   syscat, rules or ShardingRules(), mesh,
                   staged.plan_id, staged)

    def explain(self, analyze=False) -> str:
        """The plan-time EXPLAIN report; with ``analyze`` the runtime
        section merges in.  ``analyze=True`` uses the last :meth:`analyze`
        run's trace; a RunTrace may also be passed directly."""
        if self.staged is None:
            return ""
        trace = None
        if analyze is not False and analyze is not None:
            trace = analyze if hasattr(analyze, "spans") \
                else self.last_run_trace
            if trace is None:
                raise ValueError(
                    "explain(analyze=True) needs a run trace: call "
                    ".analyze(params, inputs) first")
        return self.staged.explain(analyze=trace)

    def __call__(self, params, inputs: dict, aux: Optional[dict] = None):
        ctx = ExecContext(root=params, scope=params, aux=aux or {},
                          mesh=self.mesh, rules=self.rules,
                          faults=self.faults)
        outs = run_plan(self.concrete, ctx, inputs)
        return outs if len(outs) > 1 else outs[0]

    # -- EXPLAIN ANALYZE ----------------------------------------------------
    def _predict_costs(self, cost_model=None) -> dict:
        """Cost-model predictions per concrete node (memoized: the plan is
        immutable, so one walk serves every analyze run)."""
        if self._predicted is not None and cost_model is None:
            return self._predicted
        cm = cost_model or CostModel()
        predicted: dict = {}

        def visit(plan):
            for n in plan.topo():
                if n.subplan is not None:
                    visit(n.subplan)
                in_types = [plan.types.get(i) or plan.inputs.get(i)
                            for i in n.inputs]
                try:
                    feats = raw_features(n.impl, in_types, n.attrs,
                                         self.syscat)
                    sec = cm.op_seconds(n.impl, in_types, n.attrs,
                                        self.syscat)
                except Exception:
                    continue
                predicted[n.id] = (float(sec), feats)

        visit(self.concrete)
        if cost_model is None:
            object.__setattr__(self, "_predicted", predicted)
        return predicted

    def analyze(self, params, inputs: dict, aux: Optional[dict] = None, *,
                feedback=None, cost_model=None, recorder=None,
                trip_context=None):
        """EXPLAIN ANALYZE execution: run the plan **eagerly** under a span
        tracer, device-sync **once** at the end, and build a
        :class:`~repro.core.tracing.RunTrace` pairing every physical node's
        observed dispatch-ms / counts / xfer bytes with the cost model's
        prediction.  The trace lands in ``self.last_run_trace`` (rendered by
        ``explain(analyze=True)``) and its ``(impl, features, observed_s)``
        samples feed ``core.feedback.fit_weights``.  With ``feedback``
        given, the count sink also drains into it (superset of
        :meth:`observe`).  With ``recorder`` (a
        :class:`~repro.core.ledger.FlightRecorder`), the run's trace summary
        lands in the ring, and two incident triggers trip a dump: an
        executor exception, and any BoundedRel overflow observed in the
        resolved counts.  ``trip_context`` — a zero-arg callable returning a
        dict — is merged into the ``executor_error`` trip detail, letting
        the serving runtime attach the ledger snapshot + metrics report so
        an incident dump shows memory/occupancy state at failure time.
        Returns the plan outputs, like ``__call__``."""
        from .tracing import RunTrace, Tracer
        tracer = Tracer()
        sink: list = []
        run_aux = dict(aux or {})
        run_aux["count_sink"] = sink
        ctx = ExecContext(root=params, scope=params, aux=run_aux,
                          mesh=self.mesh, rules=self.rules,
                          tracer=tracer, faults=self.faults)
        t0 = time.perf_counter()
        try:
            with tracer.span("run", "run", plan_id=self.plan_id):
                outs = run_plan(self.concrete, ctx, inputs)
            with tracer.span("device_sync", "sync") as sync_sp:
                jax.block_until_ready(outs)
        except Exception as exc:
            if recorder is not None:
                detail = {"plan_id": self.plan_id, "error": repr(exc)}
                if trip_context is not None:
                    try:
                        detail.update(trip_context() or {})
                    except Exception:
                        pass
                recorder.trip("executor_error", detail)
            raise
        wall_ms = (time.perf_counter() - t0) * 1e3
        # ONE device_get: deferred span attrs + the count sink together
        counts = tracer.resolve(sink)
        predicted = self._predict_costs(cost_model)
        samples = []
        for sp in tracer.spans:
            hit = predicted.get(sp.name)
            if hit is None:
                continue
            sec, feats = hit
            sp.attrs["predicted_s"] = sec
            samples.append((sp.attrs.get("impl", sp.name), feats, sp.dur))
        trace = RunTrace(spans=list(tracer.spans), wall_ms=wall_ms,
                         sync_ms=sync_sp.dur_ms if sync_sp else 0.0,
                         counts=counts, samples=samples,
                         plan_id=self.plan_id)
        object.__setattr__(self, "last_run_trace", trace)
        if recorder is not None:
            recorder.record_trace(trace)
            overflows = [
                {"site": list(map(str, site)), "count": float(c),
                 "capacity": int(cap)}
                for site, c, cap in counts
                if site and site[0] == "compact_overflow" and c > 0]
            overflows += [
                {"span": sp.name, "capacity": sp.attrs.get("capacity")}
                for sp in trace.spans if sp.attrs.get("overflow")]
            if overflows:
                recorder.trip("overflow", {"plan_id": self.plan_id,
                                           "overflows": overflows})
        if feedback is not None:
            _drain_counts(counts, feedback)
        return outs if len(outs) > 1 else outs[0]

    def observe(self, params, inputs: dict, feedback,
                aux: Optional[dict] = None):
        """Execute the plan **eagerly** while recording observed
        cardinalities: every ``rel_filter`` / ``sel_mask`` site reports its
        actual ``count / capacity`` into ``feedback`` (a
        ``SelectivityFeedback``).  BoundedRel makes the count a concrete
        runtime value outside jit, so observation is one un-jitted run;
        the accumulated device-side counts transfer in **one**
        ``device_get`` at the end (``resolve_counts`` — the same transfer
        point EXPLAIN ANALYZE uses), never per site.  Re-compiling with the
        same feedback object then re-plans under the observed
        selectivities (and misses the plan cache by construction).
        Returns the plan outputs, exactly like ``__call__``."""
        from .tracing import resolve_counts
        sink: list = []
        out_aux = dict(aux or {})
        out_aux["count_sink"] = sink
        outs = self.__call__(params, inputs, aux=out_aux)
        _drain_counts(resolve_counts(sink), feedback)
        return outs


def plan_and_compile(logical: Plan, catalog: FunctionCatalog,
                     syscat: SystemCatalog, *,
                     mesh=None, rules: ShardingRules = ShardingRules(),
                     cost_model: Optional[CostModel] = None,
                     engines=None,
                     allow_pallas=None,
                     data_parallel: bool = True,
                     buffering: bool = False,
                     global_batch: int = 1,
                     rewrite_pipeline=None,
                     cache=None,
                     pipeline=None,
                     plan_threads: int = 1,
                     feedback=None,
                     store_versions: tuple = ()) -> PlannedFunction:
    """Thin compatibility wrapper over the staged plan pipeline.

    Resolves the engine selection (``engines`` names from the registry;
    legacy ``allow_pallas`` still maps through), runs — or fetches from the
    plan cache — the Algorithm-1 pass pipeline, and binds the staged plan to
    this call's runtime context.  ``cache=False`` forces a fresh planning
    run; any other value uses the given / default PlanCache.

    ``feedback`` is an optional observed-selectivity store (consumed by the
    rewrites, folded into the plan id); ``store_versions`` is the bound
    stores' monotonic version vector — appending to a store bumps it, so
    plans cached against the previous contents provably invalidate.
    """
    from .pipeline import PlanOptions, compile_staged
    from .rewrite import DEFAULT_PIPELINE
    opts = PlanOptions(
        engines=resolve_engines(engines, allow_pallas=allow_pallas),
        data_parallel=data_parallel,
        buffering=buffering,
        global_batch=global_batch,
        rewrite_pipeline=tuple(rewrite_pipeline or DEFAULT_PIPELINE),
        plan_threads=plan_threads)
    extra_key = (("store_versions", tuple(store_versions))
                 if store_versions else ())
    staged = compile_staged(logical, catalog, syscat, options=opts,
                            cost_model=cost_model, pipeline=pipeline,
                            cache=cache, feedback=feedback,
                            extra_key=extra_key)
    return PlannedFunction.from_staged(staged, syscat, rules=rules,
                                       mesh=mesh)
