"""Cost-model effectiveness (paper Fig. 14/15 — "bars with stars").

For each multi-candidate pattern, time every candidate physical sub-plan on
CPU across input sizes, and check whether the learned/analytic cost model
selects the actually-fastest one.  Reports per-point winner vs. selection
and overall selection accuracy + regret."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_model import CostModel
from repro.core.ir import SystemCatalog, TensorT
from repro.layers import attention as A
from repro.layers import moe as X
from repro.layers.common import KeyGen

from .common import emit, time_fn

SYS = SystemCatalog()                      # 1-device catalog for CPU timing


def bench_attention_candidates():
    rows, hits, regrets = [], 0, []
    model = CostModel()
    kg = KeyGen(jax.random.key(0))
    h, kv, d = 4, 2, 16
    window = 32
    cands = {
        "attn_xla": lambda q, k, v: A.sdpa_full(q, k, v, causal=True,
                                                window=0),
        "attn_banded": lambda q, k, v: A.sdpa_banded(q, k, v, window=window),
        "attn_flash": lambda q, k, v: A.sdpa_flash(q, k, v, causal=True,
                                                   window=window),
    }
    for seq in (128, 512, 1024):
        rng = np.random.RandomState(seq)
        q = jnp.asarray(rng.randn(1, seq, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(1, seq, kv, d), jnp.float32)
        v = jnp.asarray(rng.randn(1, seq, kv, d), jnp.float32)
        times = {}
        for name, fn in cands.items():
            if name == "attn_flash" and seq > 256:
                continue   # interpret-mode flash too slow to time fairly
            times[name] = time_fn(jax.jit(fn), q, k, v, warmup=1, iters=3)
        t = TensorT((1, seq, h * d), "float32", ("batch", "seq", "embed"))
        attrs = {"heads": h, "kv_heads": kv, "head_dim": d, "window": window,
                 "causal": True}
        est = {"attn_xla": model.op_seconds("sdpa_xla", [t], attrs, SYS),
               "attn_banded": model.op_seconds("sdpa_banded_xla", [t], attrs,
                                               SYS)}
        est = {k2: v2 for k2, v2 in est.items() if k2 in times}
        pick = min(est, key=est.get)
        best = min(times, key=times.get)
        hits += int(pick == best)
        regrets.append(times[pick] / times[best])
        for name, sec in times.items():
            star = "*chosen*" if name == pick else ""
            rows.append((f"cost_model/attn/seq{seq}/{name}", sec * 1e6,
                         f"best={best}{star}"))
    rows.append(("cost_model/attn/selection", 0.0,
                 f"accuracy={hits}/3 regret={np.mean(regrets):.3f}x"))
    return rows


def bench_moe_candidates():
    rows, hits, regrets = [], 0, []
    model = CostModel()
    kg = KeyGen(jax.random.key(1))
    e, f, nx, k = 32, 64, 8, 2
    p, _ = X.init_moe(kg, {"embed": e, "ffn": f, "experts": nx})
    cands = {
        "moe_dense": lambda x: X.moe_dense(p, x, top_k=k, experts=nx),
        "moe_drop": lambda x: X.moe_dropping(p, x, top_k=k, experts=nx),
    }
    for toks in (256, 1024):
        rng = np.random.RandomState(toks)
        x = jnp.asarray(rng.randn(1, toks, e), jnp.float32)
        times = {n: time_fn(jax.jit(fn), x, warmup=1, iters=3)
                 for n, fn in cands.items()}
        t = TensorT((1, toks, e), "float32", ("batch", "seq", "embed"))
        attrs = {"ffn": f, "experts": nx, "top_k": k}
        est = {
            "moe_dense": model.op_seconds("moe_dense_onehot", [t], attrs,
                                          SYS),
            "moe_drop": model.op_seconds("moe_dropping", [t], attrs, SYS),
        }
        pick = min(est, key=est.get)
        best = min(times, key=times.get)
        hits += int(pick == best)
        regrets.append(times[pick] / times[best])
        for name, sec in times.items():
            star = "*chosen*" if name == pick else ""
            rows.append((f"cost_model/moe/toks{toks}/{name}", sec * 1e6,
                         f"best={best}{star}"))
    rows.append(("cost_model/moe/selection", 0.0,
                 f"accuracy={hits}/2 regret={np.mean(regrets):.3f}x"))
    return rows


def main():
    rows = bench_attention_candidates() + bench_moe_candidates()
    emit(rows)
    return rows


if __name__ == "__main__":
    main()
