"""Traffic kind ``aggregate``: one-shot MA-Echo merges of N client
checkpoints, back to back, through ``repro.fl.llm_adapter.aggregate_llm``.

The traffic file gives the clients (count, perturbation of the seeded
base weights), the projectors (feature rows and token ids per client,
ridge), the backend and the ``MAEchoConfig`` fields.  The checkpoints
stay on the host, where a one-chip deployment keeps them (they do not
fit beside the executor), so each aggregate moves them onto the chip.

Correctness: every leaf of the last aggregate of the window against the
plain MA-Echo of ``chipbench/reference/maecho.py`` on the same clients
and projectors: ``merged_gap`` is the widest gap between a merged weight
and the reference's, over the RMS spread of that leaf's clients around
their mean, at the worst leaf.
"""
from __future__ import annotations

import numpy as np

from chipbench import harness

SPAN = "chipbench.aggregate_llm"


def _levels(path: str) -> int:
    return 1 if path.startswith("layers.") else 0


def setup(r) -> dict:
    import jax

    from repro.core.maecho import MAEchoConfig, dispatch_summary
    from repro.fl.llm_adapter import stack_levels_fn
    from repro.utils import trees

    conf, tr = r.conf, r.traffic
    cfg = harness.program_config(conf)
    n = tr["n_clients"]
    macfg = MAEchoConfig(**tr["maecho"])
    clients, projs = [], []
    for i in range(n):
        c = harness.make_client(conf, r.seed, i, tr["client_delta_std"])
        p = harness.make_projectors(conf, r.seed, i, tr["probe_rows"],
                                    tr["probe_tokens"],
                                    tr["projector_ridge"])
        clients.append(jax.device_get(c))
        projs.append(jax.device_get(p))
        del c, p

    shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                          clients[0])
    pstack = jax.tree.map(
        lambda *xs: jax.ShapeDtypeStruct((len(xs),) + np.shape(xs[0]),
                                         np.asarray(xs[0]).dtype), *projs)
    lv = stack_levels_fn(cfg)
    levels = trees.map_with_path(lambda path, _: lv(path), shapes)
    per_leaf, counts = dispatch_summary(shapes, pstack, levels, macfg, "io",
                                        tr["backend"])
    routes = {path: route for path, _, route in per_leaf}
    st = dict(cfg=cfg, macfg=macfg, clients=clients, projs=projs,
              routes=routes, counters={"oracle_leaves": counts.get("oracle", 0)},
              out=None)
    call(r, st, -1)           # warm-up: compiles, or loads from the cache
    return st


def call(r, st, i: int) -> None:
    import jax

    from repro.fl import llm_adapter

    st["out"] = None
    out = llm_adapter.aggregate_llm(st["cfg"], st["clients"], st["projs"],
                                    st["macfg"], backend=r.traffic["backend"])
    st["out"] = jax.block_until_ready(out)


def release(r, st) -> None:
    """Move the last result to the host, freeing the program's state."""
    import jax

    st["merged"] = jax.device_get(st.pop("out"))


def end_to_end(r, st, elapsed: float) -> dict:
    """``agg_s``: the window's elapsed time over the aggregates it ran."""
    return {"agg_s": {"value": elapsed / len(r.calls), "unit": "s"}}


def attempts(r, st) -> tuple:
    """(aggregates the window ran, those that failed): a failure raises."""
    return len(r.calls), 0


CONTROLS = ("bf16", "fp8")


def check(r, st, control: str = "") -> dict:
    """Compare every leaf of the merged model with the reference.
    ``control`` ("bf16" or "fp8") puts the reference, its matmul operands
    rounded to that type, in the program's place."""
    import jax.numpy as jnp

    from chipbench.reference import maecho as ref

    flat = lambda t: dict(harness.flatten(t))     # noqa: E731
    clients = [flat(c) for c in st["clients"]]
    projs = [flat(p) for p in st["projs"]]
    merged = flat(st["merged"])
    worst, worst_path = 0.0, None
    for path in sorted(merged):
        V = jnp.stack([jnp.asarray(c[path]) for c in clients])
        P = jnp.stack([jnp.asarray(p[path]) for p in projs])
        lv = _levels(path)
        want = ref.aggregate_leaf(V, P, lv, r.traffic["maecho"])
        got = (ref.aggregate_leaf(V, P, lv, r.traffic["maecho"], lowp=control)
               if control else jnp.asarray(merged[path], jnp.float32))
        spread = jnp.sqrt(jnp.mean(jnp.square(V - jnp.mean(V, 0))))
        gap = float(jnp.max(jnp.abs(got - want)) / spread)
        if not gap <= worst:
            worst, worst_path = gap, path
        del V, P, want, got
    return {"merged_gap": worst, "merged_gap_leaf": worst_path}


def context(r, st) -> dict:
    """What the per-layer metric readers may read."""
    from chipbench import work

    kernel_paths = [p for p, route in st["routes"].items()
                    if route != "oracle"]
    m = r.traffic["maecho"]
    n = r.traffic["n_clients"]
    return {"counters": st["counters"], "routes": st["routes"],
            "work_per_call": work.maecho_aggregate(r.conf, n, m["tau"]),
            "kernel_work_per_call": work.maecho_aggregate(
                r.conf, n, m["tau"], kernel_paths)}
