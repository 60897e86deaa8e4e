#!/usr/bin/env python3
"""Chip smoke: the system's main path, once, on a TPU at published widths.

One chip (the default)::

    python chip_smoke.py [--seed 0]

runs qwen2-0.5b at its published widths (24 layers, d_model 896, d_ff
4864, vocab 151936; random weights from ``--seed``) through the normal
entry points:

1. fine-tune — two silos take a few ``model.make_train_step(adamw)``
   steps on differently seeded ``lm_token_batches`` streams;
2. aggregate — each silo's projectors come from
   ``fl.llm_adapter.build_projections`` on one probe batch, and
   ``aggregate_llm(backend="auto")`` merges the silos with every weight
   leaf on the Pallas kernel routes; ``layers.wq`` and
   ``layers.w_gate`` are checked against ``backend="oracle"``;
3. serve — four requests are served from the merged model through
   ``launch.serve.run_fixed`` and ``run_arrival``, which must agree
   token for token; ``run_arrival``'s serve step must write its cache
   in place (its ``serve.kv_write`` counter).

Four chips::

    python chip_smoke.py --chips 4

runs only the mesh path at qwen2-1.5b widths cut to the benchmark
cell's 12 layers: ``aggregate_llm(backend="sharded2d")`` on host
checkpoints with no mesh given, so the program derives the (2, 2)
("data", "model") mesh and places each checkpoint into its shards
itself; ``layers.wq`` and ``layers.w_gate`` are checked against
``backend="oracle"`` on one chip (the whole merge does not fit one).

Every phase runs in this one process: a chip belongs to one process.
Printed seconds are cold-run wall time with compilation included —
set-up numbers, not a benchmark.  The last line of stdout is
``{"ok": true, "device": {...}}``.  Any failure exits non-zero without
that line, and so does a run where JAX finds no TPU or where the
repository's ``src/repro`` is not beside this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
N_SILOS = 2
FINETUNE_STEPS, BATCH, SEQ = 3, 4, 128
N_REQUESTS, PROMPT_LEN, GEN = 4, 128, 32   # window 256: two decode blocks
PARITY_LEAVES = ("wq", "w_gate")
REL_TOL = 1e-3


def _say(msg: str) -> None:
    print(msg, flush=True)


def _device_bytes(device, key: str):
    return (device.memory_stats() or {}).get(key)


def _rel_err(a, b) -> float:
    """max|a − b| / max|b| on the host (operands may sit on different
    devices or meshes)."""
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _stacked_shapes(trees_):
    """Shape-only stack of per-client pytrees (leading client axis)."""
    import jax

    return jax.tree.map(
        lambda *xs: jax.ShapeDtypeStruct((len(xs),) + xs[0].shape,
                                         xs[0].dtype), *trees_)


def _coverage(cfg, client_params, client_projs, macfg, backend,
              mesh=None):
    """Print the compiled plan's routes; return ``(path, levels,
    route)`` per leaf."""
    import jax

    from repro.core.maecho import coverage_report, dispatch_summary
    from repro.fl.llm_adapter import stack_levels_fn
    from repro.utils import trees

    W0 = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                      client_params[0])
    P = _stacked_shapes(client_projs)
    lv = stack_levels_fn(cfg)
    levels = trees.map_with_path(lambda p, _: lv(p), W0)
    coverage_report(W0, P, levels, macfg, backend, mesh)
    per_leaf, _ = dispatch_summary(W0, P, levels, macfg, "io", backend,
                                   mesh)
    return per_leaf, W0


def _weight_leaves(per_leaf, W0):
    """Paths of the 2-D (or stacked 2-D) weight leaves."""
    from repro.utils.trees import tree_paths

    ndim = {p: x.ndim for p, x in tree_paths(W0)}
    return [p for p, lvl, _ in per_leaf if ndim[p] - lvl == 2]


# --------------------------------------------------------------------------
# one chip: fine-tune -> aggregate -> serve
# --------------------------------------------------------------------------
def main_path(cfg, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.maecho import MAEchoConfig
    from repro.data.synthetic import lm_token_batches
    from repro.fl.llm_adapter import aggregate_llm, build_projections
    from repro.launch.serve import run_arrival, run_fixed
    from repro.models.zoo import get_model
    from repro.optim import adamw
    from repro.utils import spans

    dev = jax.devices()[0]
    model = get_model(cfg)
    _say(f"[setup] {cfg.name}: {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
         f"params {cfg.n_params() / 1e6:.1f}M fp32")

    # ---- fine-tune: silos and the base live on the host between
    # phases, so the device holds one silo's training state at a time
    t0 = time.time()
    base = jax.device_get(model.init_params(jax.random.PRNGKey(seed)))
    opt = adamw(1e-3)
    step_fn = jax.jit(model.make_train_step(opt))
    silos, projs = [], []
    for i in range(N_SILOS):
        dom = 101 * (i + 1) + seed
        params = jax.device_put(base, dev)
        state = opt.init(params)
        losses = []
        for t, b in enumerate(lm_token_batches(cfg.vocab, BATCH, SEQ,
                                               FINETUNE_STEPS, seed=dom)):
            params, state, loss = step_fn(params, state, b, jnp.int32(t))
            losses.append(float(loss))
        if not np.all(np.isfinite(losses)):
            raise RuntimeError(f"silo {i}: non-finite loss {losses}")
        probe = list(lm_token_batches(cfg.vocab, BATCH, SEQ, 1,
                                      seed=dom + 7))
        projs.append(jax.device_get(build_projections(cfg, params,
                                                      probe)))
        silos.append(jax.device_get(params))
        del params, state
        _say(f"[finetune] silo {i}: losses "
             f"{[round(x, 4) for x in losses]}")
    del base
    _say(f"[finetune] cold-run {time.time() - t0:.1f}s (compile "
         f"included); peak_bytes_in_use "
         f"{_device_bytes(dev, 'peak_bytes_in_use')}")

    # ---- aggregate: every weight leaf on the kernel routes, fallback
    # warnings are errors.  Sequential QP: the batched solve keeps every
    # leaf's transposed anchors live at once, which does not fit 16 GB
    # at these widths.
    t0 = time.time()
    macfg = MAEchoConfig(tau=15, eta=0.5, mu=20.0, qp_batched=False)
    per_leaf, W0 = _coverage(cfg, silos, projs, macfg, "auto")
    weights = _weight_leaves(per_leaf, W0)
    off = [(p, r) for p, _, r in per_leaf
           if p in weights and r not in ("kernel", "stacked")]
    if off:
        raise RuntimeError(f"weight leaves off the kernel routes: {off}")
    merged = aggregate_llm(cfg, silos, projs, macfg, backend="auto")
    merged = jax.block_until_ready(merged)
    finite = all(bool(jnp.all(jnp.isfinite(x)))
                 for x in jax.tree.leaves(merged))
    if not finite:
        raise RuntimeError("merged params are not finite")
    _say(f"[aggregate] {len(weights)} weight leaves on kernel routes; "
         f"cold-run {time.time() - t0:.1f}s (compile included); "
         f"peak_bytes_in_use {_device_bytes(dev, 'peak_bytes_in_use')}")

    def sub(tree):
        return {"layers": {k: tree["layers"][k] for k in PARITY_LEAVES}}

    t0 = time.time()
    oracle = aggregate_llm(cfg, [sub(s) for s in silos],
                           [sub(p) for p in projs], macfg,
                           backend="oracle")
    parity = {}
    for k in PARITY_LEAVES:
        got, want = merged["layers"][k], oracle["layers"][k]
        base_avg = sum(s["layers"][k] for s in silos) / len(silos)
        parity[k] = {"rel": _rel_err(got, want),
                     "rel_of_update": _rel_err(got - base_avg,
                                               want - base_avg)}
    _say(f"[aggregate] kernel vs oracle {json.dumps(parity)} "
         f"({time.time() - t0:.1f}s)")
    bad = {k: v["rel"] for k, v in parity.items() if not v["rel"] < REL_TOL}
    if bad:
        raise RuntimeError(f"kernel vs oracle rel error >= {REL_TOL}: "
                           f"{bad}")
    del silos, projs, oracle

    # ---- serve from the merged params
    t0 = time.time()
    rng = np.random.RandomState(seed)
    prompts = jnp.asarray(rng.randint(0, cfg.vocab,
                                      size=(N_REQUESTS, PROMPT_LEN)),
                          jnp.int32)
    fixed, fstats = run_fixed(cfg, model, merged, prompts, GEN)
    t_arrival = time.perf_counter_ns()
    outs, _ = run_arrival(cfg, model, merged, prompts, GEN,
                          slots=N_REQUESTS)
    writes = sorted({r.attrs["path"] for r in spans.records(t_arrival)
                     if r.name == "serve.kv_write"})
    if writes != ["in_place"]:
        raise RuntimeError(f"run_arrival's serve step wrote its cache by "
                           f"{writes}, not in place")
    fixed = np.asarray(fixed)
    if fixed.shape != (N_REQUESTS, GEN):
        raise RuntimeError(f"run_fixed tokens shape {fixed.shape}")
    if fixed.min() < 0 or fixed.max() >= cfg.vocab:
        raise RuntimeError("run_fixed tokens out of the vocabulary")
    first_diff = {r: int(np.argmax(fixed[r] != np.asarray(outs[r])))
                  for r in range(N_REQUESTS)
                  if not np.array_equal(fixed[r], np.asarray(outs[r]))}
    if first_diff:
        raise RuntimeError(f"run_arrival tokens differ from run_fixed "
                           f"(request: first differing step) {first_diff}")
    _say(f"[serve] {N_REQUESTS} requests x {PROMPT_LEN} prompt + {GEN} "
         f"generated, window {fstats['window']}; run_arrival matches "
         f"run_fixed, its cache written in place; cold-run {time.time() - t0:.1f}s (compile "
         f"included); peak_bytes_in_use "
         f"{_device_bytes(dev, 'peak_bytes_in_use')}")
    return {"parity": parity}


# --------------------------------------------------------------------------
# four chips: sharded2d on the mesh the program derives
# --------------------------------------------------------------------------
def mesh_path(cfg, seed: int) -> dict:
    import jax

    from repro.core.maecho import MAEchoConfig
    from repro.data.synthetic import lm_token_batches
    from repro.fl.llm_adapter import (aggregate_llm, build_projections,
                                      stack_levels_fn)
    from repro.models.zoo import get_model
    from repro.utils import spans
    from repro.utils.trees import tree_paths

    devs = jax.devices()
    model = get_model(cfg)
    _say(f"[setup] {cfg.name} widths, {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, d_ff {cfg.d_ff}; {len(devs)} chips")

    # the silos' checkpoints and projectors, kept on the host as an
    # operator holds them
    t0 = time.time()
    base = model.init_params(jax.random.PRNGKey(seed))
    clients, projs = [], []
    for i in range(N_SILOS):
        keys = jax.random.split(jax.random.PRNGKey(seed + 1 + i),
                                len(jax.tree.leaves(base)))
        c = jax.tree.unflatten(jax.tree.structure(base), [
            x + 1e-2 * jax.random.normal(k, x.shape, x.dtype)
            for x, k in zip(jax.tree.leaves(base), keys)])
        probe = list(lm_token_batches(cfg.vocab, BATCH, SEQ, 1,
                                      seed=seed + 11 * (i + 1)))
        projs.append(jax.device_get(build_projections(cfg, c, probe)))
        clients.append(jax.device_get(c))
        del c
    del base
    _say(f"[setup] clients and projectors {time.time() - t0:.1f}s")

    macfg = MAEchoConfig(tau=15, eta=0.5, mu=20.0, qp_batched=False)
    t0 = time.time()
    t_ns = time.perf_counter_ns()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        sharded = jax.block_until_ready(aggregate_llm(
            cfg, clients, projs, macfg, backend="sharded2d"))
    for w in caught:
        _say(f"[mesh] warning: {w.message}")
    recs = spans.records(t_ns)
    routes = {r.attrs["route"]: r.attrs["n"] for r in recs
              if r.name == "maecho.route"}
    placed = {r.attrs["device"]: r.attrs["n"] for r in recs
              if r.name == "maecho.place_bytes"}
    mesh = dict(sharded["layers"]["wq"].sharding.mesh.shape)
    _say(f"[mesh] sharded2d on mesh {mesh}, routes {routes}; cold-run "
         f"{time.time() - t0:.1f}s (compile included); bytes placed per "
         f"chip {placed}; peak_bytes_in_use per chip "
         f"{[_device_bytes(d, 'peak_bytes_in_use') for d in devs]}")
    lv = stack_levels_fn(cfg)
    weights = [p for p, x in tree_paths(clients[0]) if x.ndim - lv(p) == 2]
    on_mesh = routes.get("sharded2d", 0) + routes.get("sharded", 0)
    if on_mesh != len(weights):
        raise RuntimeError(f"{len(weights)} weight leaves, {on_mesh} on "
                           f"the mesh routes: {routes}")

    def sub(tree):
        return {"layers": {k: tree["layers"][k] for k in PARITY_LEAVES}}

    t0 = time.time()
    oracle = aggregate_llm(cfg, [sub(c) for c in clients],
                           [sub(p) for p in projs], macfg, backend="oracle")
    errs = {k: _rel_err(sharded["layers"][k], oracle["layers"][k])
            for k in PARITY_LEAVES}
    _say(f"[mesh] sharded2d vs one-chip oracle {json.dumps(errs)} "
         f"({time.time() - t0:.1f}s)")
    bad = {k: e for k, e in errs.items() if not e < REL_TOL}
    if bad:
        raise RuntimeError(f"sharded2d vs oracle rel error >= {REL_TOL}: "
                           f"{bad}")
    return {"mesh": mesh, "max_rel": max(errs.values())}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: the repository's src/repro is not beside "
                 f"this script (looked in {SRC})")
    sys.path.insert(0, str(SRC))
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found — JAX's devices are "
                 f"{devs[0].platform!r}; this smoke runs only on a TPU")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU chips, found {len(devs)}")
    _say(f"[device] {devs[0].platform} {devs[0].device_kind} x "
         f"{len(devs)}")

    from repro.configs import get_config

    if args.chips == 4:
        mesh_path(get_config("qwen2-1.5b").replace(n_layers=12), args.seed)
    else:
        warnings.filterwarnings("error", category=RuntimeWarning)
        main_path(get_config("qwen2-0.5b"), args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
