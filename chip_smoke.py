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
   token for token.

Four chips::

    python chip_smoke.py --chips 4

runs only the mesh path: ``backend="sharded2d"`` on a (2, 2)
("data", "model") mesh at qwen2-1.5b widths cut to 4 layers, with the
client weights placed by ``sharding.rules``, against
``backend="auto"`` on one chip.

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
    outs, _ = run_arrival(cfg, model, merged, prompts, GEN,
                          slots=N_REQUESTS)
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
         f"run_fixed; cold-run {time.time() - t0:.1f}s (compile "
         f"included); peak_bytes_in_use "
         f"{_device_bytes(dev, 'peak_bytes_in_use')}")
    return {"parity": parity}


# --------------------------------------------------------------------------
# four chips: sharded2d on a (2, 2) mesh vs auto on one chip
# --------------------------------------------------------------------------
def mesh_path(cfg, seed: int, n_data: int = 2, n_model: int = 2) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.maecho import MAEchoConfig
    from repro.data.synthetic import lm_token_batches
    from repro.fl.llm_adapter import aggregate_llm, build_projections
    from repro.launch.mesh import make_debug_mesh
    from repro.models.zoo import get_model
    from repro.sharding.rules import make_rules
    from repro.utils.trees import tree_paths

    devs = jax.devices()
    mesh = make_debug_mesh(n_data, n_model)
    model = get_model(cfg)
    _say(f"[setup] {cfg.name} widths, {cfg.n_layers} layers, d_model "
         f"{cfg.d_model}, d_ff {cfg.d_ff}; mesh {dict(mesh.shape)}")

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
    rules = make_rules(mesh, cfg)
    shardings = rules.params_shardings(clients[0])
    replicated = NamedSharding(mesh, PartitionSpec())
    placed = [jax.device_put(c, shardings) for c in clients]
    placed_p = [jax.device_put(p, replicated) for p in projs]
    _say(f"[mesh] bytes_in_use per chip after placement "
         f"{[_device_bytes(d, 'bytes_in_use') for d in devs]}")

    t0 = time.time()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        per_leaf, W0 = _coverage(cfg, placed, placed_p, macfg,
                                 "sharded2d", mesh)
        sharded = aggregate_llm(cfg, placed, placed_p, macfg,
                                backend="sharded2d", mesh=mesh)
        sharded = jax.block_until_ready(sharded)
    for w in caught:
        _say(f"[mesh] warning: {w.message}")
    weights = _weight_leaves(per_leaf, W0)
    routes = {p: r for p, _, r in per_leaf}
    if any(routes[p] not in ("sharded2d", "sharded") for p in weights):
        raise RuntimeError(f"weight leaves off the mesh routes: "
                           f"{[(p, routes[p]) for p in weights]}")
    _say(f"[mesh] sharded2d cold-run {time.time() - t0:.1f}s (compile "
         f"included); bytes_in_use per chip "
         f"{[_device_bytes(d, 'bytes_in_use') for d in devs]}; "
         f"peak_bytes_in_use per chip "
         f"{[_device_bytes(d, 'peak_bytes_in_use') for d in devs]}")
    sharded = jax.device_get(sharded)
    del placed, placed_p

    t0 = time.time()
    single = jax.device_get(aggregate_llm(cfg, clients, projs, macfg,
                                          backend="auto"))
    _say(f"[mesh] one-chip auto cold-run {time.time() - t0:.1f}s")
    errs = {p: _rel_err(a, b) for (p, a), (_, b) in zip(
        tree_paths(sharded), tree_paths(single))}
    worst = max(errs, key=errs.get)
    _say(f"[mesh] sharded2d vs one-chip auto: max rel {errs[worst]:.3e} "
         f"({worst}); " + json.dumps({p: f"{e:.2e}" for p, e in
                                      errs.items()}))
    if not errs[worst] < REL_TOL:
        raise RuntimeError(f"sharded2d vs auto rel error {errs[worst]} "
                           f">= {REL_TOL} at {worst}")
    return {"max_rel": errs[worst]}


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
        mesh_path(get_config("qwen2-1.5b").replace(n_layers=4), args.seed)
    else:
        warnings.filterwarnings("error", category=RuntimeWarning)
        main_path(get_config("qwen2-0.5b"), args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
