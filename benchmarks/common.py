"""Shared benchmark scaffolding.

Every benchmark mirrors one table/figure of the paper on the synthetic
datasets (offline container — DESIGN.md §1) and emits CSV rows
``name,us_per_call,derived`` where ``derived`` carries the
table-specific metric (usually accuracy).

Rows are also collected in-process so ``benchmarks.run`` can persist
each suite as ``BENCH_<suite>.json`` (run-over-run perf trajectory —
every invocation appends a run entry; set ``REPRO_BENCH_DIR`` to move
them off the repo root).
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import numpy as np

from repro.data.partition import dirichlet_partition
from repro.data.synthetic import DatasetSpec
from repro.fl import models as pm
from repro.fl.client import (LocalTrainConfig, compute_projections,
                             evaluate_classifier, train_classifier)

# benchmark-scale dataset (kept smaller than the paper's 60k MNIST so
# the whole suite runs on one CPU core; relative orderings preserved)
BENCH_DATA = DatasetSpec("bench", n_train=8000, n_test=1500, latent=24,
                         out_dim=784, seed=0)
MLP = dataclasses.replace(pm.MLP_SPEC, hidden=(200, 100, 50))


def timed(fn, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    jax.block_until_ready(jax.tree_util.tree_leaves(out)[0]) \
        if jax.tree_util.tree_leaves(out) else None
    return out, (time.time() - t0) * 1e6


def refuse_on_tpu(suite: str) -> None:
    """Raise where the default backend is a TPU.

    For suites that spawn one child per forced host-device count: this
    process already holds the chip, so a child that needs it fails or
    hangs, and forced host devices time the CPU anyway.  The chip's
    multi-device path runs in one process (``chip_smoke.py --chips 4``).
    """
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{suite} forces host devices in child processes and cannot "
            f"run beside a TPU; run it with JAX_PLATFORMS=cpu, or run "
            f"`python chip_smoke.py --chips 4` for the chip's mesh path")


def train_locals(spec, data, n_clients, beta, *, epochs=6,
                 same_init=False, seed=0, max_steps=0, proj_alpha=1.0,
                 max_samples=1536):
    parts = dirichlet_partition(data["train_y"], n_clients, beta,
                                seed=seed)
    clients, projs, local_accs = [], [], []
    for k, ix in enumerate(parts):
        init_seed = seed if same_init else seed * 100 + k
        p0 = pm.init(spec, jax.random.PRNGKey(init_seed))
        p, _ = train_classifier(
            spec, p0, data["train_x"][ix], data["train_y"][ix],
            LocalTrainConfig(epochs=epochs, max_steps=max_steps,
                             seed=seed + k))
        clients.append(p)
        projs.append(compute_projections(
            spec, p, data["train_x"][ix], alpha=proj_alpha,
            max_samples=max_samples))
        local_accs.append(evaluate_classifier(
            spec, p, data["test_x"], data["test_y"]))
    return parts, clients, projs, float(np.mean(local_accs))


def ensemble_acc(spec, clients, data) -> float:
    from repro.core.aggregators import ensemble_logits
    import jax.numpy as jnp
    x = jnp.asarray(data["test_x"])
    logits = ensemble_logits(
        lambda w, xx: pm.forward(spec, w, xx), clients, x)
    return float(np.mean(np.argmax(np.asarray(logits), -1) ==
                         data["test_y"]))


_ROWS: list[dict] = []


def row(name: str, us: float, derived, peak_bytes=None) -> str:
    """Emit one bench row.  ``peak_bytes`` (optional) records the
    compiled program's peak temp-buffer footprint alongside the time —
    rows carrying it are gated on BOTH metrics by
    ``tools/check_bench_regression.py``; rows without it keep the
    legacy time-only shape."""
    line = f"{name},{us:.0f},{derived}"
    print(line, flush=True)
    entry = {"name": name, "us_per_call": round(us),
             "derived": str(derived)}
    if peak_bytes is not None:
        entry["peak_bytes"] = int(peak_bytes)
    _ROWS.append(entry)
    return line


def drain_rows() -> list[dict]:
    """Return and clear the rows collected since the last drain."""
    out = list(_ROWS)
    _ROWS.clear()
    return out


def persist_rows(suite: str, rows: list[dict], quick: bool) -> str:
    """Append one run entry to BENCH_<suite>.json (perf trajectory).

    Written atomically (temp file + rename); an unreadable existing
    file is preserved as ``<path>.corrupt`` instead of silently
    discarding the trajectory.
    """
    path = os.path.join(os.environ.get("REPRO_BENCH_DIR", "."),
                        f"BENCH_{suite}.json")
    runs = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                loaded = json.load(f)
            runs = loaded["runs"]
            if not isinstance(runs, list):
                raise ValueError("runs is not a list")
        except (OSError, ValueError, KeyError, TypeError):
            runs = []
            os.replace(path, path + ".corrupt")
            print(f"# warning: unreadable {path} moved to "
                  f"{path}.corrupt; starting a fresh trajectory",
                  flush=True)
    runs.append({"timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
                 "quick": quick, "rows": rows})
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump({"suite": suite, "runs": runs}, f, indent=1)
        os.replace(tmp, path)
    finally:
        # a failed dump (unserialisable row, full disk) must not leave
        # the half-written temp file behind
        if os.path.exists(tmp):
            os.remove(tmp)
    return path
