"""Multi-round federated learning (paper §5.3 "Applied to Multi-round
Federated Learning" / §7.4).

Each communication round: sample m of N clients, local-train from the
global model, aggregate.  The aggregation operator is pluggable —
``fedavg``, ``fedprox`` (fedavg + proximal local loss), or ``maecho``
(Algorithm 1 replaces the averaging operation, the paper's claim that
it converges in fewer rounds).

The maecho round hands the sampled clients' *whole leaf batch* to one
aggregation call: with ``MAEchoConfig.qp_batched`` (default) every
outer iteration stacks all layers' Gram matrices and issues a single
vmapped PGD solve instead of one QP per layer — the round loop never
serialises over leaves.  ``MultiRoundConfig.maecho_backend`` selects
the per-leaf compute path (``"oracle"`` | ``"kernel"`` | ``"auto"`` |
``"sharded"`` | ``"sharded2d"``, see ``core.maecho`` — per-leaf
routing is compiled once per model shape into ``core.plan.AggPlan``
and reused across rounds); for the sharded backends pass the mesh
through ``run_multi_round(..., mesh=...)`` (default: a 1-D mesh over
every visible device).  Scan-over-layers models (leaves with leading
stacked-layer axes) ride the same fast paths: pass their per-leaf
axis counts via ``run_multi_round(..., stack_levels=...)`` and the
layer axis folds into the kernel grid instead of forcing the oracle.

Cross-device cohorts: ``MultiRoundConfig.hierarchy_group_size`` > 0
routes the maecho round through the two-tier
:func:`maecho_aggregate_hierarchical` — silo groups aggregate
independently, then the group aggregates aggregate once more — so no
single QP or residual pass ever spans the whole cohort.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.maecho import (MAEchoConfig, default_projections,
                               maecho_aggregate)
from repro.fl import models as pm
from repro.fl.client import (LocalTrainConfig, compute_projections,
                             evaluate_classifier, train_classifier)
from repro.fl.server import _flatten_convs, _unflatten_convs


@dataclasses.dataclass(frozen=True)
class MultiRoundConfig:
    n_rounds: int = 10
    n_clients: int = 10
    sample_clients: int = 5
    method: str = "fedavg"        # fedavg | fedprox | maecho
    local: LocalTrainConfig = LocalTrainConfig(epochs=10)
    maecho: MAEchoConfig = MAEchoConfig(tau=20, eta=0.5)
    # "auto" promotes big leaves to the fused Pallas pipeline on TPU;
    # "sharded" additionally splits eligible leaves' out-rows over the
    # mesh and "sharded2d" the residual 2-D (out × in) over both mesh
    # axis groups (run_multi_round's ``mesh`` argument).  The default
    # stays "oracle": on a CPU backend the kernels run in the Pallas
    # interpreter, which is simulation, not a speedup.
    maecho_backend: str = "oracle"  # oracle|kernel|auto|sharded|sharded2d
    proj_alpha: float = 1.0
    seed: int = 0
    # > 0 switches the maecho round to the two-tier hierarchical
    # aggregation (:func:`maecho_aggregate_hierarchical`): the sampled
    # cohort is split into silo groups of this size, each group
    # aggregates independently, and the group outputs are aggregated
    # once more.  0 = flat single-tier (the paper's cross-silo mode).
    hierarchy_group_size: int = 0


def maecho_aggregate_hierarchical(
    client_weights,
    projections=None,
    cfg: MAEchoConfig = MAEchoConfig(),
    *,
    group_size: int,
    convention: str = "oi",
    stack_levels=None,
    backend: str = "oracle",
    mesh=None,
    client_mask=None,
    tier2_cfg: Optional[MAEchoConfig] = None,
):
    """Two-tier MA-Echo for cross-device cohorts: aggregate silo
    groups of ``group_size`` clients independently (tier 1), then
    aggregate the group aggregates (tier 2).

    Peak client residency drops from the whole cohort to
    ``max(group_size, n_groups)`` per aggregation call — composing
    with ``MAEchoConfig.client_chunk``, which bounds the *residual*
    residency inside each call.  ``client_mask`` reuses the flat
    ragged-participation contract per tier: the cohort-wide (N,) mask
    is sliced into each group's submask, groups with zero participants
    are dropped entirely (they contribute no tier-1 aggregate), and
    every surviving group participates fully in tier 2.  With
    ``group_size >= len(client_weights)`` and a single surviving
    group, the flat single-tier result is returned unchanged — exact
    parity with :func:`repro.core.maecho.maecho_aggregate`.

    Tier-2 projections are the per-leaf mean of each group's
    *participating* members' projectors — an approximation (a mean of
    projectors is not itself a projector; factored ``{"U", "s"}``
    leaves average factor-wise), consistent with the group aggregate
    representing its members' shared row space.  ``tier2_cfg``
    optionally overrides the tier-2 solver config (e.g. fewer outer
    iterations over the small n_groups axis)."""
    n = len(client_weights)
    gs = int(group_size)
    if gs <= 0:
        raise ValueError("group_size must be positive")
    if projections is None:
        projections = default_projections(client_weights)
    mask = None
    if client_mask is not None:
        mask = np.asarray(client_mask, bool)
        if mask.shape != (n,):
            raise ValueError(
                f"client_mask must be ({n},) booleans for the "
                f"hierarchical mode, got shape {mask.shape}")
    tier1_w, tier1_p = [], []
    for start in range(0, n, gs):
        members = list(range(start, min(start + gs, n)))
        if mask is None:
            members_in = members
            sub = None
        else:
            members_in = [i for i in members if mask[i]]
            if not members_in:
                continue                  # empty group: no aggregate
            sub = (None if len(members_in) == len(members)
                   else mask[members[0]:members[-1] + 1])
        gw = [client_weights[i] for i in members]
        gp = [projections[i] for i in members]
        tier1_w.append(maecho_aggregate(
            gw, gp, cfg, convention=convention,
            stack_levels=stack_levels, backend=backend, mesh=mesh,
            client_mask=sub))
        tier1_p.append(jax.tree_util.tree_map(
            lambda *xs: sum(xs) / len(xs),
            *[projections[i] for i in members_in]))
    if not tier1_w:
        raise ValueError(
            "client_mask excludes every client — at least one "
            "participant is required")
    if len(tier1_w) == 1:
        return tier1_w[0]
    return maecho_aggregate(
        tier1_w, tier1_p, tier2_cfg if tier2_cfg is not None else cfg,
        convention=convention, stack_levels=stack_levels,
        backend=backend, mesh=mesh)


def run_multi_round(
    spec: pm.PaperModelSpec,
    client_data: list[tuple[np.ndarray, np.ndarray]],
    test_data: tuple[np.ndarray, np.ndarray],
    cfg: MultiRoundConfig,
    global_init=None,
    on_round: Optional[Callable] = None,
    mesh=None,
    stack_levels=None,
) -> tuple[list, float]:
    """Returns (per-round global accuracies, final accuracy).

    ``mesh`` is threaded into the aggregation call for
    ``maecho_backend="sharded"`` (``core.maecho`` builds a default
    1-D all-devices mesh when it is None); ``stack_levels`` is the
    per-leaf stacked-layer-axis count passed straight through to
    ``maecho_aggregate`` for scan-over-layers models (the paper
    MLP/CNN specs are flat — leave it None there)."""
    rng = np.random.RandomState(cfg.seed)
    params = (global_init if global_init is not None
              else pm.init(spec, jax.random.PRNGKey(cfg.seed)))
    history = []
    for rnd in range(cfg.n_rounds):
        picks = rng.choice(cfg.n_clients, size=cfg.sample_clients,
                           replace=False)
        locals_, projs = [], []
        for k in picks:
            x, y = client_data[k]
            lcfg = cfg.local
            if cfg.method == "fedprox":
                lcfg = dataclasses.replace(
                    lcfg, fedprox_mu=lcfg.fedprox_mu or 0.1)
            p, _ = train_classifier(spec, params, x, y, lcfg,
                                    anchor=params)
            locals_.append(p)
            if cfg.method == "maecho":
                projs.append(compute_projections(
                    spec, p, x, alpha=cfg.proj_alpha))

        flat, shapes = zip(*[_flatten_convs(p) for p in locals_])
        flat = list(flat)
        if cfg.method == "maecho":
            fprojs = [_flatten_proj(pr) for pr in projs]
            if cfg.hierarchy_group_size > 0:
                new = maecho_aggregate_hierarchical(
                    flat, fprojs, cfg.maecho,
                    group_size=cfg.hierarchy_group_size,
                    backend=cfg.maecho_backend, mesh=mesh,
                    stack_levels=stack_levels)
            else:
                new = maecho_aggregate(flat, fprojs, cfg.maecho,
                                       backend=cfg.maecho_backend,
                                       mesh=mesh,
                                       stack_levels=stack_levels)
        else:
            from repro.core.aggregators import fedavg
            new = fedavg(flat)
        params = _unflatten_convs(new, shapes[0])

        acc = evaluate_classifier(spec, params, *test_data)
        history.append(acc)
        if on_round:
            on_round(rnd, acc, params)
    return history, history[-1]


def _flatten_proj(projs):
    # projections are already per-layer {"W": P, "b": ()} dicts; conv
    # projectors were computed on im2col features, matching the
    # flattened conv weight — structure already aligned.
    return projs
