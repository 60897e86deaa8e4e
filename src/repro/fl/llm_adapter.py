"""MA-Echo over the LLM zoo — cross-silo fine-tuning aggregation.

Maps every parameter leaf of every architecture family onto one of the
projector rules from ``repro.core.maecho`` (DESIGN.md §4):

  full    — (d_in, d_in) projector from captured layer-input features
  diag    — embedding tables: the input space is the one-hot vocab, so
            P is the client's token-support indicator (d=vocab diag)
  scalar  — biases, norms, SSM diagonal params (A_log, D, dt_bias),
            depthwise conv taps: the input is always live, the paper's
            null space is degenerate (paper §6), so the bias rule holds

Feature capture (``probe_features``) re-runs the forward as an
*unstacked* python-loop over layers (client-side, smoke/fine-tune
scale), collecting the exact input stream of each matmul.  For MoE, the
features for expert e are the tokens *routed to e* — per-expert
projectors over disjoint input subspaces, the paper's non-IID sweet
spot realised inside a single model.

Weight convention here is "io" (x @ W) throughout.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import projections as proj
from repro.core.maecho import MAEchoConfig, maecho_aggregate
from repro.models import dense, moe as moe_mod
from repro.models import layers as L
from repro.models.config import ModelConfig
from repro.utils import trees


# --------------------------------------------------------------------------
# stack levels: how many leading layer axes each leaf carries
# --------------------------------------------------------------------------
def stack_levels_fn(cfg: ModelConfig) -> Callable[[str], int]:
    def fn(path: str) -> int:
        if cfg.family == "hybrid":
            return 2 if path.startswith("mamba.") else 0
        if _expert_leaf(path):
            return 2                    # (L, E) — per-layer, per-expert
        if path.startswith(("layers.", "enc_layers.", "dec_layers.")):
            return 1
        return 0
    return fn


def _expert_leaf(path: str) -> bool:
    return any(k in path for k in ("we_gate", "we_up", "we_down"))


# --------------------------------------------------------------------------
# projector construction
# --------------------------------------------------------------------------
def _full_P(feats, alpha):
    f = feats.reshape(-1, feats.shape[-1]).astype(jnp.float32)
    f = f / jnp.maximum(jnp.linalg.norm(f, axis=-1, keepdims=True), 1e-6)
    return proj.projection_from_features(f, alpha)


def default_llm_projections(cfg: ModelConfig, params, alpha: float = 1.0,
                            token_support=None):
    """Scalar rule everywhere, diag on the embedding if token_support
    (bool (vocab,)) is given.  The fallback when no probe exists."""
    def mk(path, leaf):
        lead = _lead_shape(cfg, path, leaf)
        if path == "embed" and token_support is not None:
            return token_support.astype(leaf.dtype)
        return jnp.ones(lead, jnp.float32)
    return trees.map_with_path(mk, params)


def _lead_shape(cfg: ModelConfig, path: str, leaf):
    lv = stack_levels_fn(cfg)(path)
    return leaf.shape[:lv]


def build_projections(cfg: ModelConfig, params, batches,
                      alpha: float = 1.0):
    """Capture features over ``batches`` and build the projector pytree.

    Leaves with a captured feature stream get full per-layer P; the
    embedding gets the diag token-support rule; everything else the
    scalar rule.
    """
    feats, support = probe_features(cfg, params, batches)
    expert = feats.pop("__expert__", None)

    def build(f):
        if isinstance(f, list):
            if isinstance(f[0], list):         # hybrid (G, k) nesting
                return jnp.stack([jnp.stack([_full_P(x, alpha)
                                             for x in row]) for row in f])
            return jnp.stack([_full_P(x, alpha) for x in f])
        return _full_P(f, alpha)

    def mk(path, leaf):
        if path in feats:
            return build(feats[path])
        if expert is not None and path in ("layers.we_gate",
                                           "layers.we_up"):
            # per-expert projectors from the routed token streams
            return jnp.stack([
                jax.vmap(lambda fe: _full_P(fe, alpha))(expert[l])
                for l in range(leaf.shape[0])])
        if path == "embed" and support is not None:
            return support.astype(jnp.float32)
        return jnp.ones(_lead_shape(cfg, path, leaf), jnp.float32)

    return trees.map_with_path(mk, params)


# --------------------------------------------------------------------------
# feature probes (unstacked forward, python loop over layers)
# --------------------------------------------------------------------------
def probe_features(cfg: ModelConfig, params, batches):
    if cfg.family in ("dense", "vlm"):
        return _probe_dense(cfg, params, batches)
    if cfg.family == "moe":
        return _probe_moe(cfg, params, batches)
    if cfg.family == "ssm":
        return _probe_mamba(cfg, params, batches)
    if cfg.family == "hybrid":
        return _probe_hybrid(cfg, params, batches)
    if cfg.family == "encdec":
        return _probe_encdec(cfg, params, batches)
    raise ValueError(cfg.family)


def _collect(store, key, val, max_rows=1024):
    v = val.reshape(-1, val.shape[-1])
    if v.shape[0] > max_rows:
        v = v[:: max(1, v.shape[0] // max_rows)][:max_rows]
    store.setdefault(key, []).append(v)


def _cat(store):
    return {k: ([jnp.concatenate(x, 0) for x in zip(*v)]
                if isinstance(v[0], (list, tuple))
                else jnp.concatenate(v, 0))
            for k, v in store.items()}


def _token_support(cfg, batches):
    sup = np.zeros(cfg.vocab, np.float32)
    for b in batches:
        if "tokens" in b:
            sup[np.unique(np.asarray(b["tokens"]))] = 1.0
    return jnp.asarray(sup)


def _probe_dense(cfg: ModelConfig, params, batches):
    nL = cfg.n_layers
    per_layer: dict[str, list] = {}
    final_feats = []
    for batch in batches:
        x, positions = dense.embed_inputs(cfg, params, batch)
        rows = [dict() for _ in range(nL)]
        for l in range(nL):
            lp = trees.tree_map(lambda a: a[l], params["layers"])
            h1 = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            _collect(rows[l], "qkv", h1)
            a = dense.attn_block(lp, h1, positions, cfg)
            # input of wo is attention output pre-projection; reuse a's
            # pre-wo stream via a dedicated recompute:
            x = x + a
            h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            _collect(rows[l], "mlp_in", h2)
            x = x + dense.mlp_block(lp, h2, cfg)
        xf = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        final_feats.append(xf.reshape(-1, cfg.d_model)[:1024])
        for l in range(nL):
            for k, v in rows[l].items():
                per_layer.setdefault((k, l), []).extend(v)

    feats = {}
    for name, param_keys in (("qkv", ("layers.wq", "layers.wk",
                                      "layers.wv")),
                             ("mlp_in", ("layers.w_gate", "layers.w_up"))):
        stacked = [jnp.concatenate(per_layer[(name, l)], 0)
                   for l in range(nL)]
        for pk in param_keys:
            feats[pk] = stacked
    out = {k: v for k, v in feats.items()}
    if not cfg.tie_embeddings:
        out["lm_head"] = jnp.concatenate(final_feats, 0)
    support = _token_support(cfg, batches)
    return out, support


def _probe_moe(cfg: ModelConfig, params, batches):
    nL = cfg.n_layers
    m = cfg.moe
    per_layer: dict = {}
    expert_feats: dict = {}
    final_feats = []
    for batch in batches:
        x, positions = dense.embed_inputs(cfg, params, batch)
        for l in range(nL):
            lp = trees.tree_map(lambda a: a[l], params["layers"])
            h1 = L.rms_norm(x, lp["ln1"], cfg.norm_eps)
            per_layer.setdefault(("qkv", l), []).append(
                h1.reshape(-1, cfg.d_model)[:512])
            x = x + dense.attn_block(lp, h1, positions, cfg)
            h2 = L.rms_norm(x, lp["ln2"], cfg.norm_eps)
            per_layer.setdefault(("router", l), []).append(
                h2.reshape(-1, cfg.d_model)[:512])
            # routed per-expert features
            B, S, d = h2.shape
            T = B * S
            g = min(m.group_size, T)
            pad = (-T) % g
            xg = jnp.pad(h2.reshape(T, d), ((0, pad), (0, 0)))
            xg = xg.reshape(-1, g, d)
            dispatch, _, _ = moe_mod._route(lp, xg, cfg)
            xe = jnp.einsum("ngec,ngd->necd", dispatch, xg)
            xe = xe.transpose(1, 0, 2, 3).reshape(m.n_experts, -1, d)
            expert_feats.setdefault(l, []).append(xe[:, :256])
            y, _ = moe_mod.moe_block(lp, h2, cfg)
            x = x + y
        xf = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        final_feats.append(xf.reshape(-1, cfg.d_model)[:1024])

    feats: dict = {}
    qkv = [jnp.concatenate(per_layer[("qkv", l)], 0) for l in range(nL)]
    for pk in ("layers.wq", "layers.wk", "layers.wv"):
        feats[pk] = qkv
    router = [jnp.concatenate(per_layer[("router", l)], 0)
              for l in range(nL)]
    feats["layers.router"] = router
    if m.n_shared_experts:
        feats["layers.ws_gate"] = router
        feats["layers.ws_up"] = router
    if not cfg.tie_embeddings:
        feats["lm_head"] = jnp.concatenate(final_feats, 0)
    # expert leaves: handled separately in build_projections_moe below
    support = _token_support(cfg, batches)
    feats["__expert__"] = {
        l: jnp.concatenate(v, 1) for l, v in expert_feats.items()}
    return feats, support


def _probe_mamba(cfg: ModelConfig, params, batches):
    from repro.models import mamba
    nL = cfg.n_layers
    per_layer: dict = {}
    final_feats = []
    for batch in batches:
        x = params["embed"].astype(cfg.cdtype)[batch["tokens"]]
        for l in range(nL):
            lp = trees.tree_map(lambda a: a[l], params["layers"])
            h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
            per_layer.setdefault(("in_proj", l), []).append(
                h.reshape(-1, cfg.d_model)[:512])
            x = x + mamba.mamba1_block(lp, h, cfg)
        xf = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        final_feats.append(xf.reshape(-1, cfg.d_model)[:1024])
    feats = {"layers.in_proj":
             [jnp.concatenate(per_layer[("in_proj", l)], 0)
              for l in range(nL)]}
    if not cfg.tie_embeddings:
        feats["lm_head"] = jnp.concatenate(final_feats, 0)
    return feats, _token_support(cfg, batches)


def _probe_hybrid(cfg: ModelConfig, params, batches):
    from repro.models import hybrid as hy
    from repro.models import mamba
    G = cfg.n_layers // cfg.hybrid.attn_every
    k = cfg.hybrid.attn_every
    shared_in: list = []
    mamba_in: dict = {}
    final_feats = []
    sp = params["shared_attn"]
    for batch in batches:
        x = params["embed"].astype(cfg.cdtype)[batch["tokens"]]
        B, S, _ = x.shape
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        for g in range(G):
            h1 = L.rms_norm(x, sp["ln1"], cfg.norm_eps)
            shared_in.append(h1.reshape(-1, cfg.d_model)[:512])
            x = x + dense.attn_block(sp, h1, positions, cfg)
            x = x + dense.mlp_block(
                sp, L.rms_norm(x, sp["ln2"], cfg.norm_eps), cfg)
            for j in range(k):
                lp = trees.tree_map(lambda a: a[g, j], params["mamba"])
                h = L.rms_norm(x, lp["norm"], cfg.norm_eps)
                mamba_in.setdefault((g, j), []).append(
                    h.reshape(-1, cfg.d_model)[:256])
                x = x + mamba.mamba2_block(lp, h, cfg)
        xf = L.rms_norm(x, params["ln_f"], cfg.norm_eps)
        final_feats.append(xf.reshape(-1, cfg.d_model)[:1024])
    # stacked (G, k) leaf -> list-of-lists flattened in scan order
    feats = {
        "shared_attn.wq": jnp.concatenate(shared_in, 0),
        "shared_attn.wk": jnp.concatenate(shared_in, 0),
        "shared_attn.wv": jnp.concatenate(shared_in, 0),
        "mamba.in_proj": [[jnp.concatenate(mamba_in[(g, j)], 0)
                           for j in range(k)] for g in range(G)],
    }
    if not cfg.tie_embeddings:
        feats["lm_head"] = jnp.concatenate(final_feats, 0)
    return feats, _token_support(cfg, batches)


def _probe_encdec(cfg: ModelConfig, params, batches):
    from repro.models import encdec as ed
    nL = cfg.n_layers
    nE = cfg.encdec.n_enc_layers
    store: dict = {}
    for batch in batches:
        enc_out = ed.encode(cfg, params, batch["audio_embeds"])
        x = params["embed"].astype(cfg.cdtype)[batch["tokens"]]
        Sd = batch["tokens"].shape[1]
        x = x + params["dec_pos"].astype(cfg.cdtype)[:Sd]
        for l in range(nL):
            lp = trees.tree_map(lambda a: a[l], params["dec_layers"])
            h = L.layer_norm(x, lp["ln1_g"], lp["ln1_b"], cfg.norm_eps)
            _collect(store, ("dec_self", l), h)
            x = x + ed._mha(lp, h, h, cfg, causal=True)
            h = L.layer_norm(x, lp["ln2_g"], lp["ln2_b"], cfg.norm_eps)
            _collect(store, ("dec_crossq", l), h)
            _collect(store, ("dec_crosskv", l), enc_out)
            x = x + ed._mha(lp, h, enc_out, cfg, causal=False, pre="x")
            h = L.layer_norm(x, lp["ln3_g"], lp["ln3_b"], cfg.norm_eps)
            _collect(store, ("dec_mlp", l), h)
            x = x + L.gelu_mlp(h, lp["w_in"].astype(cfg.cdtype),
                               lp["b_in"].astype(cfg.cdtype),
                               lp["w_out"].astype(cfg.cdtype),
                               lp["b_out"].astype(cfg.cdtype))

    def stack(name, n):
        return [jnp.concatenate(store[(name, l)], 0) for l in range(n)]

    feats = {
        "dec_layers.wq": stack("dec_self", nL),
        "dec_layers.wk": stack("dec_self", nL),
        "dec_layers.wv": stack("dec_self", nL),
        "dec_layers.wxq": stack("dec_crossq", nL),
        "dec_layers.wxk": stack("dec_crosskv", nL),
        "dec_layers.wxv": stack("dec_crosskv", nL),
        "dec_layers.w_in": stack("dec_mlp", nL),
    }
    return feats, _token_support(cfg, batches)


# --------------------------------------------------------------------------
# aggregation entry point
# --------------------------------------------------------------------------
def aggregate_llm(cfg: ModelConfig, client_params: list,
                  client_projs: list = None,
                  macfg: MAEchoConfig = MAEchoConfig(tau=20, eta=0.5),
                  backend: str = "auto", mesh=None):
    """One-shot MA-Echo over fine-tuned LLM checkpoints.

    ``backend="auto"`` (default) promotes every leaf big enough to
    tile — including the scan-over-layers transformer stacks, whose
    layer axis folds into the stacked kernel grid — to the fused
    Pallas pipeline; smoke-scale models (dims below one 128-tile)
    degrade to the oracle with identical results.  On a host with
    several chips, ``backend="sharded2d"`` shards each leaf's residual
    2-D (out × in) over every chip — the mesh derived from the device
    count (4 → (2, 2)) unless ``mesh`` is given, with one psum per leaf
    per outer iteration over both axis groups — and places each host
    checkpoint straight into its shards; ``backend="sharded"`` splits
    only the out-rows.  Routing is compiled once per model shape into
    an ``AggPlan`` (``core.plan``); inspect it with
    ``core.maecho.dispatch_summary`` or ``dryrun_agg --backend ...``.
    """
    if client_projs is None:
        client_projs = [default_llm_projections(cfg, p)
                        for p in client_params]
    return maecho_aggregate(
        client_params, client_projs, macfg, convention="io",
        stack_levels=stack_levels_fn(cfg), backend=backend, mesh=mesh)
