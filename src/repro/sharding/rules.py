"""Per-architecture sharding rules (DESIGN.md §5).

Mesh axes: optional ``pod`` (2), ``data`` (16), ``model`` (16).
Batch shards over (pod, data); weights shard their feature dims over
``model`` and — when ``cfg.fsdp`` — their other dim over ``data``
(ZeRO-3 style, required for the 314B/405B configs to fit 16 GB v5e).

Every rule passes through :func:`_ok`, which verifies divisibility and
falls back to replication — GSPMD would handle uneven shards with
padding, but even sharding keeps the roofline numbers honest.
"""
from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.utils import trees


def mesh_axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        out = 1
        for n in name:
            out *= mesh_axis_size(mesh, n)
        return out
    return mesh.shape[name] if name in mesh.shape else 1


def data_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.shape else ("data",)


def sharded_ok2d(out_d: int, in_d: int, out_asz: int, in_asz: int,
                 block: int = 128, warn: bool = False) -> bool:
    """Eligibility of a leaf for the 2-D (out × in) sharded pipeline.

    Both dims must reach one tile, and *each* dim's tile count must
    divide evenly over its axis group — the same block-granular `_ok`
    divisibility contract as ``kernels.ops.sharded_ok``, applied per
    axis (out-rows over ``cfg.mesh_axis``, in-columns over
    ``cfg.mesh_in_axis``; every device gets whole tiles on both dims).
    This is what lets a leaf whose out-dim alone cannot span the fleet
    (out tiles < device count) still aggregate sharded: the fleet
    factors as out_asz × in_asz and only the per-axis counts must
    divide.  With ``warn=True`` an ineligible leaf surfaces the
    fallback once via ``kernels.ops.fallback_warn`` (the plan compiler
    sets it) instead of degrading silently.
    """
    if out_d < block or in_d < block:
        ok = False
    else:
        ok = ((-(-out_d // block)) % out_asz == 0
              and (-(-in_d // block)) % in_asz == 0)
    if not ok and warn:
        from repro.kernels.ops import fallback_warn

        fallback_warn(
            f"sharded2d-ineligible leaf (out={out_d}, in={in_d}, "
            f"axes={out_asz}x{in_asz}, block={block}): falling back "
            f"to the 1-D out-dim shard / single-device dispatch")
    return ok


class Rules:
    """Builds PartitionSpecs with divisibility checks."""

    def __init__(self, mesh: Mesh, cfg: ModelConfig):
        self.mesh = mesh
        self.cfg = cfg
        self.fsdp_ax = "data" if cfg.fsdp else None

    def _ok(self, dim: int, ax) -> Optional[str]:
        if ax is None:
            return None
        if dim % mesh_axis_size(self.mesh, ax) == 0:
            return ax
        return None

    def spec(self, shape: tuple, axes: tuple) -> P:
        """axes: per-dim axis names (or None); divisibility-checked."""
        assert len(shape) == len(axes), (shape, axes)
        return P(*[self._ok(d, a) for d, a in zip(shape, axes)])

    # ---------------- parameters ----------------
    def param_spec(self, path: str, shape: tuple) -> P:
        cfg = self.cfg
        f = self.fsdp_ax
        rules: list[tuple[str, tuple]] = [
            # embeddings / heads
            (r"(^|\.)embed$", ("model", f)),
            (r"lm_head$", (f, "model")),
            (r"vision_proj$", (None, "model")),
            (r"dec_pos$", (None, None)),
            # attention (stacked: leading L handled by padding below)
            (r"\.w?x?q$|\.wq$", (f, "model")),
            (r"\.wk$|\.wxk$", (f, "model")),
            (r"\.wv$|\.wxv$", (f, "model")),
            (r"\.wo$|\.wxo$", ("model", f)),
            (r"\.b(q|k|v|xq|xv)$", ("model",)),
            (r"\.b(o|xo)$", (None,)),
            # dense mlp
            (r"w_gate$|w_up$|ws_gate$|ws_up$|w_in$", (f, "model")),
            (r"w_down$|ws_down$|w_out$", ("model", f)),
            (r"\.b_in$", ("model",)),
            (r"\.b_out$", (None,)),
            # moe (leading E handled below)
            (r"we_gate$|we_up$", (f, "model")),
            (r"we_down$", ("model", f)),
            (r"router$", (f, None)),
            # mamba
            (r"in_proj$", (f, "model")),
            (r"out_proj$", ("model", f)),
            (r"x_proj$", ("model", None)),
            (r"dt_proj$", (None, "model")),
            (r"conv_w$", ("model", None)),
            (r"A_log$", ("model", None) if cfg.ssm and cfg.ssm.version == 1
             else ("model",)),
            (r"dt_bias$|(^|\.)D$", ("model",)),
            (r"conv_b$", ("model",)),
            # norms / everything 1-D
            (r"ln|norm|gate_norm", (None,)),
        ]
        trailing = trees.first_match(rules, path)
        if trailing is None:
            return P(*([None] * len(shape)))
        lead = len(shape) - len(trailing)
        axes = (None,) * lead + tuple(trailing)
        return self.spec(shape, axes)

    def params_shardings(self, param_specs):
        def mk(path, leaf):
            return NamedSharding(self.mesh,
                                 self.param_spec(path, leaf.shape))
        return trees.map_with_path(mk, param_specs)

    # ---------------- inputs ----------------
    def batch_spec(self, path: str, shape: tuple) -> P:
        da = data_axes(self.mesh)
        key = path.split(".")[-1]
        if key in ("tokens", "labels", "token", "loss_mask"):
            return self.spec(shape, (da,) + (None,) * (len(shape) - 1))
        if key in ("patch_embeds", "audio_embeds"):
            return self.spec(shape, (da,) + (None,) * (len(shape) - 1))
        if key == "position":
            return P()
        return P(*([None] * len(shape)))

    def cache_spec(self, path: str, shape: tuple) -> P:
        """KV / SSM cache sharding for decode.

        KV: (L, B, W, Hkv, hd) — batch over data, heads over model when
        divisible, else head_dim over model.  SSM h: (…, B, di|nh, ds…)
        — inner dim over model.  Hybrid attn cache: (G, B, W, Hkv, hd).
        """
        da = data_axes(self.mesh)
        last = path.split(".")[-1]
        if last in ("k", "v", "xk", "xv"):
            L, B, W, Hkv, hd = shape[-5:] if len(shape) == 5 else (
                (None,) + shape)
            nm = mesh_axis_size(self.mesh, "model")
            if Hkv is not None and Hkv % nm == 0:
                axes = (None, da, None, "model", None)
            else:
                axes = (None, da, None, None, "model")
            return self.spec(shape, axes[-len(shape):])
        if last == "h":          # (L, B, di, ds) or (G, k, B, nh, ds, hd)
            if len(shape) == 4:
                return self.spec(shape, (None, da, "model", None))
            return self.spec(shape, (None, None, da, "model", None, None))
        if last == "conv":       # (L, B, K-1, C) or (G, k, B, K-1, C)
            if len(shape) == 4:
                return self.spec(shape, (None, da, None, "model"))
            return self.spec(shape, (None, None, da, None, "model"))
        return P(*([None] * len(shape)))

    def inputs_shardings(self, input_specs):
        def mk(path, leaf):
            if path.startswith("cache"):
                return NamedSharding(self.mesh,
                                     self.cache_spec(path, leaf.shape))
            return NamedSharding(self.mesh,
                                 self.batch_spec(path, leaf.shape))
        return trees.map_with_path(mk, input_specs)


def make_rules(mesh: Mesh, cfg: ModelConfig) -> Rules:
    return Rules(mesh, cfg)
