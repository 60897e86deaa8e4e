"""2-D (out × in) mesh-sharded aggregation scaling (ISSUE 5 tentpole).

Times the sharded2d MA-Echo pipeline over factored host-device grids
(1x1 / 2x1 / 2x2 / 2x4): the two-axis Gram phase alone
(``ops.maecho_sharded2d_gram`` — per-device residual *tile* + partial
contraction + ONE psum over both axis groups) and a full
``maecho_aggregate`` with ``backend="sharded2d"``.  A "thin" row times
the fleet-spanning case the 2-D shard exists for: a leaf whose
out-dim tile count cannot divide the full device count 1-D
(``ops.sharded_ok`` rejects it) but factors over the 2-D grid.

The forced host-device count must be fixed before jax initializes, so
every grid runs in its own subprocess; each child asserts Gram parity
against the jnp oracle.  On this CPU container the "devices" share one
socket, so the curve records interpret-mode *overhead* scaling, not
the TPU speedup — the row trajectory still gates regressions in the
2-D dispatch path (two-axis padding, shard_map specs, psum placement).
Rows land in ``BENCH_sharded2d_agg.json`` via ``benchmarks.run``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import refuse_on_tpu, row

_CHILD = r"""
import json, os, sys
nd, nm, out_d, in_d, N, tau, thin_out = map(int, sys.argv[1:8])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={nd * nm} "
    + os.environ.get("XLA_FLAGS", ""))
import time
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.core.maecho import MAEchoConfig, maecho_aggregate
from repro.kernels import ops

n = nd * nm
assert len(jax.devices()) >= n, (len(jax.devices()), n)
mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(nd, nm),
            ("data", "model"))
k = jax.random.PRNGKey(0)
W = jax.random.normal(k, (out_d, in_d)) * 0.3
V = jax.random.normal(jax.random.fold_in(k, 1), (N, out_d, in_d)) * 0.3
U = jnp.linalg.qr(jax.random.normal(jax.random.fold_in(k, 2),
                                    (N, in_d, 16)))[0]
s = jax.random.uniform(jax.random.fold_in(k, 3), (N, 16))
P = jnp.einsum("nik,nk,njk->nij", U, s, U)          # dense PSD


def best_of(fn, reps=3):
    out = fn()
    jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
    best = 1e30
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
        best = min(best, time.perf_counter() - t0)
    return out, best * 1e6


gram = jax.jit(lambda W, V, P: ops.maecho_sharded2d_gram(
    W, V, P, mesh=mesh, axis_out="data", axis_in="model")[0])
G, gram_us = best_of(lambda: gram(W, V, P))
# parity against a float64 numpy reference: at in_d >= 1024 the fp32
# jnp oracle's own single-pass accumulation error exceeds 1e-3, while
# the kernel's blockwise fp32 scratch stays ~1e-6 — compare to truth
R64 = np.einsum("noi,nij->noj",
                np.asarray(W, np.float64)[None] - np.asarray(V,
                                                             np.float64),
                np.asarray(P, np.float64))
G64 = np.einsum("noi,moi->nm", R64, R64)
rel = float(np.max(np.abs(np.asarray(G, np.float64) - G64))
            / np.max(np.abs(G64)))
assert rel < 1e-3, f"sharded2d Gram diverged from f64 truth: rel={rel}"

clients = [{"W": V[i]} for i in range(N)]
projs = [{"W": P[i]} for i in range(N)]
cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=60)
_, agg_us = best_of(lambda: maecho_aggregate(
    clients, projs, cfg, backend="sharded2d", mesh=mesh))

# the fleet-spanning thin leaf: 1-D-ineligible over n devices,
# 2-D-eligible over (nd, nm)
thin_us = 0.0
thin_1d_ok = True
if thin_out:
    thin_1d_ok = ops.sharded_ok(thin_out, in_d, n)
    Vt = V[:, :thin_out]
    ct = [{"W": Vt[i]} for i in range(N)]
    a, _ = best_of(lambda: maecho_aggregate(
        ct, projs, cfg, backend="oracle"))
    b, thin_us = best_of(lambda: maecho_aggregate(
        ct, projs, cfg, backend="sharded2d", mesh=mesh))
    err = float(jnp.max(jnp.abs(a["W"] - b["W"])))
    assert err < 1e-3, f"thin-leaf sharded2d parity: {err}"
print(json.dumps({"gram_us": gram_us, "agg_us": agg_us,
                  "thin_us": thin_us, "thin_1d_ok": thin_1d_ok,
                  "match": rel < 1e-3}))
"""


def _child(nd: int, nm: int, out_d: int, in_d: int, N: int, tau: int,
           thin_out: int = 0) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, str(nd), str(nm), str(out_d),
         str(in_d), str(N), str(tau), str(thin_out)],
        env=env, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(
            f"sharded2d_agg child (grid={nd}x{nm}) failed:\n"
            f"{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(quick: bool = False):
    refuse_on_tpu("sharded2d_agg")
    # interpret-mode sizes: the dense-P contraction is O(out·in²) per
    # client per pass on a single socket, so stay at smoke scale — the
    # trajectory gates dispatch regressions, not TPU throughput
    out_d, in_d, N, tau = ((512, 256, 3, 2) if quick
                           else (1024, 512, 3, 2))
    grids = [(1, 1), (2, 2)] if quick else [(1, 1), (2, 1), (2, 2),
                                            (2, 4)]
    thin_out = 256                       # 2 tiles: 1-D dies past d=2
    base = {}
    for nd, nm in grids:
        thin = thin_out if (nd, nm) == grids[-1] else 0
        res = _child(nd, nm, out_d, in_d, N, tau, thin)
        base.setdefault("gram", res["gram_us"])
        base.setdefault("agg", res["agg_us"])
        tag = f"out{out_d}x{in_d}_N{N}"
        row(f"sharded2d_agg/gram_d{nd}x{nm}_{tag}", res["gram_us"],
            f"vs_d1={base['gram'] / max(res['gram_us'], 1):.2f}x;"
            f"match={res['match']}")
        row(f"sharded2d_agg/agg_tau{tau}_d{nd}x{nm}_{tag}",
            res["agg_us"],
            f"vs_d1={base['agg'] / max(res['agg_us'], 1):.2f}x")
        if thin:
            row(f"sharded2d_agg/agg_thin_tau{tau}_d{nd}x{nm}_"
                f"out{thin_out}x{in_d}_N{N}", res["thin_us"],
                f"spans_{nd * nm}dev_despite_1d_ineligible="
                f"{not res['thin_1d_ok']}")


if __name__ == "__main__":
    run()
