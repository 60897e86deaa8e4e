"""Mesh-sharded aggregation scaling (ISSUE 3 tentpole).

Times the out-dim-sharded MA-Echo pipeline at 1/2/4/8 host devices:
the Gram phase alone (``ops.maecho_sharded_gram`` — residual tiles +
partial contraction + one psum) and a full ``maecho_aggregate`` with
``backend="sharded"``.  The forced host-device count must be fixed
before jax initializes, so every device count runs in its own
subprocess; the parent collects one JSON line per child.

On this CPU container the "devices" share one socket, so the curve
records interpret-mode *overhead* scaling, not the TPU speedup — the
row trajectory still gates regressions in the sharded dispatch path
(padding, shard_map plumbing, psum placement), and each child asserts
Gram parity against the jnp oracle.  Rows land in
``BENCH_sharded_agg.json`` via ``benchmarks.run``.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import refuse_on_tpu, row

_CHILD = r"""
import json, os, sys
n, out_d, in_d, N, tau = map(int, sys.argv[1:6])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={n} "
    + os.environ.get("XLA_FLAGS", ""))
import time
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from repro.core.maecho import MAEchoConfig, maecho_aggregate
from repro.kernels import ops, ref

assert len(jax.devices()) >= n, (len(jax.devices()), n)
mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))
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


gram = jax.jit(lambda W, V, P: ops.maecho_sharded_gram(
    W, V, P, mesh=mesh, axis="data")[0])
G, gram_us = best_of(lambda: gram(W, V, P))
Gr = ref.maecho_gram_ref(W, V, P)
rel = float(jnp.max(jnp.abs(G - Gr)) / jnp.max(jnp.abs(Gr)))
assert rel < 1e-3, f"sharded Gram diverged from oracle: rel={rel}"

clients = [{"W": V[i]} for i in range(N)]
projs = [{"W": P[i]} for i in range(N)]
cfg = MAEchoConfig(tau=tau, eta=0.5, qp_iters=60)
_, agg_us = best_of(lambda: maecho_aggregate(
    clients, projs, cfg, backend="sharded", mesh=mesh))
print(json.dumps({"gram_us": gram_us, "agg_us": agg_us,
                  "match": rel < 1e-3}))
"""


def _child(n: int, out_d: int, in_d: int, N: int, tau: int) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, str(n), str(out_d), str(in_d),
         str(N), str(tau)],
        env=env, capture_output=True, text=True, timeout=1200)
    if r.returncode != 0:
        raise RuntimeError(
            f"sharded_agg child (devices={n}) failed:\n"
            f"{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run(quick: bool = False):
    refuse_on_tpu("sharded_agg")
    out_d, in_d, N, tau = ((1024, 256, 3, 2) if quick
                           else (4096, 256, 4, 2))
    devices = [1, 2] if quick else [1, 2, 4, 8]
    base = {}
    for n in devices:
        res = _child(n, out_d, in_d, N, tau)
        base.setdefault("gram", res["gram_us"])
        base.setdefault("agg", res["agg_us"])
        tag = f"out{out_d}x{in_d}_N{N}"
        row(f"sharded_agg/gram_d{n}_{tag}", res["gram_us"],
            f"vs_d1={base['gram'] / max(res['gram_us'], 1):.2f}x;"
            f"match={res['match']}")
        row(f"sharded_agg/agg_tau{tau}_d{n}_{tag}", res["agg_us"],
            f"vs_d1={base['agg'] / max(res['agg_us'], 1):.2f}x")


if __name__ == "__main__":
    run()
