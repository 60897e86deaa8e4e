"""Kernel micro-benchmarks: wall time of the jnp oracle path on CPU
(interpret-mode Pallas timing is not meaningful hardware signal; the
TPU numbers come from the roofline analysis) + allclose sanity.

Each row's ``derived`` records the effective Pallas interpret flag the
parity check ran under (the interpreter on a CPU backend, Mosaic on a
TPU — ``kernels/env.py``), so a trajectory point says which it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, timed
from repro.kernels import maecho_gram as mg
from repro.kernels import maecho_update as mu
from repro.kernels import maecho_v_update as mv
from repro.kernels import ops, ref
from repro.kernels.env import interpret_default


def run(quick: bool = False):
    interp = interpret_default()
    k = jax.random.PRNGKey(0)
    # maecho_update
    N, out_d, in_d = 5, 512, 512
    W = jax.random.normal(k, (out_d, in_d))
    V = jax.random.normal(jax.random.fold_in(k, 1), (N, out_d, in_d))
    P = jax.random.normal(jax.random.fold_in(k, 2),
                          (N, in_d, in_d)) * 0.1
    alpha = jnp.ones(N) / N
    fn = jax.jit(lambda: ref.maecho_update_ref(W, V, P, alpha, 0.5))
    fn()
    _, us = timed(fn)
    # the kernels take a stack of layers: this leaf is the stack L = 1
    W1, V1, P1 = W[None], V[:, None], P[:, None]
    got = mu.maecho_update_stacked(W1, V1, P1, alpha[None], eta=0.5)[0]
    ok = np.allclose(np.asarray(got),
                     np.asarray(ref.maecho_update_ref(W, V, P, alpha,
                                                      0.5)), atol=1e-3)
    row("kernels/maecho_update_512x512_N5", us, f"allclose={ok} interpret={interp}")

    # maecho_gram / maecho_v_update (streaming-pipeline stages)
    fn = jax.jit(lambda: ref.maecho_gram_ref(W, V, P))
    fn()
    _, us = timed(fn)
    ok = np.allclose(np.asarray(mg.maecho_gram_stacked(W1, V1, P1)[0]),
                     np.asarray(fn()), atol=1e-2, rtol=1e-4)
    row("kernels/maecho_gram_512x512_N5", us, f"allclose={ok} interpret={interp}")

    fn = jax.jit(lambda: ref.maecho_v_update_ref(W, V, P, 0.5))
    fn()
    _, us = timed(fn)
    ok = np.allclose(np.asarray(mv.maecho_v_update_stacked(
                         W1, V1, P1, frac=0.5)[:, 0]),
                     np.asarray(fn()), atol=1e-3)
    row("kernels/maecho_v_update_512x512_N5", us, f"allclose={ok} interpret={interp}")

    # block-RLS
    d, b = 512, 64
    Q = jnp.eye(d)
    Xb = jax.random.normal(k, (b, d))
    fn = jax.jit(lambda: ref.block_rls_update_ref(Q, Xb, 1.0))
    fn()
    _, us = timed(fn)
    got = ops.block_rls_update(Q, Xb, 1.0)
    ok = np.allclose(np.asarray(got),
                     np.asarray(ref.block_rls_update_ref(Q, Xb, 1.0)),
                     atol=1e-3)
    row("kernels/block_rls_512_b64", us, f"allclose={ok} interpret={interp}")

    # flash attention
    B, S, H, D = 2, 512, 4, 64
    q = jax.random.normal(k, (B, S, H, D))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, D))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, H, D))
    fn = jax.jit(lambda: ref.flash_attention_ref(q, kk, v, causal=True))
    fn()
    _, us = timed(fn)
    got = ops.flash_attention(q, kk, v, causal=True, bq=128, bk=128)
    ok = np.allclose(np.asarray(got),
                     np.asarray(ref.flash_attention_ref(q, kk, v,
                                                        causal=True)),
                     atol=1e-4)
    row("kernels/flash_attention_512x4x64", us, f"allclose={ok} interpret={interp}")


if __name__ == "__main__":
    run()
