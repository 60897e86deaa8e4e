"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("out_d,in_d,N", [
    (128, 128, 1), (128, 256, 2), (256, 128, 3), (384, 384, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maecho_update_sweep(out_d, in_d, N, dtype):
    k = jax.random.PRNGKey(out_d + in_d + N)
    W = jax.random.normal(k, (out_d, in_d), dtype)
    V = jax.random.normal(jax.random.fold_in(k, 1), (N, out_d, in_d),
                          dtype)
    P = (jax.random.normal(jax.random.fold_in(k, 2), (N, in_d, in_d))
         * 0.05).astype(dtype)
    alpha = jax.nn.softmax(jax.random.normal(jax.random.fold_in(k, 3),
                                             (N,)))
    got = ops.maecho_update(W, V, P, alpha, eta=0.7)
    want = ref.maecho_update_ref(W.astype(jnp.float32),
                                 V.astype(jnp.float32),
                                 P.astype(jnp.float32), alpha, 0.7)
    tol = 1e-4 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


def test_maecho_update_auto_pads_odd_shapes():
    k = jax.random.PRNGKey(0)
    W = jax.random.normal(k, (200, 300))
    V = jax.random.normal(jax.random.fold_in(k, 1), (2, 200, 300))
    P = jax.random.normal(jax.random.fold_in(k, 2), (2, 300, 300)) * 0.05
    alpha = jnp.array([0.6, 0.4])
    got = ops.maecho_update_auto(W, V, P, alpha, eta=1.0)
    want = ref.maecho_update_ref(W, V, P, alpha, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-4)


@pytest.mark.parametrize("d,b", [(128, 16), (256, 32), (512, 8)])
def test_block_rls_kernel(d, b):
    k = jax.random.PRNGKey(d + b)
    Q0 = jax.random.normal(k, (d, d))
    Q = Q0 @ Q0.T / d + jnp.eye(d)
    Xb = jax.random.normal(jax.random.fold_in(k, 1), (b, d))
    got = ops.block_rls_update(Q, Xb, 1.0, bo=128)
    want = ref.block_rls_update_ref(Q, Xb, 1.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("S,Hq,Hkv,D", [
    (256, 4, 4, 64),    # MHA
    (256, 8, 2, 64),    # GQA 4:1
    (512, 4, 1, 128),   # MQA
    (256, 6, 6, 96),    # non-128 head_dim (whisper/phi3 shapes)
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(S, Hq, Hkv, D, causal):
    k = jax.random.PRNGKey(S + Hq)
    B = 2
    q = jax.random.normal(k, (B, S, Hq, D))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, Hkv, D))
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, Hkv, D))
    got = ops.flash_attention(q, kk, v, causal=causal, bq=128, bk=128)
    want = ref.flash_attention_ref(q, kk, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    k = jax.random.PRNGKey(9)
    B, S, H, D = 1, 256, 2, 64
    q = jax.random.normal(k, (B, S, H, D), jnp.bfloat16)
    kk = jax.random.normal(jax.random.fold_in(k, 1), (B, S, H, D),
                           jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(k, 2), (B, S, H, D),
                          jnp.bfloat16)
    got = ops.flash_attention(q, kk, v, causal=True, bq=128, bk=128)
    want = ref.flash_attention_ref(q, kk, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=0.05)


def test_kernel_used_inside_algorithm_one():
    """One Algorithm-1 iteration stepped with the fused kernel matches
    the pure-jnp layer step (integration of kernel with core)."""
    from repro.core.maecho import MAEchoConfig, _leaf_sequential
    from repro.core.plan import LeafPlan
    k = jax.random.PRNGKey(3)
    N, out_d, in_d = 2, 128, 128
    W = jax.random.normal(k, (out_d, in_d))
    V = jax.random.normal(jax.random.fold_in(k, 1), (N, out_d, in_d))
    P = jax.random.normal(jax.random.fold_in(k, 2), (N, in_d, in_d)) * 0.1
    cfg = MAEchoConfig(tau=1, eta=0.5, qp_iters=100)
    lp = LeafPlan("W", 0, "kernel", "full", out_d, in_d, 128)
    W1, _ = _leaf_sequential(W, V, P, lp, cfg, "oi")
    # recover alpha by construction: uniform when G symmetric-ish is
    # fine for this check — instead compare against ref with the same
    # alpha extracted via the kernel path on identical inputs
    from repro.core.qp import solve_qp
    R = jnp.einsum("noi,nij->noj", W[None] - V, P)
    G = jnp.einsum("noi,moi->nm", R, R)
    alpha = solve_qp(G, 1.0, iters=100)
    W_kernel = ops.maecho_update(W, V, P, alpha, eta=0.5)
    np.testing.assert_allclose(np.asarray(W1), np.asarray(W_kernel),
                               atol=1e-3)


@pytest.mark.parametrize("backend,want", [("cpu", True), ("tpu", False)])
def test_interpret_resolves_from_backend(monkeypatch, backend, want):
    """Kernels interpret exactly when the default backend is the CPU;
    an explicit flag always wins."""
    from repro.kernels import env

    monkeypatch.setattr(env.jax, "default_backend", lambda: backend)
    assert env.interpret_default() is want
    assert env.resolve(None) is want
    assert env.resolve(True) is True and env.resolve(False) is False


def test_flash_attention_grad_matches_reference():
    """The kernel's custom VJP is the reference's: a train step can keep
    the kernel forward."""
    k = jax.random.PRNGKey(11)
    q = jax.random.normal(k, (2, 128, 4, 64))
    kk = jax.random.normal(jax.random.fold_in(k, 1), (2, 128, 2, 64))
    v = jax.random.normal(jax.random.fold_in(k, 2), (2, 128, 2, 64))
    w = jax.random.normal(jax.random.fold_in(k, 3), (2, 128, 4, 64))

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) * w)

    got = jax.grad(loss(lambda *a: ops.flash_attention(
        *a, causal=True, bq=128, bk=128)), argnums=(0, 1, 2))(q, kk, v)
    want = jax.grad(loss(lambda *a: ref.flash_attention_ref(
        *a, causal=True)), argnums=(0, 1, 2))(q, kk, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)
