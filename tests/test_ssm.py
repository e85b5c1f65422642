"""Mamba2 SSD: chunked algorithm vs the token-by-token recurrence oracle,
swept over chunk sizes and shapes (hypothesis)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.config import ModelConfig, SSMConfig
from repro.models.ssm import (ssm_apply, ssm_decode_step, ssm_params,
                              ssm_sequential_ref, _ssd_chunked)
from repro.launch.mesh import make_mesh


def _cfg(d_state=16, head_dim=8, chunk=8, d_model=32):
    return ModelConfig(name="t", arch_type="ssm", n_layers=1,
                       d_model=d_model, d_ff=0, vocab=16, dtype="float32",
                       ssm=SSMConfig(d_state=d_state, d_conv=4, expand=2,
                                     head_dim=head_dim, chunk=chunk))


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 100), chunk=st.sampled_from([4, 8, 16, 64]))
def test_chunked_matches_sequential(seed, chunk):
    cfg = _cfg(chunk=chunk)
    mesh = make_mesh((1, 1), ("data", "model"))
    p = ssm_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 64, 32)) * 0.5
    y_ref = ssm_sequential_ref(p, x, cfg)
    y = jax.jit(lambda p, x: ssm_apply(p, x, cfg, mesh=mesh,
                                       batch_axes=("data",)))(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=5e-5)


def test_ssd_state_carry_composes():
    """Running SSD over [first half; second half] with the carried state
    equals running it over the full sequence (the invariant the cross-
    device relay relies on)."""
    b, t, nh, hd, N = 2, 32, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, t, nh, hd))
    B = jax.random.normal(ks[1], (b, t, N)) * 0.3
    C = jax.random.normal(ks[2], (b, t, N)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, t, nh)))
    adt = -0.5 * dt
    s0 = jnp.zeros((b, nh, N, hd))
    y_full, s_full = _ssd_chunked(x, B, C, dt, adt, s0, chunk=8)
    h = t // 2
    y1, s1 = _ssd_chunked(x[:, :h], B[:, :h], C[:, :h], dt[:, :h],
                          adt[:, :h], s0, chunk=8)
    y2, s2 = _ssd_chunked(x[:, h:], B[:, h:], C[:, h:], dt[:, h:],
                          adt[:, h:], s1, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full), atol=1e-4)


def test_decode_step_matches_training_forward(mesh):
    """Greedy recurrent decode reproduces the training forward outputs
    position by position."""
    cfg = _cfg()
    p = ssm_params(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 24, 32)) * 0.5
    y_train = jax.jit(lambda p, x: ssm_apply(p, x, cfg, mesh=mesh,
                                             batch_axes=("data",)))(p, x)
    s = cfg.ssm
    state = jnp.zeros((1, s.n_heads(32), s.d_state, s.head_dim), jnp.float32)
    tail = jnp.zeros((1, s.d_conv - 1, s.d_inner(32) + 2 * s.d_state))
    outs = []
    for i in range(24):
        y, state, tail = ssm_decode_step(p, x[:, i:i + 1], state, tail, cfg)
        outs.append(y)
    y_dec = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(np.asarray(y_dec), np.asarray(y_train),
                               atol=5e-5)
