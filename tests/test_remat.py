"""Rematerialization-aware checkpointing (§3.3): numerical identity with
the un-checkpointed layer, and the no-FA-recompute property via FLOP
accounting (the paper's 'no numerical difference' claim)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mask as mk
from repro.core.attention import chunk_attn, chunk_attn_bwd
from repro.core.remat import apply_policy, remat_aware

B, T, H, D, DM = 2, 128, 4, 32, 128


def _layer_fns():
    def pre(p, x):
        h = x[0] if isinstance(x, tuple) else x
        q = (h @ p["wq"]).reshape(B, T, H, D)
        k = (h @ p["wk"]).reshape(B, T, H, D)
        v = (h @ p["wv"]).reshape(B, T, H, D)
        return q, k, v

    def attn_fwd(qkv):
        return chunk_attn(*qkv, mask=mk.causal())

    def attn_bwd(qkv, o, lse, do):
        return chunk_attn_bwd(*qkv, o, lse, do, mask=mk.causal())

    def post(p, x, o):
        h = x[0] if isinstance(x, tuple) else x
        h2 = h + o.reshape(B, T, H * D) @ p["wo"]
        return h2 + jax.nn.gelu(h2 @ p["w1"]) @ p["w2"]

    return pre, attn_fwd, attn_bwd, post


def _params(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "wq": jax.random.normal(ks[0], (DM, H * D)) * 0.05,
        "wk": jax.random.normal(ks[1], (DM, H * D)) * 0.05,
        "wv": jax.random.normal(ks[2], (DM, H * D)) * 0.05,
        "wo": jax.random.normal(ks[3], (H * D, DM)) * 0.05,
        "w1": jax.random.normal(ks[4], (DM, 4 * DM)) * 0.05,
        "w2": jax.random.normal(ks[5], (4 * DM, DM)) * 0.05,
    }


def test_remat_aware_value_and_grads_match_plain():
    pre, afwd, abwd, post = _layer_fns()
    params = _params()
    x = jax.random.normal(jax.random.PRNGKey(9), (B, T, DM))

    def plain(p, x):
        o, _ = afwd(pre(p, x))
        return post(p, x, o)

    ra = remat_aware(pre, afwd, abwd, post)

    def loss(f):
        return lambda p, x: jnp.sum(f(p, x) ** 2)

    v1, g1 = jax.value_and_grad(loss(plain))(params, x)
    v2, g2 = jax.value_and_grad(loss(ra))(params, x)
    assert v1 == v2  # forward bit-identical (paper: 'no numerical diff')
    for k in g1:
        np.testing.assert_allclose(np.asarray(g1[k]), np.asarray(g2[k]),
                                   atol=5e-4, rtol=1e-4)


def test_remat_aware_saves_fa_forward_flops():
    """grad-FLOPs ordering: hf (recomputes FA fwd) > remat_aware; and
    remat_aware ≤ none (delta trick)."""
    pre, afwd, abwd, post = _layer_fns()
    params = _params()
    x = jax.random.normal(jax.random.PRNGKey(9), (B, T, DM))

    def plain(p, x):
        o, _ = afwd(pre(p, x))
        return post(p, x, o)

    ra = remat_aware(pre, afwd, abwd, post)

    def gflops(f):
        g = jax.jit(jax.grad(lambda p, x: jnp.sum(f(p, x) ** 2)))
        return g.lower(params, x).compile().cost_analysis()["flops"]

    f_none = gflops(plain)
    f_hf = gflops(apply_policy(plain, "hf"))
    f_ra = gflops(ra)
    assert f_hf > f_ra, (f_hf, f_ra)
    # the saving must be at least one FA forward: 2·2·B·T²·H·D (QK^T + PV)
    fa_fwd = 2 * 2 * B * T * T * H * D
    assert f_hf - f_ra >= 0.9 * fa_fwd, (f_hf, f_ra, fa_fwd)


def test_policy_dispatch():
    pre, afwd, abwd, post = _layer_fns()

    def plain(p, x):
        o, _ = afwd(pre(p, x))
        return post(p, x, o)

    assert apply_policy(plain, "none") is plain
    assert apply_policy(plain, "hf") is not plain
    with pytest.raises(ValueError):
        apply_policy(plain, "bogus")
