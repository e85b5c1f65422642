"""The plain reference against the program at a small size, both in
float32 on the CPU: the same equations (interleaved RoPE, GQA head
grouping, q/k norm, tied or untied head) give the same loss and gradient
norms. Its blocked attention and blocked loss match an unblocked one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness as H
import reference
import traffic
import weights
from conftest import TINY_MODEL


def tiny(name):
    return dict(H.load_config(name), **TINY_MODEL, torch_dtype="float32")


@pytest.mark.parametrize("name", ["smollm-360m", "qwen3-8b"])
def test_reference_matches_the_program_in_float32(name):
    from repro.core.config import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.models.transformer import Runtime, build_model
    from repro.parallel.sharding import make_parallel_config
    c = tiny(name)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    par = make_parallel_config(mesh, ShapeSpec("t", 96, 1, "train"))
    model = build_model(H.program_config(c), Runtime(mesh=mesh, par=par))
    weights.check_layout(jax.eval_shape(model.init, jax.random.PRNGKey(0)),
                         c)
    p = weights.make(c, H.jax_key(5))
    row = traffic.train_rows(dict(seq=96, batch=1, zipf_exponent=1.0,
                                  base_seed=1), c["vocab_size"], 5, 1)[0]
    batch = {k: jnp.asarray(v) for k, v in row.items()}
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(
            lambda q: model.loss(q, batch)[0])(p)
    m = weights.dims(c)
    want, g_want = jax.value_and_grad(
        lambda q: reference.loss(m, q, batch, "fp32"))(p)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for k, a in weights.flatten(g_want).items():
        b = weights.flatten(g_got)[k]
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(
            jnp.linalg.norm(a)) + 1e-7, k


def naive_attention(q, k, v):
    T, Hq, D = q.shape
    g = Hq // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision="highest") / np.sqrt(D)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v,
                      precision="highest")


@pytest.mark.parametrize("T", [100, 1030])
def test_blocked_attention_matches_unblocked(T):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (T, 4, 16))
    k, v = (jax.random.normal(x, (T, 2, 16)) for x in ks[1:])
    got = reference.attention(q, k, v, None, "fp32")
    np.testing.assert_allclose(got, naive_attention(q, k, v), atol=2e-5)


def test_documents_do_not_see_each_other():
    T = 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (T, 2, 8))
    k, v = (jax.random.normal(x, (T, 2, 8)) for x in ks[1:])
    seg = jnp.repeat(jnp.arange(2), T // 2)
    got = reference.attention(q, k, v, seg, "fp32")
    second = naive_attention(q[T // 2:], k[T // 2:], v[T // 2:])
    np.testing.assert_allclose(got[T // 2:], second, atol=2e-5)


def test_float8_control_rounds_with_a_per_tensor_scale():
    x = jnp.array([1e-3, 2e-3, 3e-3, 1.0])
    y = reference.fp8_operand(x)
    assert float(y[3]) == pytest.approx(1.0, rel=0.07)
    assert bool(jnp.all(y[:3] > 0))       # small values survive the scale
    g = jax.grad(lambda t: jnp.sum(reference.fp8_operand(t) * 3.0))(x)
    np.testing.assert_allclose(g, 3.0, rtol=0.07)
