"""Random weights of a dense decoder from a seed, in one jitted call.

The tree has the program's layout (layers stacked on a leading axis, the
projections stored as ``(d_in, d_out)``) so that the program can be handed
it as it is; the reference makes the same tree from the same seed with
this function and reads it under its own equations. Nothing here imports
the program.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp


def dims(c: dict) -> dict:
    """The sizes the model is built from, from a configuration file."""
    d = c["hidden_size"]
    return dict(L=c["num_hidden_layers"], d=d, F=c["intermediate_size"],
                V=c["vocab_size"], Hq=c["num_attention_heads"],
                Hkv=c["num_key_value_heads"],
                hd=c.get("head_dim") or d // c["num_attention_heads"],
                qk_norm=bool(c.get("qk_norm", False)),
                tied=bool(c["tie_word_embeddings"]),
                eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
                dtype=c["torch_dtype"])


def shapes(c: dict) -> dict:
    """{path: (shape, init)}: init is ("normal", std) or ("norm", None)."""
    m = dims(c)
    L, d, F, V, Hq, Hkv, hd = (m[k] for k in
                               ("L", "d", "F", "V", "Hq", "Hkv", "hd"))
    s = {"embed": ((V, d), ("normal", 0.02)),
         "ln_f": ((d,), ("norm", None)),
         "layers/attn/wq": ((L, d, Hq * hd), ("normal", 1 / math.sqrt(d))),
         "layers/attn/wk": ((L, d, Hkv * hd), ("normal", 1 / math.sqrt(d))),
         "layers/attn/wv": ((L, d, Hkv * hd), ("normal", 1 / math.sqrt(d))),
         "layers/attn/wo": ((L, Hq * hd, d),
                            ("normal", 1 / math.sqrt(Hq * hd))),
         "layers/attn/ln": ((L, d), ("norm", None)),
         "layers/mlp/wg": ((L, d, F), ("normal", 1 / math.sqrt(d))),
         "layers/mlp/wu": ((L, d, F), ("normal", 1 / math.sqrt(d))),
         "layers/mlp/wd": ((L, F, d), ("normal", 1 / math.sqrt(F))),
         "layers/mlp/ln": ((L, d), ("norm", None))}
    if m["qk_norm"]:
        s["layers/attn/q_norm"] = ((L, hd), ("norm", None))
        s["layers/attn/k_norm"] = ((L, hd), ("norm", None))
    if not m["tied"]:
        s["head"] = ((d, V), ("normal", 1 / math.sqrt(d)))
    return s


def nest(flat: dict) -> dict:
    out = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out


def _leaf(key, shape, init, dtype):
    kind, std = init
    if kind == "norm":        # gains near 1, so that a norm that is skipped shows
        x = 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    else:
        x = std * jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


def make(c: dict, key, shardings=None, dtype=None):
    """The weight tree for configuration ``c`` from PRNG ``key``, made on
    the device in one jitted call (with ``shardings``: a matching tree of
    shardings, or None for the default device)."""
    s = shapes(c)
    dt = jnp.dtype(dtype or dims(c)["dtype"])
    names = sorted(s)

    def gen(key):
        ks = jax.random.split(key, len(names))
        return nest({n: _leaf(k, s[n][0], s[n][1], dt)
                     for n, k in zip(names, ks)})
    return jax.jit(gen, out_shardings=shardings)(key)


@partial(jax.jit, static_argnums=())
def leaf_norms(tree):
    """f32 L2 norm of every leaf, in ``sorted(flatten(tree))`` order."""
    flat = flatten(tree)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(flat[k].astype(
        jnp.float32)))) for k in sorted(flat)])


@jax.jit
def diff_norms(a, b):
    """f32 L2 norm of every leaf of ``a - b``, in sorted path order."""
    fa, fb = flatten(a), flatten(b)
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        fa[k].astype(jnp.float32) - fb[k].astype(jnp.float32))))
        for k in sorted(fa)])


def check_layout(abstract, c: dict) -> None:
    """Fail unless the program's parameter tree (``jax.eval_shape`` of its
    init) has exactly the paths, shapes and dtype that ``make`` gives."""
    got = {k: (tuple(v.shape), str(v.dtype))
           for k, v in flatten(abstract).items()}
    dt = str(jnp.dtype(dims(c)["dtype"]))
    want = {k: (tuple(s), dt) for k, (s, _) in shapes(c).items()}
    if got != want:
        raise ValueError(f"program parameters {sorted(got.items())} differ "
                         f"from the benchmark's {sorted(want.items())}")
