"""The plain reference of ``reference.py`` with its attention split over the
chips, for cells whose sequence is long and split over several chips.

``reference.attention`` holds every key on every device and has each
device compute every block of query rows against every key, so over 4
chips at 32K tokens each chip does the whole attention, scores above the
causal diagonal included. Here the query rows are split over the
mesh's devices (each gathers the keys and values it needs) and the rows go
in ``N_CHUNKS`` chunks, chunk ``c`` against the keys of chunks ``0..c``
alone, so no device computes a score that every row masks. The function is
the same: float32 at ``highest`` precision, the masked softmax over the
same keys (a key left out is one the mask gives weight 0), and the float8
control rounds the same operands per block. Everything else (layers, loss,
gradients, AdamW) is ``reference.py``'s own, loaded as a module of its
own with this ``attention`` in place, so ``reference`` itself is left as
it is. It imports nothing of the program.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

N_CHUNKS = 4            # chunks of query rows, each against its causal keys
Q_BLOCK = 512           # query rows per block, at most
SCORE_BYTES = 1 << 29   # ... and at most this much f32 score per block


def _load_reference():
    path = Path(__file__).resolve().with_name("reference.py")
    spec = importlib.util.spec_from_file_location("reference_sp_base", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


R = _load_reference()


def q_block(rows: int, keys: int, Hq: int) -> int:
    qb = min(Q_BLOCK, rows)
    while qb > 16 and qb * keys * Hq * 4 > SCORE_BYTES:
        qb //= 2
    return qb


def rows_attention(q, k, v, sq, sk, row0, precision):
    """Causal GQA attention of query rows ``row0 + [0, len(q))`` against
    keys ``[0, len(k))``: q (Tq, Hq, D), k/v (K, Hkv, D) f32, sq (Tq,) and
    sk (K,) the rows' documents. Rows go in blocks, each rematerialised."""
    Tq, Hq, D = q.shape
    K, Hkv, _ = k.shape
    g = Hq // Hkv
    QB = q_block(Tq, K, Hq)
    nb = -(-Tq // QB)
    pad = nb * QB - Tq
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, QB, Hkv, g, D)
    sqb = jnp.pad(sq, (0, pad), constant_values=-1).reshape(nb, QB)
    kpos = jnp.arange(K)

    @jax.checkpoint
    def block(args):
        i, qi, si = args
        qpos = row0 + i * QB + jnp.arange(QB)
        s = jnp.einsum("qhgd,khd->hgqk", R._q(qi, precision),
                       R._q(k, precision),
                       precision=R.HIGHEST) / jnp.sqrt(jnp.float32(D))
        ok = (kpos[None, :] <= qpos[:, None]) & (si[:, None] == sk[None, :])
        # padded query rows see no key: a finite floor keeps them (and
        # their gradients) finite; they are cut off below
        s = jnp.where(ok, s, -1e30)
        s = s - jax.lax.stop_gradient(jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s)
        p = p / jnp.sum(p, -1, keepdims=True)
        return jnp.einsum("hgqk,khd->qhgd", R._q(p, precision),
                          R._q(v, precision), precision=R.HIGHEST)
    o = jax.lax.map(block, (jnp.arange(nb), qb, sqb))
    return o.reshape(nb * QB, Hq, D)[:Tq]


def attention(q, k, v, seg, precision, mesh):
    """``reference.attention``'s function: q (T, Hq, D), k/v (T, Hkv, D)
    f32, documents ``seg`` (T,) or None; each chunk's rows are split over
    the devices of ``mesh`` (1-D, axis "s"), which T's chunks divide."""
    T = q.shape[0]
    n = mesh.devices.size
    nc = max(c for c in range(1, N_CHUNKS + 1) if T % (c * n) == 0)
    C = T // nc
    seg = jnp.zeros((T,), jnp.int32) if seg is None else seg
    out = []
    for c in range(nc):
        rows, keys = slice(c * C, (c + 1) * C), slice(0, (c + 1) * C)

        def local(q, k, v, sq, sk, c=c):
            row0 = c * C + jax.lax.axis_index("s") * (C // n)
            return rows_attention(q, k, v, sq, sk, row0, precision)
        out.append(jax.shard_map(
            local, mesh=mesh, in_specs=(P("s"), P(), P(), P("s"), P()),
            out_specs=P("s"))(q[rows], k[keys], v[keys], seg[rows],
                              seg[keys]))
    return jnp.concatenate(out)


R.attention = attention
train_steps = R.train_steps
