"""The plain reference: a dense decoder (RMSNorm, GQA attention with RoPE
and optional per-head q/k RMSNorm, SwiGLU MLP, tied or untied head) in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision,
with its loss, gradients and AdamW step. It imports nothing of the program
and is given only the configuration file, the seed's weights (made again
by ``weights.make``) and the token rows.

Departures from the published description, each shared with the program
so that the two compute the same function: RoPE rotates interleaved pairs
``(x[2i], x[2i+1])``, which is the published rotate-half form under a fixed
permutation of each head's query and key columns; q/k norm gains and all
norm gains are random around 1 (the weights are random).

``precision="fp8"`` is the control: every matmul operand, and in the
backward every cotangent that enters a matmul, is rounded to float8 (e4m3)
under a per-tensor scale, the nearest precision below the bfloat16 that
the configurations state. Memory stays bounded: layers are rematerialised,
attention runs in blocks of query rows, and the loss in blocks of tokens.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from weights import dims

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # query rows per attention block, at most
SCORE_BYTES = 1 << 28   # ... and at most this much f32 score per block
LOSS_BLOCK = 1024       # tokens per block of the loss


F8_MAX = 448.0          # largest finite float8 e4m3 value


def _fp8(x):
    """Round ``x`` to float8 e4m3 under one scale for the whole tensor (so
    its largest value maps to the format's largest), and back."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def fp8_operand(x):
    return _fp8(x)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_fp8(g),)          # the backward's matmuls take float8 too


fp8_operand.defvjp(_fp8_fwd, _fp8_bwd)


def _q(x, precision):
    return fp8_operand(x) if precision == "fp8" else x


def mm(a, b, precision="fp32"):
    return jnp.matmul(_q(a, precision), _q(b, precision), precision=HIGHEST)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x: (T, H, D) f32, pos: (T,) -> interleaved-pair rotation."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def shard(x, mesh, *spec):
    """Pin ``x`` to ``spec`` over ``mesh`` (a 1-D mesh, axis "s"), or leave
    it where it is when the reference runs on one device."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(*spec)))


def q_block(T: int, Hq: int) -> int:
    qb = Q_BLOCK
    while qb > 16 and qb * T * Hq * 4 > SCORE_BYTES:
        qb //= 2
    return qb


def attention(q, k, v, seg, precision, mesh=None):
    """Causal GQA attention, documents masked by ``seg`` (T,) when given.
    q (T, Hq, D), k/v (T, Hkv, D) f32; query rows go in blocks, each
    rematerialised, so no (T, T) array lives across blocks."""
    T, Hq, D = q.shape
    g = Hq // k.shape[1]
    kr = shard(jnp.repeat(k, g, axis=1), mesh)
    vr = shard(jnp.repeat(v, g, axis=1), mesh)
    QB = q_block(T, Hq)
    nb = -(-T // QB)
    pad = nb * QB - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(nb, QB, Hq, D)
    kpos = jnp.arange(T)
    sk = seg if seg is not None else jnp.zeros((T,), jnp.int32)
    sq = jnp.pad(sk, (0, pad), constant_values=-1).reshape(nb, QB)

    @jax.checkpoint
    def block(args):
        i, qi, si = args
        qpos = i * QB + jnp.arange(QB)
        s = jnp.einsum("qhd,khd->hqk", _q(qi, precision), _q(kr, precision),
                       precision=HIGHEST) / jnp.sqrt(jnp.float32(D))
        ok = (kpos[None, :] <= qpos[:, None]) & (si[:, None] == sk[None, :])
        # padded query rows see no key: a finite floor keeps them (and
        # their gradients) finite; they are cut off below
        s = jnp.where(ok[None], s, -1e30)
        s = s - jax.lax.stop_gradient(jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s)
        p = p / jnp.sum(p, -1, keepdims=True)
        return jnp.einsum("hqk,khd->qhd", _q(p, precision),
                          _q(vr, precision), precision=HIGHEST)
    o = jax.lax.map(block, (jnp.arange(nb), qb, sq))
    return o.reshape(nb * QB, Hq, D)[:T]


def layer(m, lp, h, pos, seg, precision, mesh=None):
    T = h.shape[0]
    lp = jax.tree.map(lambda x: x.astype(jnp.float32), lp)
    a, f = lp["attn"], lp["mlp"]
    x = rms(h, a["ln"], m["eps"])
    q = mm(x, a["wq"], precision).reshape(T, m["Hq"], m["hd"])
    k = mm(x, a["wk"], precision).reshape(T, m["Hkv"], m["hd"])
    v = mm(x, a["wv"], precision).reshape(T, m["Hkv"], m["hd"])
    if m["qk_norm"]:
        q = rms(q, a["q_norm"], m["eps"])
        k = rms(k, a["k_norm"], m["eps"])
    q, k = rope(q, pos, m["theta"]), rope(k, pos, m["theta"])
    o = shard(attention(q, k, v, seg, precision, mesh), mesh, "s")
    h = h + mm(o.reshape(T, -1), a["wo"], precision)
    x = rms(h, f["ln"], m["eps"])
    gate = shard(mm(x, f["wg"], precision), mesh, "s")
    up = shard(mm(x, f["wu"], precision), mesh, "s")
    return shard(h + mm(jax.nn.silu(gate) * up, f["wd"], precision),
                 mesh, "s")


def hidden(m, p, tokens, seg, precision, mesh=None):
    """Final hidden states (T, d) of one row of tokens (T,); over a mesh,
    the rows of every activation are split over its devices."""
    pos = jnp.arange(tokens.shape[0])
    h = shard(p["embed"][tokens].astype(jnp.float32), mesh, "s")

    @jax.checkpoint
    def body(h, lp):
        return layer(m, lp, h, pos, seg, precision, mesh), None
    h, _ = jax.lax.scan(body, h, p["layers"])
    return rms(h, p["ln_f"].astype(jnp.float32), m["eps"])


def head_weight(m, p):
    w = p["embed"].T if m["tied"] else p["head"]
    return w.astype(jnp.float32)


def row_loss_sum(m, p, tokens, labels, seg, precision, mesh=None):
    """(sum of token losses, number of tokens with a label) of one row."""
    h = hidden(m, p, tokens, seg, precision, mesh)
    w = head_weight(m, p)
    T = h.shape[0]
    nb = -(-T // LOSS_BLOCK)
    pad = nb * LOSS_BLOCK - T
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, LOSS_BLOCK, -1)
    lb = jnp.pad(labels, (0, pad), constant_values=-100).reshape(
        nb, LOSS_BLOCK)

    @jax.checkpoint
    def block(carry, xs):
        hi, li = xs
        logits = mm(hi, w, precision)
        lse = jax.scipy.special.logsumexp(logits, -1)
        ll = jnp.take_along_axis(logits, jnp.maximum(li, 0)[:, None],
                                 -1)[:, 0]
        return carry + jnp.sum(jnp.where(li >= 0, lse - ll, 0.0)), None
    total, _ = jax.lax.scan(block, jnp.float32(0), (hb, lb))
    return total, jnp.sum(labels >= 0)


def loss(m, p, batch, precision, mesh=None):
    """Mean token cross-entropy over every labelled token of the batch."""
    seg = batch.get("segment_ids")
    tot, n = jnp.float32(0), jnp.int32(0)
    for b in range(batch["tokens"].shape[0]):
        t, c = row_loss_sum(m, p, batch["tokens"][b], batch["labels"][b],
                            None if seg is None else seg[b], precision, mesh)
        tot, n = tot + t, n + c
    return tot / jnp.maximum(n, 1).astype(jnp.float32)


def schedule(step, opt):
    """Linear warm-up to ``lr``, then cosine to 0 at ``total_steps``."""
    lr, wu, tot = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < wu:
        return lr * (step + 1) / max(wu, 1)
    prog = min(max((step - wu) / max(tot - wu, 1), 0.0), 1.0)
    return 0.5 * lr * (1 + jnp.cos(jnp.pi * prog))


@partial(jax.jit, static_argnums=(0, 1, 2))
def _grad(mkey, precision, mesh, p, batch):
    m = dict(mkey)
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), p)
    val, g = jax.value_and_grad(
        lambda q: loss(m, q, batch, precision, mesh))(p32)
    if mesh is not None:
        g = jax.tree.map(lambda x: shard(x, mesh, *leaf_spec(
            x.shape, mesh.devices.size)), g)
    return val, g


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
def _adam(okey, p, mom, vel, g, step, lr):
    opt = dict(okey)
    gn = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(gn, 1e-6))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2 = opt["beta1"], opt["beta2"]
    bc1, bc2 = 1 - b1 ** (step + 1), 1 - b2 ** (step + 1)
    mom = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mom, g)
    vel = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, vel, g)

    def upd(w, a, b):
        w32 = w.astype(jnp.float32)
        u = (a / bc1) / (jnp.sqrt(b / bc2) + opt["eps"]) \
            + opt["weight_decay"] * w32
        return (w32 - lr * u).astype(w.dtype)
    return jax.tree.map(upd, p, mom, vel), mom, vel, gn, g


def leaf_spec(shape, n: int):
    """A leaf split along its first axis that ``n`` divides (as FSDP
    splits weights and optimizer state)."""
    spec = [None] * len(shape)
    for i, s in enumerate(shape):
        if s % n == 0:
            spec[i] = "s"
            break
    return spec


def spread(tree, mesh):
    return jax.tree.map(lambda x: jax.device_put(x, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(*leaf_spec(x.shape,
                                                    mesh.devices.size)))),
        tree)


def train_steps(config: dict, opt: dict, p, batches, precision="fp32",
                mesh=None):
    """Follow ``len(batches)`` AdamW steps from weights ``p`` (stored in
    their served dtype, as the configuration states). Returns the losses,
    the pre-clip gradient norms, the clipped first gradient and the
    weights after the last step. Over ``mesh`` the weights, optimizer
    state and activations are split over its devices."""
    mkey = tuple(sorted(dims(config).items()))
    okey = tuple(sorted(opt.items()))
    zeros = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), t))
    mom, vel = zeros(p), zeros(p)
    if mesh is not None:
        p, mom, vel = spread(p, mesh), spread(mom, mesh), spread(vel, mesh)
    losses, gnorms, first = [], [], None
    for i, batch in enumerate(batches):
        lval, g = _grad(mkey, precision, mesh, p, batch)
        p, mom, vel, gn, gclip = _adam(okey, p, mom, vel, g, i,
                                       jnp.float32(schedule(i, opt)))
        losses.append(float(lval))
        gnorms.append(float(gn))
        if i == 0:
            first = gclip
        del g, gclip
    return losses, gnorms, first, p

