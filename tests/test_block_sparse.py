"""Brute-force verification of the static block-sparsity ranges every
pruned kernel derives its iteration space from (kernels/block_sparse.py):
for each block, the predicted valid ranges must cover exactly (or, with a
prefix, as a hull) the ground truth computed from the dense position mask
— across causal / window / prefix_lm / document MaskSpecs."""
import itertools

import numpy as np
import pytest

from repro.core.mask import MaskSpec, causal, doc_boundaries, document
from repro.kernels import block_sparse as bs


def _dense_mask(br, bc, nq, nk, m: MaskSpec):
    """(Tq, Tk) boolean attend-mask, same semantics as MaskSpec.allow."""
    qp = m.q_offset + np.arange(nq * br)
    kp = m.kv_offset + np.arange(nk * bc)
    out = np.ones((nq * br, nk * bc), dtype=bool)
    pre = (kp < m.prefix_len)[None, :] if m.prefix_len else None
    if m.causal:
        c = kp[None, :] <= qp[:, None]
        out &= (c | pre) if pre is not None else c
    if m.window and m.window > 0:
        w = (qp[:, None] - kp[None, :]) < m.window
        out &= (w | pre) if pre is not None else w
    if m.document:
        seg_q = np.searchsorted(m.boundaries, qp, side="right")
        seg_k = np.searchsorted(m.boundaries, kp, side="right")
        out &= seg_q[:, None] == seg_k[None, :]
    return out


def _sweep():
    masks = []
    for (c, rel, window) in itertools.product(
            [False, True], [-96, -16, 0, 16, 96], [0, 1, 24, 1000]):
        masks.append(MaskSpec(causal=c, window=window, q_offset=rel))
    # prefix_lm (hull bounds) and static-boundary documents
    for pre in (1, 20, 64, 500):
        masks.append(MaskSpec(causal=True, prefix_len=pre))
        masks.append(MaskSpec(causal=True, window=24, prefix_len=pre,
                              q_offset=16))
    for bnd in ((0,), (0, 30), (0, 17, 40, 41), (0, 64, 128)):
        masks.append(document(boundaries=bnd))
        masks.append(document(boundaries=bnd, window=24))
        masks.append(MaskSpec(document=True, boundaries=bnd))  # doc-only
    return list(itertools.product([16, 32], [16, 48], [1, 3, 4], [1, 2, 5],
                                  masks))


@pytest.mark.parametrize("br,bc", [(16, 16), (16, 48), (32, 16), (32, 48)])
def test_block_bounds_match_dense_mask(br, bc):
    """kv/q bounds agree with any() of the dense mask for every block of
    every sweep config (hull: predicted range must contain every non-empty
    block and, for contiguous kinds, nothing more)."""
    for (br_, bc_, nq, nk, m) in _sweep():
        if (br_, bc_) != (br, bc):
            continue
        dm = _dense_mask(br, bc, nq, nk, m)
        hull = bool(m.prefix_len)     # prefix makes ranges a hull, not exact
        kw = dict(br=br, bc=bc, mask=m)
        for i in range(nq):
            lo, hi = bs.kv_block_bounds(i, nk=nk, **kw)
            assert 0 <= lo and hi <= nk - 1
            for j in range(nk):
                tile = dm[i * br:(i + 1) * br, j * bc:(j + 1) * bc]
                cfg = (br, bc, nq, nk, m, i, j)
                if hull:
                    assert (lo <= j <= hi) or not tile.any(), cfg
                else:
                    assert (lo <= j <= hi) == bool(tile.any()), cfg
        for j in range(nk):
            lo_q, hi_q = bs.q_block_bounds(j, nq=nq, **kw)
            for i in range(nq):
                tile = dm[i * br:(i + 1) * br, j * bc:(j + 1) * bc]
                cfg = (br, bc, nq, nk, m, i, j)
                if hull:
                    assert (lo_q <= i <= hi_q) or not tile.any(), cfg
                else:
                    assert (lo_q <= i <= hi_q) == bool(tile.any()), cfg


def test_profiles_count_the_same_valid_pairs():
    """The fwd/dq orientation (rows = q blocks) and the dkv orientation
    (rows = kv blocks) execute the same set of valid (i, j) pairs."""
    for (br, bc, nq, nk, m) in _sweep():
        if m.prefix_len:
            continue                   # hull ranges differ per orientation
        kw = dict(nq=nq, nk=nk, br=br, bc=bc, mask=m)
        pk, pq = bs.kv_profile(**kw), bs.q_profile(**kw)
        assert pk.executed_steps == pq.executed_steps, (br, bc, nq, nk, m)
        assert pk.full_steps == pq.full_steps == nq * nk
        assert pk.executed_steps <= pk.launched_steps <= pk.full_steps
        assert pk.seq_grid == max(pk.row_counts, default=0)


def test_local_causal_chunk_work_ratio():
    """The acceptance target: the local causal chunk (rel=0, Tq=Tk) at
    nq = nk ≥ 8 executes ≥1.5x fewer grid steps than the dense sweep."""
    for n in (8, 16):
        p = bs.kv_profile(nq=n, nk=n, br=128, bc=128, mask=causal())
        assert p.executed_steps == n * (n + 1) // 2      # exact trapezoid
        assert p.work_ratio >= 1.5, (n, p.work_ratio)
        pq = bs.q_profile(nq=n, nk=n, br=128, bc=128, mask=causal())
        assert pq.executed_steps == p.executed_steps


def test_document_prunes_below_dense_causal():
    """Packed-batch acceptance: a document mask (static boundaries) executes
    strictly fewer grid steps than the dense causal mask over the same
    sequence — the cross-document blocks are gone."""
    T, n = 1024, 8
    br = bc = T // n
    bnd = doc_boundaries(T, 4)
    pc = bs.kv_profile(nq=n, nk=n, br=br, bc=bc, mask=causal())
    pd = bs.kv_profile(nq=n, nk=n, br=br, bc=bc,
                       mask=document(boundaries=bnd))
    assert pd.executed_steps < pc.executed_steps < pd.full_steps
    pq = bs.q_profile(nq=n, nk=n, br=br, bc=bc,
                      mask=document(boundaries=bnd))
    assert pq.executed_steps == pd.executed_steps


def test_degenerate_ranges():
    """All-masked and all-unmasked edges of the range computation."""
    # q chunk entirely before the kv chunk: causal masks everything
    p = bs.kv_profile(nq=2, nk=2, br=64, bc=64, mask=causal(-128))
    assert p.executed_steps == 0 and p.seq_grid == 0
    assert p.work_ratio == float("inf")
    # no mask at all: pruning must be the identity
    p = bs.kv_profile(nq=3, nk=5, br=64, bc=64, mask=MaskSpec())
    assert p.executed_steps == p.full_steps == 15
    assert p.row_counts == (5, 5, 5)
    # window beyond the whole kv chunk: also the identity (causal only)
    p = bs.kv_profile(nq=2, nk=2, br=64, bc=64,
                      mask=MaskSpec(causal=True, window=10_000, q_offset=64))
    assert p.row_counts == (2, 2)
    # dynamic-segment documents: the causal half still prunes, but the
    # segment half needs runtime arrays
    m = document()
    assert m.needs_segments
    assert m.prunable                  # via the causal component
    # document-only (causal dropped, e.g. a ring step) with dynamic
    # segments: nothing static to prune at all
    assert not m.replace(causal=False).prunable


def test_traced_bounds_match_python_bounds():
    """The same formulas under jax tracing (kernel bodies / index maps)
    produce the same numbers as the Python path (grid sizing)."""
    import jax
    import jax.numpy as jnp

    for m in (MaskSpec(causal=True, window=40, q_offset=48),
              document(boundaries=(0, 37, 80), window=40, rel_offset=16),
              MaskSpec(causal=True, prefix_len=33)):
        kw = dict(br=32, bc=16, nk=7, mask=m)

        @jax.jit
        def traced(i, kw=kw):
            return jnp.stack(bs.kv_block_bounds(i, **kw))

        for i in range(4):
            want = bs.kv_block_bounds(i, **kw)
            got = tuple(int(x) for x in traced(jnp.int32(i)))
            assert got == want, (m, i, got, want)

        @jax.jit
        def traced_q(j, kw=kw):
            kwq = dict(br=kw["br"], bc=kw["bc"], nq=5, mask=kw["mask"])
            return jnp.stack(bs.q_block_bounds(j, **kwq))

        for j in range(4):
            want = bs.q_block_bounds(j, br=32, bc=16, nq=5, mask=m)
            got = tuple(int(x) for x in traced_q(jnp.int32(j)))
            assert got == want, (m, j, got, want)
