"""The merge network of ops/bitonic.py, held to numpy: it sorts, its
packed kernels return the model's run-ids, and rows that tie on every
compared column come out in the order a plain simulation of the same
compare-exchange sequence leaves them — whatever layout a stage ran in.

Level lengths go from 2 to 2^15 rows: strides under 8, 8–64 and >= 128
on the plain split, and from 2^14 on the two tiled views.
"""

import functools

import numpy as np
import pytest

SENTINEL = 0xFFFFFFFF


def _sorted_runs(rng, k, p, ncmp, ties=False):
    """(K, P) columns, ``ncmp`` compared and one carried: each run
    sorted on the compared columns.  Without ``ties`` the last compared
    column is unique, so the sorted order is too."""
    n = k * p
    cols = [
        rng.integers(0, 3, size=n, dtype=np.uint32) for _ in range(ncmp - 1)
    ]
    last = rng.integers(0, 2, size=n) if ties else rng.permutation(n)
    cols.append(last.astype(np.uint32))
    cols.append(np.arange(n, dtype=np.uint32))  # carried: the row's id
    cols = [c.reshape(k, p) for c in cols]
    order = np.lexsort(tuple(cols[c] for c in range(ncmp - 1, -1, -1)), axis=1)
    return [np.take_along_axis(c, order, axis=1) for c in cols]


def _numpy_sorted(cols, ncmp):
    flat = [c.reshape(-1) for c in cols]
    order = np.lexsort(tuple(flat[c] for c in range(ncmp - 1, -1, -1)))
    return [c[order] for c in flat]


def _simulate(cols, ncmp):
    """The network as the module's docstring states it, one stage at a
    time on whole rows: level by level each even run is joined to its
    odd neighbour reversed, then strides L/2 … 1 swap iff lo > hi."""
    x = np.stack(cols, axis=2).astype(np.int64)  # (K, P, C)
    while x.shape[0] > 1:
        x = np.concatenate([x[0::2], x[1::2, ::-1]], axis=1)
        b, l, c = x.shape
        s = l // 2
        while s:
            y = x.reshape(b, l // (2 * s), 2, s, c)
            lo, hi = y[:, :, 0], y[:, :, 1]
            gt = np.zeros(lo.shape[:-1], bool)
            eq = np.ones(lo.shape[:-1], bool)
            for j in range(ncmp):
                gt |= eq & (lo[..., j] > hi[..., j])
                eq &= lo[..., j] == hi[..., j]
            swap = gt[..., None]
            x = np.stack(
                [np.where(swap, hi, lo), np.where(swap, lo, hi)], axis=2
            ).reshape(b, l, c)
            s //= 2
    return [x[0, :, j].astype(np.uint32) for j in range(x.shape[2])]


@functools.lru_cache(maxsize=None)
def _network(ncmp, vmapped):
    import jax

    from dbeel_tpu.ops import bitonic

    def run(*cols):
        return bitonic._merge_runs(cols, ncmp)

    return jax.jit(jax.vmap(run) if vmapped else run)


# (log2 of the level length, runs, compared columns, under vmap).  Two
# runs of width 2 at every length up to 2^13 rows; 8 and 64 runs at some;
# every width, plain and under vmap, where the strides are all under 8
# (2^3) and where they reach 128 (2^8); the wider rows once more where
# most strides are >= 128 (2^13); the tiled views (2^14 rows and more)
# once a width and once through four levels.  The full product compiles
# for ten minutes, and tier-1's workers share their cores.
_SORT_CASES = sorted(
    {(log_l, 2, 2, False) for log_l in range(1, 14)}
    | {(6, 8, 2, False), (10, 64, 2, False), (13, 8, 2, False)}
    | {(13, 64, 2, False), (13, 8, 3, True), (13, 8, 8, False)}
    | {
        (log_l, 8, ncmp, vmapped)
        for log_l in (3, 8)
        for ncmp in (2, 3, 8)
        for vmapped in (False, True)
    }
    | {(14, 2, 2, False), (14, 2, 3, True), (14, 2, 8, False)}
    | {(15, 64, 2, False)}
)


def _case_id(case):
    log_l, k, ncmp, vmapped = case
    return f"l2^{log_l}-k{k}-w{ncmp}-{'vmap' if vmapped else 'plain'}"


@pytest.mark.parametrize(
    "log_l,k,ncmp,vmapped", _SORT_CASES, ids=map(_case_id, _SORT_CASES)
)
def test_network_sorts_like_numpy(log_l, k, ncmp, vmapped):
    l = 1 << log_l
    rng = np.random.default_rng(log_l * 1000 + k * 10 + ncmp)
    slots = [_sorted_runs(rng, k, l // k, ncmp) for _ in range(2)]
    if vmapped:
        got = _network(ncmp, True)(*[np.stack(c) for c in zip(*slots)])
        got = [[np.asarray(c)[j] for c in got] for j in range(2)]
    else:
        got = [[np.asarray(c) for c in _network(ncmp, False)(*slots[0])]]
    for cols, out in zip(slots, got):
        want = _numpy_sorted(cols, ncmp)
        for w, o in zip(want, out):
            assert (w == o).all()


@pytest.mark.parametrize(
    "k,p,ncmp",
    [(2, 4, 2), (8, 64, 3), (64, 16, 2), (2, 1 << 12, 8), (8, 1 << 11, 3),
     (2, 1 << 14, 2)],
)
def test_tied_rows_keep_the_order_of_the_simulated_network(k, p, ncmp):
    # Ties on every compared column: nothing but the sequence of
    # compare-exchanges (same pairs, same direction, swap iff lo > hi)
    # decides where the carried ids land.
    rng = np.random.default_rng(k * p + ncmp)
    cols = _sorted_runs(rng, k, p, ncmp, ties=True)
    got = _network(ncmp, False)(*cols)
    want = _simulate(cols, ncmp)
    compared = np.stack([c.reshape(-1) for c in cols[:ncmp]], axis=1)
    assert len(np.unique(compared, axis=0)) < k * p
    for w, o in zip(want, got):
        assert (w == np.asarray(o)).all()


def _model_rids(keys, counts, k, p, bits):
    """Run-ids of one batch slot: valid entries by (key, run, position),
    then the sentinel rows, whose run-id reads all ones."""
    run, pos = np.nonzero(np.arange(p)[None, :] < counts[:, None])
    order = np.lexsort((pos, run) + tuple(keys[run, pos].T[::-1]))
    rids = np.full(k * p, (1 << bits) - 1, np.uint32)
    rids[: len(order)] = run[order]
    return rids


@pytest.mark.parametrize(
    "k,p,words",
    [(4, 64, 1), (4, 64, 2), (64, 8, 1), (8, 1 << 11, 1), (8, 1 << 11, 2),
     (2, 1 << 14, 1)],
)
def test_packed_kernels_return_the_models_run_ids(k, p, words):
    from dbeel_tpu.ops import bitonic

    rng = np.random.default_rng(k + p + words)
    j = 3
    keys = rng.integers(0, 1 << 8, size=(j, k, p, words), dtype=np.uint32)
    order = np.lexsort(tuple(keys[..., w] for w in range(words - 1, -1, -1)), axis=2)
    keys = np.take_along_axis(keys, order[..., None], axis=2)
    counts = rng.integers(0, p + 1, size=(j, k)).astype(np.uint32)
    counts[0, 0], counts[0, 1] = p, 0  # a full run beside an empty one
    counts[2] = 0  # the empty batch slot that pads a last launch
    keys[np.arange(p)[None, None, :] >= counts[:, :, None]] = SENTINEL
    bits = bitonic.rid_pack_bits(k)
    if words == 1:
        out = bitonic.merge_runs_prefix32_packed_batch_kernel(
            keys[..., 0], counts, bits
        )
    else:
        out = bitonic.merge_runs_prefix64_packed_batch_kernel(
            keys, counts, bits
        )
    out = np.asarray(out)
    for slot in range(j):
        got = bitonic.unpack_rids(out[slot], bits, k * p)
        want = _model_rids(keys[slot], counts[slot], k, p, bits)
        assert (got == want).all(), slot
