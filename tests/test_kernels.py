"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle,
swept over shapes and dtypes per the deliverable spec."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import cms as cms_lib
from repro.kernels.cms.cms_update import cms_update_pallas
from repro.kernels.cms.ref import cms_update_ref
from repro.kernels.cms import ops as cms_ops
from repro.kernels.repulsion.nbody import repulsion_pallas
from repro.kernels.repulsion.ref import repulsion_ref
from repro.kernels.repulsion import ops as rep_ops
from repro.kernels.segment.seg_matmul import segment_sum_pallas
from repro.kernels.segment.ref import segment_sum_ref
from repro.kernels.segment import ops as seg_ops
from repro.kernels.merge.ref import merge_combine_ref, merge_positions
from repro.kernels.merge.sorted_merge import (
    merge_combine_pallas,
    merge_positions_pallas,
    merge_ranks,
)
from repro.kernels.merge import ops as merge_ops
from repro.obs.metrics import REGISTRY
from repro.kernels.raster.ref import (
    count_scatter_into_ref,
    count_scatter_ref,
    disk_accum_ref,
)
from repro.kernels.raster.splat import count_scatter_pallas, disk_accum_pallas
from repro.kernels.raster import ops as raster_ops
from repro.kernels.grid import ref as grid_ref
from repro.kernels.grid.tiled import far_field_pallas, near_field_pallas
from repro.kernels.grid import ops as grid_ops

INT32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------- repulsion
@pytest.mark.parametrize("n,tile", [(128, 128), (256, 128), (512, 256), (1024, 512)])
@pytest.mark.parametrize("use_radii", [True, False])
def test_repulsion_kernel_vs_ref(n, tile, use_radii):
    rng = np.random.default_rng(n + use_radii)
    pos = jnp.asarray(rng.uniform(-100, 100, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 4.0, n).astype(np.float32))
    radii = jnp.asarray(rng.uniform(0.0, 2.0, n).astype(np.float32))
    got = repulsion_pallas(
        pos, mass, radii, kr=80.0, ti=tile, tj=tile, use_radii=use_radii, interpret=True
    )
    want = repulsion_ref(pos, mass, 80.0, radii=radii if use_radii else None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-3)


def test_repulsion_ops_backends_agree():
    rng = np.random.default_rng(3)
    n = 300  # deliberately not tile-aligned: exercises padding
    pos = jnp.asarray(rng.uniform(-10, 10, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    f_ref = rep_ops.repulsion(pos, mass, 80.0, backend="ref")
    f_chk = rep_ops.repulsion(pos, mass, 80.0, backend="chunked")
    f_pal = rep_ops.repulsion(pos, mass, 80.0, backend="interpret", tile=128)
    np.testing.assert_allclose(np.asarray(f_chk), np.asarray(f_ref), rtol=2e-4, atol=1e-3)
    np.testing.assert_allclose(np.asarray(f_pal), np.asarray(f_ref), rtol=2e-4, atol=1e-3)


def test_repulsion_padding_neutral():
    """mass-0 padding must not change forces on real nodes."""
    rng = np.random.default_rng(5)
    n = 200
    pos = jnp.asarray(rng.uniform(-10, 10, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    f1 = rep_ops.repulsion(pos, mass, 80.0, backend="interpret", tile=128)
    pos_p = jnp.concatenate([pos, jnp.zeros((56, 2), jnp.float32)])
    mass_p = jnp.concatenate([mass, jnp.zeros(56, jnp.float32)])
    f2 = rep_ops.repulsion(pos_p, mass_p, 80.0, backend="interpret", tile=128)[:n]
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-5)


# ---------------------------------------------------------------------- CMS
@pytest.mark.parametrize("rows,cols,n,blk", [(1, 128, 700, 256), (4, 512, 2000, 1024), (4, 5000, 4096, 1024)])
def test_cms_kernel_vs_ref(rows, cols, n, blk):
    rng = np.random.default_rng(rows * cols)
    h = jnp.asarray(rng.integers(0, cols, (rows, n)).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 3, n).astype(np.float32))
    sketch = jnp.asarray(rng.uniform(0, 1, (rows, cols)).astype(np.float32))
    got = cms_update_pallas(sketch, h, w, cols, blk=blk, interpret=True)
    want = cms_update_ref(sketch, h, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


def test_cms_kernel_padding_mask():
    cols, n = 64, 100
    h = jnp.asarray(np.full((4, n), 7, np.int32))
    h = h.at[:, 50:].set(-1)  # padding
    w = jnp.ones(n, jnp.float32)
    sketch = jnp.zeros((4, cols), jnp.float32)
    got = cms_update_pallas(sketch, h, w, cols, blk=64, interpret=True)
    assert float(got[0, 7]) == 50.0


def test_cms_ops_matches_core_cms():
    """kernels/cms/ops must agree with core/cms.update (same hash family)."""
    rng = np.random.default_rng(11)
    cfg = cms_lib.CMSConfig(rows=4, cols=256, seed=3)
    keys = jnp.asarray(rng.integers(0, 100, 500).astype(np.int32))
    w = jnp.asarray(rng.uniform(0, 2, 500).astype(np.float32))
    s0 = cms_lib.init_sketch(cfg)
    want = cms_lib.update(s0, keys, w, cfg)
    got = cms_ops.update(s0, keys, w, cfg, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-4)


# ------------------------------------------------------------- segment sum
@pytest.mark.parametrize("e,d,n,tn,blk", [
    (500, 8, 100, 128, 256),
    (2048, 64, 300, 256, 512),
    (1000, 128, 1000, 256, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_sum_kernel_vs_ref(e, d, n, tn, blk, dtype):
    rng = np.random.default_rng(e + d)
    data = jnp.asarray(rng.standard_normal((e, d)).astype(np.float32)).astype(dtype)
    seg = jnp.asarray(rng.integers(0, n, e).astype(np.int32))
    got = segment_sum_pallas(data, seg, n, tn=tn, blk=blk, interpret=True)
    want = segment_sum_ref(data.astype(jnp.float32), seg, n)
    rtol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=rtol, atol=1e-2
    )


def test_segment_sum_drops_out_of_range():
    data = jnp.ones((10, 4), jnp.float32)
    seg = jnp.asarray([0, 1, 2, 99, -1, 0, 1, 2, 99, -1], jnp.int32)
    got = segment_sum_pallas(data, seg, 3, tn=128, blk=128, interpret=True)
    want = segment_sum_ref(data, seg, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    assert float(got.sum()) == 6 * 4  # 6 in-range rows


def test_segment_ops_wrapper():
    rng = np.random.default_rng(21)
    data = jnp.asarray(rng.standard_normal((256, 16)).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, 50, 256).astype(np.int32))
    a = seg_ops.segment_sum(data, seg, 50, backend="ref")
    b = seg_ops.segment_sum(data, seg, 50, backend="interpret")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)


def test_segment_sum_sorted_flag():
    """The ``indices_are_sorted`` fast path (FA2 attraction / grid stats)
    matches the unsorted path on sorted ids, incl. out-of-range tails."""
    rng = np.random.default_rng(13)
    data = jnp.asarray(rng.standard_normal((512, 3)).astype(np.float32))
    seg = jnp.asarray(np.sort(rng.integers(0, 80, 512)).astype(np.int32))
    seg = seg.at[-20:].set(80)  # trash tail sorts last, must be dropped
    a = seg_ops.segment_sum(data, seg, 80, backend="ref")
    b = seg_ops.segment_sum(data, seg, 80, backend="ref",
                            indices_are_sorted=True)
    c = seg_ops.segment_sum(data, seg, 80, backend="interpret",
                            indices_are_sorted=True)
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(c), np.asarray(a), rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ grid fields
@pytest.mark.parametrize("n,g,ti,tc", [
    (300, 8, 128, 128),   # C=64 < one cell tile
    (1000, 16, 256, 128),  # padding on both axes
    (512, 32, 256, 256),   # n < C
])
def test_grid_far_field_kernel_vs_ref(n, g, ti, tc):
    rng = np.random.default_rng(n + g)
    pos = jnp.asarray(rng.uniform(-300, 300, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 4.0, n).astype(np.float32))
    cell, order = grid_ref.bin_and_sort(pos, g)
    ccent, cmass = grid_ops.cell_stats(pos[order], mass[order], cell[order],
                                       g * g, backend="ref")
    want = grid_ref.far_field_ref(pos, mass, cell, ccent, cmass, 80.0)
    got = far_field_pallas(pos, mass, cell, ccent, cmass, 80.0,
                           ti=ti, tc=tc, interpret=True)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4 * max(scale, 1.0))


@pytest.mark.parametrize("n,g,window,ti", [
    (300, 8, 16, 128),
    (700, 4, 64, 128),   # heavy cells, window spilling into neighbor tiles
    (256, 16, 0, 128),   # empty band
    (100, 1, 256, 128),  # window > n: ti is raised to cover it
])
def test_grid_near_field_kernel_vs_ref(n, g, window, ti):
    rng = np.random.default_rng(n + window)
    pos = jnp.asarray(rng.uniform(-300, 300, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 4.0, n).astype(np.float32))
    cell, order = grid_ref.bin_and_sort(pos, g)
    pos_s, mass_s, cell_s = pos[order], mass[order], cell[order]
    want = grid_ref.near_field_ref(pos_s, mass_s, cell_s, 80.0, window)
    got = near_field_pallas(pos_s, mass_s, cell_s, 80.0, window,
                            ti=ti, interpret=True)
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4 * max(scale, 1.0))


def test_grid_ops_padding_neutral():
    """mass-0 padding must not change grid forces on real nodes."""
    rng = np.random.default_rng(17)
    n = 200
    pos = jnp.asarray(rng.uniform(-50, 50, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 2.0, n).astype(np.float32))
    cell, order = grid_ref.bin_and_sort(pos, 8)
    ccent, cmass = grid_ops.cell_stats(pos[order], mass[order], cell[order],
                                       64, backend="ref")
    f1 = grid_ref.far_field_ref(pos, mass, cell, ccent, cmass, 80.0)
    pos_p = jnp.concatenate([pos, jnp.zeros((56, 2), jnp.float32)])
    mass_p = jnp.concatenate([mass, jnp.zeros(56, jnp.float32)])
    cell_p = jnp.concatenate([cell, jnp.full(56, -1, jnp.int32)])
    f2 = grid_ref.far_field_ref(pos_p, mass_p, cell_p, ccent, cmass, 80.0)[:n]
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2), rtol=1e-5)


# ---------------------------------------------------- sorted-merge-combine

def _sorted_run(pairs: dict, size: int, s_cap: int):
    """{(a, b): w} → (a [size], b [size], w [size]) sorted, trash-padded."""
    items = sorted(pairs.items())
    assert len(items) <= size
    a = np.full(size, s_cap, np.int32)
    b = np.full(size, s_cap, np.int32)
    w = np.zeros(size, np.float32)
    for i, ((x, y), ww) in enumerate(items):
        a[i], b[i], w[i] = x, y, ww
    return jnp.asarray(a), jnp.asarray(b), jnp.asarray(w)


def _rand_pairs(rng, k: int, s_cap: int, max_w: int = 5) -> dict:
    pairs = {}
    while len(pairs) < k:
        x, y = sorted(rng.choice(s_cap, size=2, replace=False))
        pairs[(int(x), int(y))] = float(rng.integers(1, max_w + 1))
    return pairs


def _merge_oracle(state: dict, chunk: dict, cap: int):
    union = dict(state)
    for p, w in chunk.items():
        union[p] = union.get(p, 0) + w
    kept = dict(sorted(union.items())[:cap])
    return kept, len(union)


def _assert_merge_outputs_equal(got, want, label=""):
    for x, y in zip(got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=label)


@pytest.mark.parametrize("cap,c,ks,kc,tn,blk", [
    (64, 16, 20, 10, 64, 64),
    (256, 64, 200, 64, 64, 128),  # blk = 2·tn
    (100, 24, 77, 20, 32, 64),  # cap/C not tile-aligned: exercises padding
    (256, 64, 200, 64, 128, 64),  # tn = 2·blk: a band of three blocks
    (128, 64, 128, 60, 32, 16),  # state full to a block boundary, overflows
    (128, 64, 100, 0, 64, 32),  # chunk all sentinel
    (512, 16, 300, 16, 64, 32),  # cap ≫ C: the chunk fits one rank block
    (32, 256, 20, 200, 32, 16),  # C ≫ cap: the state fits one rank block
])
def test_merge_kernel_vs_ref(cap, c, ks, kc, tn, blk):
    rng = np.random.default_rng(cap + c)
    s_cap = 32
    state = _rand_pairs(rng, ks, s_cap)
    chunk = _rand_pairs(rng, kc, s_cap)
    sa, sb, sw = _sorted_run(state, cap, s_cap)
    ca, cb, cw = _sorted_run(chunk, c, s_cap)
    want = merge_combine_ref(sa, sb, sw, ca, cb, cw, s_cap)
    got = merge_combine_pallas(
        sa, sb, sw, ca, cb, cw, s_cap, tn=tn, blk=blk, interpret=True
    )
    _assert_merge_outputs_equal(got, want)
    # and both match the python oracle
    kept, n = _merge_oracle(state, chunk, cap)
    oa, ob, ow, n_out = want
    assert int(n_out) == n
    got_pairs = {
        (int(a), int(b)): float(w)
        for a, b, w in zip(np.asarray(oa), np.asarray(ob), np.asarray(ow))
        if a < s_cap
    }
    assert got_pairs == kept


@pytest.mark.parametrize("tn,blk", [(32, 32), (16, 8), (8, 16)])
@pytest.mark.parametrize("case", [
    "empty_chunk", "all_duplicate", "all_padding_state_too",
    "state_at_capacity", "chunk_below_state", "chunk_above_state",
    "dup_on_tile_boundary", "state_overflows_cap",
])
def test_merge_kernel_adversarial(case, tn, blk):
    """Pallas-interpret vs ref on the contract's edge cases, over band
    shapes (tn = blk, tn = 2·blk, blk = 2·tn). Both runs end on a block
    boundary, so the last tiles' bands clamp to a run's last block."""
    rng = np.random.default_rng(7)
    s_cap, cap, c = 16, 32, 16
    state = _rand_pairs(rng, 12, s_cap)
    if case == "empty_chunk":  # chunk all sentinel
        chunk = {}
    elif case == "all_duplicate":
        chunk = {p: 1.0 for p in list(state)[:c]}  # every pair already held
    elif case == "all_padding_state_too":
        state, chunk = {}, {}
    elif case == "state_at_capacity":
        state = _rand_pairs(rng, cap, s_cap)  # no free slot: pure overflow
        chunk = _rand_pairs(rng, c, s_cap)
    elif case == "chunk_below_state":
        state = {(8, j): 1.0 for j in range(9, 16)}
        chunk = {(0, j): 2.0 for j in range(1, 8)}  # all keys sort first
    elif case == "chunk_above_state":
        state = {(0, j): 1.0 for j in range(1, 8)}
        chunk = {(8, j): 2.0 for j in range(9, 16)}
    elif case == "dup_on_tile_boundary":
        # Chunk keys repeating the state's pairs on either side of a tile
        # boundary, plus a few new keys.
        state = _rand_pairs(rng, 24, s_cap)
        held = sorted(state)
        edge = tn if tn < len(held) else len(held) // 2
        chunk = {held[edge - 1]: 3.0, held[edge]: 4.0}
        chunk.update({p: 2.0 for p in _rand_pairs(rng, 6, s_cap)
                      if p not in state})
    else:  # state_overflows_cap: chunk keys below push the state past cap
        state = {(a, b): 1.0 for a in (4, 5, 6) for b in range(a + 1, 16)}
        chunk = {(0, j): 2.0 for j in range(1, 16)}
    sa, sb, sw = _sorted_run(state, cap, s_cap)
    ca, cb, cw = _sorted_run(chunk, c, s_cap)
    want = merge_combine_ref(sa, sb, sw, ca, cb, cw, s_cap)
    got = merge_combine_pallas(
        sa, sb, sw, ca, cb, cw, s_cap, tn=tn, blk=blk, interpret=True
    )
    _assert_merge_outputs_equal(got, want, case)
    kept, n = _merge_oracle(state, chunk, cap)
    assert int(want[3]) == n
    oa, ow = np.asarray(want[0]), np.asarray(want[2])
    assert ((oa < s_cap) == (np.arange(cap) < len(kept))).all()
    want_w = np.array([w for _, w in sorted(kept.items())], np.float32)
    np.testing.assert_array_equal(ow[: len(kept)], want_w)


def test_merge_grid_is_banded():
    """The kernel's grid visits a band of input blocks per output tile:
    at most 2·band·tiles steps, where a dense (tiles × blocks) grid
    would take tiles · (cap + C) / blk."""
    rng = np.random.default_rng(11)
    s_cap, cap, c, tn, blk = 64, 352, 96, 32, 16
    sa, sb, sw = _sorted_run(_rand_pairs(rng, 300, s_cap), cap, s_cap)
    ca, cb, cw = _sorted_run(_rand_pairs(rng, 70, s_cap), c, s_cap)
    for name in ("merge.grid_steps", "merge.dense_grid_steps"):
        REGISTRY.gauge(name).set(-1)
    got = merge_combine_pallas(
        sa, sb, sw, ca, cb, cw, s_cap, tn=tn, blk=blk, interpret=True
    )
    want = merge_combine_ref(sa, sb, sw, ca, cb, cw, s_cap)
    _assert_merge_outputs_equal(got, want)
    tiles, band = cap // tn, tn // blk + 1
    steps = REGISTRY.value("merge.grid_steps")
    dense = REGISTRY.value("merge.dense_grid_steps")
    assert 0 < steps <= 2 * band * tiles
    assert dense == tiles * (cap + c) // blk
    assert steps * 4 < dense


_SENT = 0xFFFFFFFF


def _key_run(keys, size: int):
    """Sorted uint32 keys, sentinel-padded to ``size``."""
    keys = np.sort(np.asarray(keys, np.uint64))
    out = np.full(size, _SENT, np.uint32)
    out[: len(keys)] = keys
    return out


def _unique_keys(rng, k: int, lo: int = 0, hi: int = _SENT):
    """k distinct keys in [lo, hi), sorted."""
    keys = np.unique(rng.integers(lo, hi, 2 * k + 8, dtype=np.uint64))
    return rng.choice(keys, k, replace=False)


def _rank_case(case: str, rng):
    """(queries, table, weights or None) for one ``merge_ranks`` case."""
    if case == "random":
        return (_key_run(_unique_keys(rng, 50), 64),
                _key_run(rng.integers(0, 1000, 70), 80), None)
    if case == "all_sentinel":
        return _key_run([], 40), _key_run([], 24), None
    if case == "queries_below_table":
        return (_key_run(_unique_keys(rng, 30, 0, 1000), 32),
                _key_run(_unique_keys(rng, 60, 5000, 9000), 64), None)
    if case == "queries_above_table":
        return (_key_run(_unique_keys(rng, 30, 5000, 9000), 32),
                _key_run(_unique_keys(rng, 60, 0, 1000), 64), None)
    if case == "queries_in_one_table_block":
        table = _unique_keys(rng, 60, 0, 10_000)
        inside = np.arange(table[20], table[22] + 1)
        return (_key_run(rng.choice(inside, min(30, inside.size), replace=False), 32),
                _key_run(table, 64), None)
    if case == "table_in_one_query_block":
        queries = _unique_keys(rng, 60, 0, 10_000)
        inside = np.arange(queries[20], queries[22] + 1)
        return (_key_run(queries, 64),
                _key_run(rng.choice(inside, 30), 32), None)
    if case == "table_much_larger":  # cap ≫ C
        return (_key_run(_unique_keys(rng, 6), 8),
                _key_run(_unique_keys(rng, 300), 320), None)
    if case == "queries_much_larger":  # C ≫ cap
        return (_key_run(_unique_keys(rng, 300), 320),
                _key_run(_unique_keys(rng, 6), 8), None)
    if case == "keys_above_2_31":
        hi = _unique_keys(rng, 80, 1 << 31, (1 << 31) + 400)
        return (_key_run(hi[:40], 48),
                _key_run(np.concatenate([hi[30:], _unique_keys(rng, 10, 0, 1 << 31)]), 64),
                None)
    # weighted: a 0/1 weight on the table, which holds the sentinel too.
    table = _key_run(_unique_keys(rng, 50, 0, 2000), 64)
    return (_key_run(_unique_keys(rng, 40, 0, 2000), 48), table,
            rng.integers(0, 2, table.size).astype(bool))


@pytest.mark.parametrize("bq,bt", [(8, 8), (16, 8), (8, 32)])
@pytest.mark.parametrize("case", [
    "random", "all_sentinel", "queries_below_table", "queries_above_table",
    "queries_in_one_table_block", "table_in_one_query_block",
    "table_much_larger", "queries_much_larger", "keys_above_2_31", "weighted",
])
def test_merge_ranks_vs_searchsorted(case, bq, bt):
    """The rank walk (interpret mode) against ``np.searchsorted`` (side
    left, weighted by a prefix sum of ``wt``) and ``table == query``
    membership, for every query, sentinels included."""
    q, t, wt = _rank_case(case, np.random.default_rng(17))
    cnt, eq = merge_ranks(
        jnp.asarray(q), jnp.asarray(t), None if wt is None else jnp.asarray(wt),
        bq=bq, bt=bt, interpret=True,
    )
    ins = np.searchsorted(t, q, side="left")
    if wt is not None:
        ins = np.concatenate([[0], np.cumsum(wt)])[ins]
    np.testing.assert_array_equal(np.asarray(cnt), ins, err_msg=case)
    np.testing.assert_array_equal(np.asarray(eq), np.isin(q, t), err_msg=case)


@pytest.mark.parametrize("cap,c,live,c_live,dups,blk", [
    (256, 128, 200, 100, 40, 16),
    (300, 70, 290, 70, 70, 32),  # every chunk key held, cap not block-aligned
    (64, 512, 50, 400, 10, 32),  # C ≫ cap
    (1024, 32, 700, 20, 5, 64),  # cap ≫ C
    (128, 64, 0, 60, 0, 16),  # empty state
])
def test_merge_positions_pallas_vs_ref(cap, c, live, c_live, dups, blk):
    """The two rank walks give ``ref.merge_positions``' three outputs bit
    for bit on sorted unique runs, ``dups`` chunk keys held by the state."""
    rng = np.random.default_rng(cap + c)
    state = _unique_keys(rng, live)
    fresh = np.setdiff1d(_unique_keys(rng, 2 * c_live), state)
    chunk = np.concatenate([
        rng.choice(state, dups, replace=False) if dups else [],
        rng.choice(fresh, c_live - dups, replace=False),
    ])
    sk, ck = jnp.asarray(_key_run(state, cap)), jnp.asarray(_key_run(chunk, c))
    got = merge_positions_pallas(sk, ck, blk=blk, interpret=True)
    _assert_merge_outputs_equal(got, merge_positions(sk, ck))


def test_merge_rank_grid_is_banded():
    """Each rank pass walks at most q_blocks + t_blocks steps, where a
    dense (q_blocks × t_blocks) grid would take their product."""
    rng = np.random.default_rng(13)
    s_cap, cap, c, blk = 64, 384, 112, 16  # shapes no other test traces
    sa, sb, sw = _sorted_run(_rand_pairs(rng, 300, s_cap), cap, s_cap)
    ca, cb, cw = _sorted_run(_rand_pairs(rng, 70, s_cap), c, s_cap)
    for name in ("merge.rank_grid_steps", "merge.rank_search_steps"):
        REGISTRY.gauge(name).set(-1)
    got = merge_combine_pallas(
        sa, sb, sw, ca, cb, cw, s_cap, tn=32, blk=blk, interpret=True
    )
    _assert_merge_outputs_equal(got, merge_combine_ref(sa, sb, sw, ca, cb, cw, s_cap))
    state_blocks, chunk_blocks = cap // blk, c // blk
    steps = REGISTRY.value("merge.rank_grid_steps")
    assert 0 < steps <= 2 * (state_blocks + chunk_blocks)
    assert steps < 2 * state_blocks * chunk_blocks  # a dense grid per pass
    assert REGISTRY.value("merge.rank_search_steps") == (
        c * int(np.ceil(np.log2(cap + 1))) + cap * int(np.ceil(np.log2(c + 1)))
    )


def test_merge_ops_wrapper():
    rng = np.random.default_rng(3)
    s_cap, cap, c = 64, 128, 32
    sa, sb, sw = _sorted_run(_rand_pairs(rng, 90, s_cap), cap, s_cap)
    ca, cb, cw = _sorted_run(_rand_pairs(rng, 25, s_cap), c, s_cap)
    a = merge_ops.merge_combine(sa, sb, sw, ca, cb, cw, s_cap, backend="ref")
    b = merge_ops.merge_combine(sa, sb, sw, ca, cb, cw, s_cap, backend="interpret")
    _assert_merge_outputs_equal(a, b)


def test_merge_s_cap_at_packing_limit():
    """s_cap = 2^16 (the BGVConfig default): packed uint32 keys brush the
    sentinel — pairs near (s_cap-2, s_cap-1) must still merge exactly."""
    s_cap, cap, c = 1 << 16, 16, 8
    top = s_cap - 1
    state = {(0, 1): 1.0, (top - 1, top): 2.0}
    chunk = {(0, 1): 1.0, (top - 2, top): 3.0, (top - 1, top): 1.0}
    sa, sb, sw = _sorted_run(state, cap, s_cap)
    ca, cb, cw = _sorted_run(chunk, c, s_cap)
    want = merge_combine_ref(sa, sb, sw, ca, cb, cw, s_cap)
    got = merge_combine_pallas(
        sa, sb, sw, ca, cb, cw, s_cap, tn=32, blk=32, interpret=True
    )
    _assert_merge_outputs_equal(got, want, "s_cap at packing limit")
    kept, n = _merge_oracle(state, chunk, cap)
    oa, ob, ow, n_out = want
    assert int(n_out) == n == 3
    got_pairs = {
        (int(a), int(b)): float(w)
        for a, b, w in zip(np.asarray(oa), np.asarray(ob), np.asarray(ow))
        if a < s_cap
    }
    assert got_pairs == kept


def test_merge_rejects_oversized_s_cap():
    """The packed uint32 pair keys only cover s_cap ≤ 2^16."""
    z = jnp.zeros(8, jnp.int32)
    with pytest.raises(ValueError, match="s_cap"):
        merge_combine_ref(z, z, z.astype(jnp.float32), z, z,
                          z.astype(jnp.float32), (1 << 16) + 1)


# -------------------------------------------------------------------- raster
@pytest.mark.parametrize("n,size,tn,blk", [
    (500, 300, 64, 128),
    (2048, 4096, 512, 512),
    (777, 1000, 128, 256),  # neither tile- nor block-aligned
])
def test_count_scatter_kernel_vs_ref(n, size, tn, blk):
    rng = np.random.default_rng(n + size)
    pos = rng.integers(0, size, n).astype(np.int32)
    pos[::7] = INT32_MAX  # dropped-sample marker (padding chunks)
    pos[::11] = size + 3  # out of range
    inc = rng.integers(1, 6, n).astype(np.int32)
    want = count_scatter_ref(jnp.asarray(pos), jnp.asarray(inc), size)
    got = count_scatter_pallas(
        jnp.asarray(pos), jnp.asarray(inc), size, tn=tn, blk=blk, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", [
    "one_pixel", "all_padding", "negatives", "hot_tile", "far_tiles", "into_acc",
])
def test_count_scatter_adversarial(case):
    """Edge-splat contract edge cases: every sample in one pixel (dense
    single-cell collision), an empty / all-padding chunk (every position
    is the dropped marker), negative positions (must drop, not wrap), one
    tile holding more blocks than a fixed band of ceil(tn/blk) + 1 would
    visit, samples in three tiles far apart with the tiles between them
    untouched, and accumulation into a non-zero ``acc``."""
    n, size, tn, blk = 640, 256, 64, 128
    rng = np.random.default_rng(3)
    inc = rng.integers(1, 4, n).astype(np.int32)
    acc = None
    if case == "one_pixel":
        pos = np.full(n, 77, np.int32)
    elif case == "all_padding":
        pos = np.full(n, INT32_MAX, np.int32)
    elif case == "negatives":
        pos = rng.integers(-5, size, n).astype(np.int32)
    elif case == "hot_tile":
        # 600 samples in tile 1 fill 5 blocks; a fixed band visits 2.
        pos = np.concatenate([
            rng.integers(0, tn, 20),
            rng.integers(tn, 2 * tn, 600),
            rng.integers(3 * tn, 4 * tn, 20),
        ]).astype(np.int32)
        rng.shuffle(pos)
    elif case == "far_tiles":
        size = 40 * tn
        hot = np.array([2, 17, 38])
        pos = (hot[rng.integers(0, 3, n)] * tn + rng.integers(0, tn, n))
        pos = pos.astype(np.int32)
        acc = rng.integers(0, 50, size).astype(np.int32)
    else:
        pos = rng.integers(0, size, n).astype(np.int32)
        acc = rng.integers(0, 50, size).astype(np.int32)
    want = count_scatter_ref(jnp.asarray(pos), jnp.asarray(inc), size)
    if acc is not None:
        want = want + jnp.asarray(acc)
    got = count_scatter_pallas(
        jnp.asarray(pos), jnp.asarray(inc), size,
        acc=None if acc is None else jnp.asarray(acc),
        tn=tn, blk=blk, interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "one_pixel":
        assert int(want[77]) == int(inc.sum())
    if case == "all_padding":
        assert int(np.asarray(want).sum()) == 0
    if case == "hot_tile":
        in_tile = (pos >= tn) & (pos < 2 * tn)
        assert int(np.asarray(want)[tn : 2 * tn].sum()) == int(inc[in_tile].sum())
    if case == "far_tiles":
        # Untouched tiles carry the accumulator through unchanged.
        cold = np.ones(size, bool)
        for t in (2, 17, 38):
            cold[t * tn : (t + 1) * tn] = False
        np.testing.assert_array_equal(np.asarray(got)[cold], acc[cold])


def test_count_scatter_grid_is_banded():
    """The kernel's grid walks the (tile, block) pairs sorted samples can
    overlap: at most tiles + blocks steps, where a dense (tiles × blocks)
    grid would take their product."""
    rng = np.random.default_rng(13)
    n, size, tn, blk = 4096, 4000, 64, 128
    pos = rng.integers(0, size, n).astype(np.int32)
    inc = rng.integers(1, 4, n).astype(np.int32)
    for name in ("raster.grid_steps", "raster.dense_grid_steps"):
        REGISTRY.gauge(name).set(-1)
    got = count_scatter_pallas(
        jnp.asarray(pos), jnp.asarray(inc), size, tn=tn, blk=blk, interpret=True
    )
    want = count_scatter_ref(jnp.asarray(pos), jnp.asarray(inc), size)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    tiles, blocks = -(-size // tn), n // blk
    steps = REGISTRY.value("raster.grid_steps")
    dense = REGISTRY.value("raster.dense_grid_steps")
    assert 0 < steps <= tiles + blocks
    assert dense == tiles * blocks
    assert steps * 4 < dense


def test_count_scatter_into_matches_fresh():
    """The accumulating form (weighted and unit-increment sorted path)
    equals fresh-buffer scatter + add."""
    rng = np.random.default_rng(9)
    size, n = 500, 1200
    pos = rng.integers(-2, size + 2, n).astype(np.int32)
    inc = rng.integers(1, 5, n).astype(np.int32)
    base = jnp.asarray(rng.integers(0, 3, size).astype(np.int32))
    got_w = count_scatter_into_ref(base, jnp.asarray(pos), jnp.asarray(inc))
    want_w = base + count_scatter_ref(jnp.asarray(pos), jnp.asarray(inc), size)
    np.testing.assert_array_equal(np.asarray(got_w), np.asarray(want_w))
    got_1 = count_scatter_into_ref(base, jnp.asarray(pos), None)
    want_1 = base + count_scatter_ref(
        jnp.asarray(pos), jnp.ones(n, jnp.int32), size
    )
    np.testing.assert_array_equal(np.asarray(got_1), np.asarray(want_1))


@pytest.mark.parametrize("n,h,w,tp,blk", [
    (64, 32, 32, 128, 64),
    (300, 60, 100, 256, 128),  # h*w not tile-aligned, n not block-aligned
])
def test_disk_accum_kernel_vs_ref(n, h, w, tp, blk):
    rng = np.random.default_rng(n + h)
    cx = jnp.asarray(rng.uniform(-10, w + 10, n).astype(np.float32))
    cy = jnp.asarray(rng.uniform(-10, h + 10, n).astype(np.float32))
    r = jnp.asarray(rng.uniform(-2, 12, n).astype(np.float32))
    g = jnp.asarray(rng.integers(-2, 13, n).astype(np.int32))
    want = disk_accum_ref(cx, cy, r, g, 11, h, w)
    got = disk_accum_pallas(cx, cy, r, g, 11, h, w, tp=tp, blk=blk, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("case", ["one_pixel", "zero_extent", "all_dead"])
def test_disk_accum_adversarial(case):
    """All nodes stacked in one pixel, a degenerate zero-extent layout
    (every center identical — what a collapsed FA2 run produces), and an
    all-dead scene (r ≤ 0 everywhere, the s_cap padding regime)."""
    n, h, w = 96, 24, 40
    rng = np.random.default_rng(11)
    g = jnp.asarray(rng.integers(0, 11, n).astype(np.int32))
    if case == "one_pixel":
        # center 0.447px from pixel (13, 7), ≥ 0.632px from every other:
        # r ∈ (0.5, 0.6) ⇒ every disk covers exactly that one pixel.
        cx = jnp.full(n, 13.4, jnp.float32)
        cy = jnp.full(n, 7.2, jnp.float32)
        r = jnp.asarray(rng.uniform(0.5, 0.6, n).astype(np.float32))
    elif case == "zero_extent":
        cx = jnp.full(n, 20.0, jnp.float32)
        cy = jnp.full(n, 12.0, jnp.float32)
        r = jnp.asarray(rng.uniform(0.0, 6.0, n).astype(np.float32))
    else:
        cx = jnp.asarray(rng.uniform(0, w, n).astype(np.float32))
        cy = jnp.asarray(rng.uniform(0, h, n).astype(np.float32))
        r = jnp.asarray(-rng.uniform(0, 2, n).astype(np.float32))
    want = disk_accum_ref(cx, cy, r, g, 11, h, w)
    got = disk_accum_pallas(cx, cy, r, g, 11, h, w, tp=128, blk=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "one_pixel":
        assert int(np.asarray(want)[:, 7, 13].sum()) == n
        assert int(np.asarray(want).sum()) == n
    if case == "all_dead":
        assert int(np.asarray(want).sum()) == 0


def test_raster_ops_wrappers():
    rng = np.random.default_rng(21)
    pos = jnp.asarray(rng.integers(0, 200, 600).astype(np.int32))
    inc = jnp.asarray(rng.integers(1, 3, 600).astype(np.int32))
    a = raster_ops.count_scatter(pos, inc, 200, backend="ref")
    b = raster_ops.count_scatter(pos, inc, 200, backend="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # accumulating form: the aliased-in-place pallas path == ref
    base = jnp.asarray(rng.integers(0, 4, 200).astype(np.int32))
    for weights in (inc, None):
        ia = raster_ops.count_scatter_into(base, pos, weights, backend="ref")
        ib = raster_ops.count_scatter_into(
            base, pos, weights, backend="interpret"
        )
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))
    n = 80
    cx = jnp.asarray(rng.uniform(0, 50, n).astype(np.float32))
    cy = jnp.asarray(rng.uniform(0, 30, n).astype(np.float32))
    r = jnp.asarray(rng.uniform(0, 5, n).astype(np.float32))
    g = jnp.asarray(rng.integers(0, 11, n).astype(np.int32))
    da = raster_ops.disk_accum(cx, cy, r, g, 11, 30, 50, backend="ref")
    db = raster_ops.disk_accum(cx, cy, r, g, 11, 30, 50, backend="interpret")
    np.testing.assert_array_equal(np.asarray(da), np.asarray(db))
