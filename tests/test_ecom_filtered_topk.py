"""The filtered top-K program (``ops.als.top_k_items_filtered``) against
the top K of the masked full score rows (the tie rule of ``top_k_host``): every row bucket, catalogs
that do and do not fill their last tile, a tile narrow enough that each is
selected by ``lax.top_k`` and one wide enough that it is selected from its
block maxima (``ops.topk.select_plan``), and the rows that test a rule's
edge, those of the excluded ids among them: they reach the program as (row,
position) pairs grouped by tile (``ops.als.tile_pairs``, held to a
brute-force grouping below). One parametrised test, so each case counts."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import (
    FILTER_PAIR_FLOOR,
    tile_items,
    tile_pairs,
    top_k_items_filtered,
)
from predictionio_tpu.ops.topk import (
    NO_ITEM,
    bucket_width,
    select_plan,
    top_k_host,
)
from predictionio_tpu.templates.serving_util import allowed_items_host

RANK, K = 8, 16
#: tile widths: every tile of the first is selected by the plain plan, every
#: tile of the second, at 32 rows, by the blocked one
TILE, TILE_WIDE = 256, 1 << 16


def _case(name: str, rows: int, n_items: int, rng, tile: int = TILE) -> dict:
    """Tables and rules of one case; every row of the batch is under it."""
    item = rng.integers(-3, 4, (n_items, RANK)).astype(np.float32)  # exact sums
    user = rng.integers(-3, 4, (rows, RANK)).astype(np.float32)
    codes = rng.integers(0, 4, (n_items, 1)).astype(np.int32)
    blocked = np.zeros(n_items, bool)
    blocked[rng.choice(n_items, 20, replace=False)] = True
    wanted = np.full((rows, 2), -2, np.int32)
    left_out = [rng.choice(n_items, int(rng.integers(0, 40)), replace=False)
                for _ in range(rows)]
    if name == "no_filter":
        blocked[:] = False
        left_out = [np.zeros(0, np.int64)] * rows
    elif name == "every_item_filtered":
        wanted[:, 0] = 7  # a category no item carries
    elif name == "fewer_than_k_allowed":
        codes[:] = 0
        codes[rng.choice(n_items, 9, replace=False)] = 1
        wanted[:, 0] = 1
    elif name == "ties_across_a_tile_edge":
        best = 3 * rng.choice([-1.0, 1.0], RANK).astype(np.float32)
        item[tile - 6:tile + 6] = best  # twelve equal scores around the edge
        user[:] = best / 3  # ... that no other item can pass
        blocked[tile - 6:tile + 6] = False
        blocked[tile - 2] = True
    elif name == "several_categories":
        codes = rng.integers(-1, 6, (n_items, 3)).astype(np.int32)
        wanted[:, 0] = rng.integers(0, 6, rows)
        wanted[::2, 1] = rng.integers(0, 6, rows)[::2]
    elif name == "more_than_32_categories":
        codes = rng.integers(0, 100, (n_items, 2)).astype(np.int32)
        wanted = np.full((rows, 4), -2, np.int32)
        wanted[:, :3] = rng.integers(30, 100, (rows, 3))
        wanted[0] = -2  # and one row that names none
    elif name in EXCLUDED_CASES:
        # the rows' own lists decide alone, and every row's scores lead to
        # the ids its list names
        blocked[:] = False
        order = np.argsort(-(user @ item.T), axis=1, kind="stable")
        n_lanes = min(128, tile)
        if name == "excluded_id_is_its_blocks_maximum":
            left_out = [order[r, :1 + r % 3] for r in range(rows)]
        elif name == "two_excluded_ids_in_one_block":
            left_out = [np.unique(order[r, 0] // n_lanes * n_lanes + np.array([0, 5, 9]))
                        for r in range(rows)]
        elif name == "a_whole_block_excluded":
            left_out = [np.arange(n_lanes) + order[r, 0] // n_lanes * n_lanes
                        for r in range(rows)]
        elif name == "a_rows_whole_top_k_excluded":
            left_out = [order[r, :K + r] for r in range(rows)]
        elif name == "the_same_id_twice":
            left_out = [np.repeat(order[r, :3], 2) for r in range(rows)]
        elif name == "ids_past_the_catalog_and_padding":
            n_slots = -(-n_items // tile) * tile
            left_out = [np.array([NO_ITEM, order[r, 0], n_items, n_slots - 1,
                                  n_slots, n_slots + 7, NO_ITEM, order[r, 1]])
                        for r in range(rows)]
        elif name == "every_pair_in_one_tile":  # the pair bucket grows
            left_out = [rng.choice(tile, 40, replace=False) + tile for _ in range(rows)]
        elif name == "no_pair_at_all":
            left_out = [np.zeros(0, np.int64)] * rows
    else:
        raise AssertionError(name)
    excluded = np.full((rows, max(1, *map(len, left_out))), NO_ITEM, np.int32)
    for row, ids in zip(excluded, left_out):
        row[:len(ids)] = ids
    left_out = [ids[ids < n_items] for ids in left_out]
    return dict(item=item, user=user, codes=codes, blocked=blocked, wanted=wanted,
                excluded=excluded, left_out=left_out)


#: the cases of the excluded ids alone (ISSUE 36)
EXCLUDED_CASES = [
    "excluded_id_is_its_blocks_maximum", "two_excluded_ids_in_one_block",
    "a_whole_block_excluded", "a_rows_whole_top_k_excluded", "the_same_id_twice",
    "ids_past_the_catalog_and_padding", "every_pair_in_one_tile", "no_pair_at_all",
]
CASES = ["no_filter", "every_item_filtered", "fewer_than_k_allowed",
         "ties_across_a_tile_edge", "several_categories", "more_than_32_categories",
         *EXCLUDED_CASES]


SHAPES = [
    (rows, TILE, n_items)
    for rows in (8, 16, 32) for n_items in (2 * TILE, 2 * TILE + 188)
] + [(32, TILE_WIDE, 2 * TILE_WIDE), (32, TILE_WIDE, 2 * TILE_WIDE + 188)]


@pytest.mark.parametrize("name", CASES)
@pytest.mark.parametrize("rows,tile,n_items", SHAPES)
def test_filtered_program_is_top_k_of_the_masked_row(rows, tile, n_items, name):
    c = _case(name, rows, n_items, np.random.default_rng(rows * 1000 + n_items),
              tile)
    item_tiles = tile_items(c["item"], 0.0, tile=tile)
    code_tiles = tile_items(c["codes"], -1, tile=tile)
    n_tiles, _, width = item_tiles.shape
    assert width == tile and n_tiles * width >= n_items
    assert select_plan(rows, width, K) == (
        "blocked" if tile == TILE_WIDE else "plain")
    blocked = np.ones(n_tiles * width, bool)
    blocked[:n_items] = c["blocked"]
    drop_row, drop_col, n_pairs = tile_pairs(c["excluded"], n_tiles, width)
    pairs = {(r, i) for r, ids in enumerate(c["excluded"].tolist()) for i in ids
             if i < n_tiles * width}
    fullest = max([0, *np.bincount([i // width for _, i in pairs])])
    assert n_pairs == len(pairs)
    assert drop_row.shape[1] == bucket_width(fullest, FILTER_PAIR_FLOOR)
    if name == "every_pair_in_one_tile":
        assert fullest == 40 * rows > FILTER_PAIR_FLOOR
    ids, vals = top_k_items_filtered(
        c["user"], item_tiles, code_tiles, blocked.reshape(n_tiles, width),
        c["wanted"], drop_row, drop_col, K)
    ids, vals = np.asarray(ids), np.asarray(vals)
    # the rule spelled out, independent of both the program and its host mirror
    scores = c["user"] @ c["item"].T
    for r in range(rows):
        ok = ~c["blocked"]
        asked = c["wanted"][r][c["wanted"][r] >= 0]
        if c["wanted"][r, 0] >= 0:
            ok = ok & np.isin(c["codes"], asked).any(axis=1)
        ok[c["left_out"][r]] = False
        masked = np.where(ok, scores[r], -np.inf)
        # the tie rule in full (top_k_host's argpartition may keep any of the
        # items tied at the cut; the integer tables here tie on purpose)
        want_ids = np.lexsort((np.arange(n_items), -masked))[:K]
        want_vals = masked[want_ids]
        assert sorted(top_k_host(masked, K)[1].tolist()) == sorted(want_vals.tolist())
        n = min(K, int(ok.sum()))
        assert ids[r, :n].tolist() == want_ids[:n].tolist(), (name, r)
        assert vals[r, :n].tolist() == want_vals[:n].tolist()
        assert (ids[r, n:] == NO_ITEM).all() and np.isneginf(vals[r, n:]).all()
        if name == "ties_across_a_tile_edge":
            tied = [i for i in range(tile - 6, tile + 6)
                    if ok[i]][:K]
            assert ids[r, :len(tied)].tolist() == tied  # ascending id over the edge
    # and the host mirror of the rule says the same
    assert (allowed_items_host(c["codes"], c["blocked"], c["wanted"], c["excluded"])
            == np.stack([
                (~c["blocked"])
                & ((np.isin(c["codes"], c["wanted"][r][c["wanted"][r] >= 0]).any(axis=1))
                   if c["wanted"][r, 0] >= 0 else True)
                & ~np.isin(np.arange(n_items), c["left_out"][r])
                for r in range(rows)])).all()


@pytest.mark.parametrize("seed", range(6))
def test_tile_pairs_is_the_brute_force_grouping(seed):
    """Seeded lists (repeats, ``NO_ITEM`` in the middle, ids past the tiles,
    negative ids, some rows empty, one tile crowded past the floor) grouped
    pair by pair in Python."""
    rng = np.random.default_rng(seed)
    rows, n_tiles, width = int(rng.choice([8, 16, 32])), int(rng.integers(1, 6)), 512
    excluded = rng.integers(-3, n_tiles * width + 40, (rows, 70)).astype(np.int32)
    excluded[rng.random(excluded.shape) < 0.3] = NO_ITEM
    excluded[::5] = NO_ITEM  # rows that leave nothing out
    excluded[1, :30] = excluded[1, 30:60]  # repeats
    if seed % 2:  # 200 distinct ids of one row in the last tile
        excluded = np.concatenate([excluded, np.full((rows, 200), NO_ITEM, np.int32)], 1)
        excluded[2, 70:] = (n_tiles - 1) * width + rng.choice(width, 200, replace=False)
    want: list[set] = [set() for _ in range(n_tiles)]
    for r in range(rows):
        for i in excluded[r].tolist():
            if 0 <= i < n_tiles * width:
                want[i // width].add((r, i % width))
    drop_row, drop_col, n_pairs = tile_pairs(excluded, n_tiles, width)
    p = bucket_width(max(map(len, want)), FILTER_PAIR_FLOOR)
    assert p >= (256 if seed % 2 else FILTER_PAIR_FLOOR)
    assert drop_row.shape == drop_col.shape == (n_tiles, p)
    assert drop_row.dtype == drop_col.dtype == np.int32
    assert n_pairs == sum(map(len, want))
    for t in range(n_tiles):
        real = drop_col[t] < width
        assert real.sum() == len(want[t])  # no pair twice
        assert set(zip(drop_row[t, real].tolist(), drop_col[t, real].tolist())) == want[t]
        assert (drop_col[t, ~real] == width).all()  # the padding: past the tile
        assert (0 <= drop_row[t]).all() and (drop_row[t] < rows).all()


def test_a_filtered_batch_counts_its_pairs_and_its_pair_bucket():
    """``chunked_topk(filt=)`` over pinned tiles counts the real pairs it
    hands the device and the dispatch under its program's pair bucket, and
    ``/stats.json``'s ``batcher.filter`` block carries both."""
    from predictionio_tpu.api.stats import ServingStats
    from predictionio_tpu.templates.serving_util import TopkFilter, chunked_topk
    from predictionio_tpu.utils import spans

    rng = np.random.default_rng(36)
    n_items, rows = 700, 5
    item = rng.standard_normal((n_items, RANK)).astype(np.float32)
    user = rng.standard_normal((rows, RANK)).astype(np.float32)
    item_tiles = tile_items(item, 0.0, tile=TILE)
    n_tiles, _, width = item_tiles.shape
    blocked = np.zeros(n_tiles * width, bool)
    blocked[n_items:] = True
    excluded = np.full((rows, 6), NO_ITEM, np.int32)
    excluded[0, :4] = [3, 3, 699, 5]         # a repeat: three pairs
    excluded[2, :3] = [n_items + 100, 9, 9]  # past the tiles, a repeat: one
    filt = TopkFilter(
        codes=tile_items(np.zeros((n_items, 1), np.int32), -1, tile=TILE),
        blocked=blocked.reshape(n_tiles, width),
        wanted=np.full((rows, 2), -2, np.int32), excluded=excluded,
        item_tiles=item_tiles)
    collector = spans.Collector()
    previous = spans.bind(collector)
    try:
        answers = {slot: ids for part, ids_l, _ in chunked_topk(
            user, item, [(r, r, 10) for r in range(rows)], filt=filt)
            for (slot, _, _), ids in zip(part, ids_l)}
    finally:
        spans.bind(previous)
    counts = collector.take_counts()
    assert counts["filter.excludedPairs"] == 4
    assert counts[f"filter.pairBucket.{FILTER_PAIR_FLOOR}"] == 1
    assert not {3, 699, 5} & set(answers[0]) and 9 not in answers[2]
    assert answers[1][:10] == top_k_host(user[1] @ item.T, 10)[0].tolist()
    stats = ServingStats()
    stats.record_batch(size=rows, bucket=8, handle_ms=1.0, counts=counts)
    stats.record_batch(size=rows, bucket=8, handle_ms=1.0,
                       counts={**counts, "filter.pairBucket.256": 2})
    assert stats.to_json()["filter"] == {
        "excludedIds": 0, "excludedPairs": 8, "categoryRows": 0, "hostPath": 0,
        "shortAnswers": 0, "columnReads": 0, "eventReads": 0,
        f"pairBucket.{FILTER_PAIR_FLOOR}": 2, "pairBucket.256": 2}


def test_bucket_width_is_a_pow2_with_a_floor():
    assert [bucket_width(n, 8) for n in (0, 1, 8, 9, 1000)] == [8, 8, 8, 16, 1024]
    assert bucket_width(3, 1024) == 1024 and bucket_width(1025, 1024) == 2048
