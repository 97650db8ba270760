"""The filtered top-K program (``ops.als.top_k_items_filtered``) against
the top K of the masked full score rows (the tie rule of ``top_k_host``): every row bucket, catalogs
that do and do not fill their last tile, a tile narrow enough that each is
selected by ``lax.top_k`` and one wide enough that it is selected from its
block maxima (``ops.topk.select_plan``), and the rows that test a rule's
edge. One parametrised test, so each case counts."""

import numpy as np
import pytest

from predictionio_tpu.ops.als import tile_items, top_k_items_filtered
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
    else:
        raise AssertionError(name)
    excluded = np.full((rows, bucket_width(max(map(len, left_out)), 8)), NO_ITEM,
                       np.int32)
    for row, ids in zip(excluded, left_out):
        row[:len(ids)] = ids
    return dict(item=item, user=user, codes=codes, blocked=blocked, wanted=wanted,
                excluded=excluded, left_out=left_out)


CASES = ["no_filter", "every_item_filtered", "fewer_than_k_allowed",
         "ties_across_a_tile_edge", "several_categories", "more_than_32_categories"]


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
    ids, vals = top_k_items_filtered(
        c["user"], item_tiles, code_tiles, blocked.reshape(n_tiles, width),
        c["wanted"], c["excluded"], K)
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


def test_bucket_width_is_a_pow2_with_a_floor():
    assert [bucket_width(n, 8) for n in (0, 1, 8, 9, 1000)] == [8, 8, 8, 16, 1024]
    assert bucket_width(3, 1024) == 1024 and bucket_width(1025, 1024) == 2048
