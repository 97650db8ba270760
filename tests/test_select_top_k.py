"""``ops.topk.select_top_k`` against ``lax.top_k``, bit for bit, on both
sides of ``select_plan``'s rule; the rule as a function of the shape alone;
and ``/stats.json`` ``batcher.select`` counting a live batch under the plan
the rule names. Parametrised, so each case counts."""

import jax
import numpy as np
import pytest

from predictionio_tpu.ops import topk
from predictionio_tpu.ops.topk import SELECT_BLOCK, select_plan, select_top_k

B = SELECT_BLOCK
WIDE = 1 << 18


def _scores(name: str, rng) -> tuple[np.ndarray, int, str]:
    """One case: the scores, ``k`` and the plan the rule must name."""
    if name == "two_d":
        return rng.standard_normal((8, WIDE)).astype(np.float32), 16, "blocked"
    if name == "ragged_width":
        return rng.standard_normal((8, 700_001)).astype(np.float32), 16, "blocked"
    if name == "rows_in_two_leading_axes_ragged_width":
        return rng.standard_normal((3, 8, 100_003)).astype(np.float32), 16, "blocked"
    if name == "one_d":  # one row, or rows that fill no tile: lax.top_k itself
        return rng.standard_normal(8 * WIDE + 77).astype(np.float32), 16, "plain"
    if name == "rows_off_the_sublanes":
        return rng.standard_normal((3, 700_001)).astype(np.float32), 16, "plain"
    if name == "three_d":
        return rng.standard_normal((2, 4, WIDE)).astype(np.float32), 8, "blocked"
    if name == "integer_scores_tie_everywhere":
        # three values over 262,144 columns: ties inside every block,
        # over every block edge and at the cut
        return rng.integers(0, 3, (8, WIDE)).astype(np.float32), 16, "blocked"
    if name == "ties_across_a_block_edge":
        s = rng.integers(-9, 0, (8, WIDE)).astype(np.float32)
        s[:, 5 * B - 3:5 * B + 3] = 1.0  # six equal leaders around column 640
        s[:, 9 * B - 1:9 * B + 1] = 1.0  # and two around 1,152
        return s, 16, "blocked"
    if name == "ties_at_the_cut_over_more_blocks_than_k":
        s = np.zeros((8, WIDE), np.float32)
        s[:, ::B] = 2.0  # 2,048 blocks lead with the same maximum: 16 are taken
        s[:, 7 * B + 5] = 3.0
        return s, 16, "blocked"
    if name == "signed_zeros":
        s = np.where(rng.random((8, WIDE)) < 0.5, 0.0, -0.0).astype(np.float32)
        s[:, 1000:] -= rng.integers(0, 2, (8, WIDE - 1000)).astype(np.float32)
        return s, 16, "blocked"
    if name == "rows_of_all_minus_inf":
        s = rng.standard_normal((8, WIDE)).astype(np.float32)
        s[::2] = -np.inf
        return s, 16, "blocked"
    if name == "fewer_than_k_finite":
        s = np.full((8, WIDE), -np.inf, np.float32)
        for r in range(8):  # r + 3 finite entries, some sharing a block
            s[r, rng.choice(WIDE, r + 3, replace=False)] = rng.standard_normal(r + 3)
            s[r, 70_000:70_002] = 0.5
        return s, 16, "blocked"
    if name == "one_finite_entry_in_the_padded_last_block":
        s = np.full((8, WIDE + 5), -np.inf, np.float32)
        s[:, -1] = 1.0
        return s, 16, "blocked"
    if name == "k_1":
        return rng.standard_normal((8, WIDE)).astype(np.float32), 1, "blocked"
    if name == "k_over_the_number_of_blocks":
        return rng.standard_normal((512, 4096)).astype(np.float32), 64, "plain"
    if name == "k_bucket_512_on_a_tile":
        return rng.standard_normal((8, WIDE)).astype(np.float32), 512, "plain"
    if name == "narrow_catalog":
        return rng.standard_normal((32, 26_744)).astype(np.float32), 16, "plain"
    if name == "one_query_row":
        return rng.standard_normal(624_961).astype(np.float32), 16, "plain"
    if name == "k_over_128":
        return rng.standard_normal((8, 1 << 20)).astype(np.float32), 256, "plain"
    if name == "integer_dtype":
        return rng.integers(0, 1000, (8, WIDE)).astype(np.int32), 16, "blocked"
    raise AssertionError(name)


CASES = [
    "two_d", "ragged_width", "rows_in_two_leading_axes_ragged_width", "one_d",
    "rows_off_the_sublanes", "three_d",
    "integer_scores_tie_everywhere", "ties_across_a_block_edge",
    "ties_at_the_cut_over_more_blocks_than_k", "signed_zeros",
    "rows_of_all_minus_inf", "fewer_than_k_finite",
    "one_finite_entry_in_the_padded_last_block", "k_1",
    "k_over_the_number_of_blocks", "k_bucket_512_on_a_tile", "narrow_catalog",
    "one_query_row", "k_over_128", "integer_dtype",
]


@pytest.mark.parametrize("name", CASES)
def test_select_top_k_is_lax_top_k_bit_for_bit(name):
    scores, k, plan = _scores(name, np.random.default_rng(len(name)))
    rows = int(np.prod(scores.shape[:-1], dtype=np.int64))
    assert select_plan(rows, scores.shape[-1], k) == plan
    got_v, got_p = jax.jit(lambda s: select_top_k(s, k))(scores)
    want_v, want_p = jax.lax.top_k(scores, k)
    assert got_v.shape == want_v.shape == scores.shape[:-1] + (k,)
    assert got_p.dtype == want_p.dtype and got_v.dtype == want_v.dtype
    assert np.array_equal(np.asarray(got_p), np.asarray(want_p))
    bits = np.uint32 if scores.dtype.itemsize == 4 else np.uint64
    assert np.array_equal(
        np.asarray(got_v).view(bits), np.asarray(want_v).view(bits))
    if name == "ties_across_a_block_edge":
        lead = [*range(5 * B - 3, 5 * B + 3), 9 * B - 1, 9 * B]
        assert np.asarray(got_p)[0, :8].tolist() == lead  # ascending over the edge
    if name == "ties_at_the_cut_over_more_blocks_than_k":
        assert np.asarray(got_p)[0].tolist() == [7 * B + 5, *range(0, 15 * B, B)]


#: ``drop=`` on both sides of the rule: 8 rows, k 16, a width under and
#: one at ``select_plan``'s least blocked shape
DROP_SHAPES = {"plain": 1024, "blocked": 32_768}


def _drop(name: str, scores: np.ndarray, k: int, rng) -> tuple[list, list]:
    """The (row, column) pairs of one ``drop=`` case over ``scores``."""
    rows, width = scores.shape
    lead = np.argsort(-scores, axis=1, kind="stable")  # each row's ranking
    if name == "a_blocks_maximum":  # the block's next best must stand in
        return [0, 3], [int(lead[0, 0]), int(lead[3, 2])]
    if name == "two_of_one_row_in_one_block":
        c = int(lead[1, 0])
        return [1, 1, 1], [c, c ^ 1, c ^ 2]  # the leader and two beside it
    if name == "a_whole_block":
        first = int(lead[2, 0]) // B * B
        return [2] * B, list(range(first, first + B))
    if name == "a_rows_whole_top_k":
        return [5] * k + [6] * (2 * k), lead[5, :k].tolist() + lead[6, :2 * k].tolist()
    if name == "the_same_position_twice":
        return [4, 4, 7, 4], [int(lead[4, 0])] * 2 + [int(lead[7, 1]), int(lead[4, 0])]
    if name == "padding_past_the_row":  # a column of `width` pads the list
        return [0, 0, 2, 0], [width, int(lead[0, 1]), width, width]
    if name == "no_pair_at_all":
        return [0] * 8, [width] * 8
    if name == "every_row_a_pair_in_every_leading_block":
        return (np.repeat(np.arange(rows), k).tolist(), lead[:, :k].reshape(-1).tolist())
    if name == "a_list_of_more_than_one_chunk":  # 1,100 pairs in three chunks
        assert topk._DROP_CHUNK == 512
        n = 1100 // rows + 1
        return (np.repeat(np.arange(rows), n)[:1100].tolist() + [0] * 436,
                lead[:, :n].reshape(-1)[:1100].tolist() + [width] * 436)
    raise AssertionError(name)


DROP_CASES = [
    "a_blocks_maximum", "two_of_one_row_in_one_block", "a_whole_block",
    "a_rows_whole_top_k", "the_same_position_twice", "padding_past_the_row",
    "no_pair_at_all", "every_row_a_pair_in_every_leading_block",
    "a_list_of_more_than_one_chunk",
]


@pytest.mark.parametrize("plan", list(DROP_SHAPES))
@pytest.mark.parametrize("name", DROP_CASES)
@pytest.mark.parametrize("ties", [False, True])
def test_drop_is_lax_top_k_of_the_row_without_those_positions(name, plan, ties):
    """``select_top_k(drop=)`` against ``lax.top_k`` of the scores with the
    dropped positions at ``-inf``, bit for bit, ties included."""
    rng = np.random.default_rng(len(name))
    rows, width, k = 8, DROP_SHAPES[plan], 16
    assert select_plan(rows, width, k) == plan
    scores = (rng.integers(0, 5, (rows, width)) if ties
              else rng.standard_normal((rows, width))).astype(np.float32)
    row, col = (np.asarray(a, np.int32) for a in _drop(name, scores, k, rng))
    got_v, got_p = jax.jit(lambda s, r, c: select_top_k(s, k, drop=(r, c)))(
        scores, row, col)
    masked = scores.copy()
    masked[row[col < width], col[col < width]] = -np.inf
    want_v, want_p = jax.lax.top_k(masked, k)
    assert np.array_equal(np.asarray(got_p), np.asarray(want_p))
    assert np.array_equal(np.asarray(got_v).view(np.uint32),
                          np.asarray(want_v).view(np.uint32))
    if name == "a_rows_whole_top_k" and not ties:
        assert not set(np.asarray(got_p)[5].tolist()) & set(col[:k].tolist())


def test_a_long_drop_list_is_a_whole_number_of_chunks():
    import jax.numpy as jnp

    spec = jax.ShapeDtypeStruct((8, WIDE), jnp.float32)
    for n, fine in ((3, True), (512, True), (2048, True), (1100, False)):
        pairs = jax.ShapeDtypeStruct((n,), jnp.int32)
        trace = lambda: jax.make_jaxpr(  # noqa: E731
            lambda s, r, c: select_top_k(s, 16, drop=(r, c)))(spec, pairs, pairs)
        if fine:
            trace()
        else:
            with pytest.raises(ValueError, match="whole number of chunks"):
                trace()


#: the primitives ``select_top_k`` WITHOUT ``drop`` traced to before it took
#: one (ISSUE 36; the parent commit's jaxpr): every other scoring program
#: shares the function
_BLOCKED_BEFORE = [
    "reshape", "reduce_max", "reshape", "top_k", "jit", "iota",
    "broadcast_in_dim", "jit", "mul", "add", "mul", "jit", "add", "transpose",
    "reshape", "reshape", "lt", "add", "select_n", "broadcast_in_dim", "gather",
    "reshape", "top_k", "jit", "jit", "mul", "jit", "add",
]


@pytest.mark.parametrize("shape,before", [
    ((8, WIDE), _BLOCKED_BEFORE),
    ((8, 700_001), ["pad", *_BLOCKED_BEFORE]),
    ((32, 26_744), ["top_k"]),
])
def test_without_drop_the_trace_is_what_it_was(shape, before):
    import jax.numpy as jnp

    spec = jax.ShapeDtypeStruct(shape, jnp.float32)
    traced = jax.make_jaxpr(lambda s: select_top_k(s, 16))(spec)
    assert [e.primitive.name for e in traced.eqns] == before
    assert str(traced) == str(
        jax.make_jaxpr(lambda s: select_top_k(s, 16, drop=None))(spec))


@pytest.mark.parametrize("rows,width,k,plan", [
    (32, 1 << 19, 16, "blocked"),    # an e-commerce tile at a full batch
    (8, 1 << 19, 16, "blocked"),     # ... and at the floor of 8 rows
    (32, 1 << 19, 128, "blocked"),
    (32, 1 << 19, 256, "plain"),     # the chip's top_k loses the tie rule there
    (32, 1 << 19, 512, "plain"),     # the widest k bucket: 512 blocks of 4,096
    (32, 624_961, 16, "blocked"),    # KDD Cup 2011's catalog, a full batch
    (8, 624_961, 16, "blocked"),
    (1, 624_961, 16, "plain"),       # one query row: the tie rule is lost there
    (12, 624_961, 16, "plain"),      # rows that are not whole tiles of 8
    (256, 624_961, 16, "blocked"),
    (512, 624_961, 16, "plain"),     # the padded copy would pass 1 GiB
    (2048, 624_961, 16, "plain"),    # a full `pio batchpredict` chunk
    (2048, 1 << 19, 16, "blocked"),  # ... of whole blocks needs no copy
    (32, 26_744, 16, "plain"),       # ML-20M's catalog
    (2048, 26_744, 16, "plain"),
    (8, 512, 16, "plain"),           # every tier-1 catalog
])
def test_select_plan_is_a_function_of_the_shape(rows, width, k, plan):
    assert select_plan(rows, width, k) == plan
    assert select_plan(rows, width, k) == plan  # and of nothing else


@pytest.mark.parametrize("plan", ["blocked", "plain"])
def test_stats_count_a_live_batch_under_the_plan_it_names(plan):
    """A batcher over ``chunked_topk`` on pinned tables: every live batch
    lands in ``batcher.select`` under ``select_plan`` of its dispatch."""
    import jax.numpy as jnp

    from predictionio_tpu.ops.topk import bucket_k, bucket_rows, top_k_host
    from predictionio_tpu.serving import BatcherConfig, MicroBatcher
    from predictionio_tpu.templates.serving_util import TOPK_CHUNK, chunked_topk

    n_items = WIDE if plan == "blocked" else 3000
    rng = np.random.default_rng(5)
    users = rng.integers(-3, 4, (40, 4)).astype(np.float32)  # exact sums, ties
    items = rng.integers(-3, 4, (n_items, 4)).astype(np.float32)
    user_dev, item_dev = jnp.asarray(users), jnp.asarray(items)

    def handle_batch(bodies):
        valid = [(slot, body["user"], 10) for slot, body in enumerate(bodies)]
        out = [None] * len(bodies)
        for part, ids, scores in chunked_topk(user_dev, item_dev, valid):
            for (slot, _, k), r_ids, r_scores in zip(part, ids, scores):
                out[slot] = (200, {"ids": r_ids[:k], "scores": r_scores[:k]})
        return out

    batcher = MicroBatcher(handle_batch, BatcherConfig(max_batch_delay_ms=1.0))
    try:
        for u in (3, 17, 39):
            status, payload = batcher.submit({"user": u})
            assert status == 200
            want_ids, want_scores = top_k_host(users[u] @ items.T, 10)
            assert sorted(payload["scores"], reverse=True) == payload["scores"]
            assert payload["scores"] == want_scores.tolist()
            tied_at_cut = payload["scores"][-1] == want_scores[-1]
            assert payload["ids"] == want_ids.tolist() or tied_at_cut
        stats = batcher.stats.to_json()
    finally:
        batcher.close()
    assert stats["batches"] == 3
    rows = bucket_rows(1, TOPK_CHUNK)
    assert select_plan(rows, n_items, bucket_k(10, n_items)) == plan
    other = "plain" if plan == "blocked" else "blocked"
    assert stats["select"] == {plan: 3, other: 0}
    assert stats["rowsScored"] == 3 * rows and stats["rowsReal"] == 3


def test_every_plan_has_a_counter():
    from predictionio_tpu.api.stats import SELECT_PLANS, ServingStats

    assert set(SELECT_PLANS) == {"blocked", "plain"}
    stats = ServingStats()
    stats.record_batch(size=1, bucket=1, handle_ms=1.0,
                       counts={"select.blocked": 2, "select.plain": 1})
    assert stats.to_json()["select"] == {"blocked": 2, "plain": 1}
