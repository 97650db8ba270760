"""Programs of the main paths compiled for a TPU v5e that is described, not
attached (the chip's own compiler is installed here): what interpret mode
and the CPU backend cannot show — the layouts the chip picks, the copies it
adds and whether a program fits its memory. Nothing runs; no time is read.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU's library, and every xdist
worker imports every test file."""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

#: Google Local Reviews (2018): benchmark/configs/twotower_glocal2018.json
USERS, ITEMS, PAIRS, DIM, BATCH = 4_567_431, 3_116_785, 11_453_845, 64, 8192
HBM = 15.75e9  # what the compiler lets a v5e program use of the chip's 16 GiB


@pytest.fixture(scope="module")
def four_chips():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(four_chips):
    return SingleDeviceSharding(four_chips[0])


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def test_the_two_tower_epoch_touches_rows_only_and_fits(one_chip, no_compile_cache):
    """The epoch program of `twotower_glocal2018.retrain_pairs` at its real
    size, fused kernel and row update: the packed tables are arguments and
    aliased results, the scan holds no copy of one, and the program's own
    memory is a few batches'."""
    from predictionio_tpu.ops import twotower as tt

    n_pad = -(-PAIRS // BATCH) * BATCH
    steps = n_pad // BATCH
    epoch, init_state, _ = tt._epoch_program(
        None, "data", "model", BATCH, DIM, n_pad, steps, 0.05, 10.0, "bfloat16",
        "pallas", "rows")

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip), tree)

    tables = {"user": jax.ShapeDtypeStruct((USERS, DIM), jnp.float32),
              "item": jax.ShapeDtypeStruct((ITEMS, DIM), jnp.float32)}
    p, o = on_chip(jax.eval_shape(init_state, tables))
    width = tt.state_width(DIM)
    assert p["user"].shape == (USERS, width) and width == 256
    ids = jax.ShapeDtypeStruct((n_pad,), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = epoch.lower(p, o, scalar, ids, ids, key).compile()

    memory = compiled.memory_analysis()
    state_bytes = (USERS + ITEMS) * width * 4
    assert memory.argument_size_in_bytes >= state_bytes
    assert memory.alias_size_in_bytes >= state_bytes  # updated in place
    assert memory.temp_size_in_bytes < 0.5e9  # no second table anywhere
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < HBM

    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the fused kernel is in the program
    table = re.compile(rf"f32\[({USERS}|{ITEMS}),\d+\]")
    ops = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if m and table.match(m.group(1).split("{")[0]):
            ops[m.group(2)] = ops.get(m.group(2), 0) + 1
    # what yields a table-sized buffer: the arguments and the loop's carry,
    # and per table one in-place scatter (inside its fusion). No copy, no
    # broadcast or pad (a zero-filled gradient), no transpose.
    assert set(ops) <= {"parameter", "get-tuple-element", "scatter", "fusion",
                        "while", "tuple", "bitcast"}, ops
    assert ops.get("scatter") == 2, ops


@pytest.mark.parametrize(
    "chunk,width,rank",
    [
        (32768, 32, 64),  # als_ml20m.retrain: the sweep's full chunk
        (32768, 32, 16),  # the templates' default rank 10, embedded
        (4096, 32, 128),  # the largest rank the kernel takes
    ],
)
def test_the_als_solve_lowers_through_mosaic_and_fits(
    one_chip, no_compile_cache, chunk, width, rank
):
    """A chunk's normal equations built and solved as the sweep does it
    (`_partials` then `_finish_solve(.., "pallas")`): the lane-batched
    Cholesky kernel lowers through Mosaic at this rank and fits its
    scoped VMEM (a refusal raises here), and it reads the Gramian as the
    product's fusion wrote it: no transposed or padded copy of it."""
    from predictionio_tpu.ops import als, solve

    def solve_chunk(Q, val, mask):
        A, b, n = als._partials(Q, val, mask, False, 1.0, jax.lax.Precision.HIGHEST)
        return als._finish_solve(A, b, n, 0.05, None, "pallas")

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = jax.jit(solve_chunk).lower(
        arg(chunk, width, rank), arg(chunk, width), arg(chunk, width)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and solve.SOLVE_KERNEL in text
    # arrays of the Gramian's size: the product's fusions make it, and
    # nothing copies, pads or transposes it on its way to the kernel
    made: dict = {}
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]+)\]\S* ([\w\-]+)\(", line)
        if m and math.prod(int(d) for d in m.group(1).split(",")) == chunk * rank * rank:
            made[m.group(2)] = made.get(m.group(2), 0) + 1
    assert not {"copy", "transpose", "pad", "concatenate"} & set(made), made
    # temporaries: the Gramian as XLA lays it (rows of K floats padded to
    # 128 lanes) and the gathered rows relaid for the product, plus small
    # change
    lanes = max(rank, 128)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes <= (
        chunk * rank * lanes * 4 + chunk * width * lanes * 4 + (64 << 20)
    ), memory.temp_size_in_bytes


def test_the_implicit_sweeps_shared_gramian_is_blocks_summed_pairwise_and_fits(
    one_chip, no_compile_cache
):
    """The implicit objective's ``X^T X`` over a whole table, at the Taste
    Profile's user side (benchmark/configs/als_tasteprofile.json's source:
    1,019,318 rows and the sentinel): a Gramian a block of 1,024 rows, then
    ten halvings, never one accumulator over a million rows; its
    temporaries are one padded copy of the table and the blocks' Gramians."""
    from predictionio_tpu.ops import als

    rows, rank = 1_019_318 + 1, 64
    compiled = jax.jit(als._gram_all_rows, static_argnums=(1, 2, 3)).lower(
        jax.ShapeDtypeStruct((rows, rank), jnp.float32, sharding=one_chip),
        jax.lax.Precision.HIGHEST, None, None,
    ).compile()
    text = compiled.as_text()
    blocks = 1024
    assert f"f32[{blocks},{als._YTY_BLOCK_ROWS},{rank}]" in text  # the table in blocks
    assert f"f32[{blocks},{rank},{rank}]" in text  # a Gramian a block
    for half in (512, 64, 8, 1):  # the halvings, down to one
        assert f"f32[{half},2,{rank},{rank}]" in text, half
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == rank * 128 * 4  # [64, 64], rows padded to the lanes
    padded = blocks * als._YTY_BLOCK_ROWS * rank * 4
    assert memory.temp_size_in_bytes <= padded + blocks * rank * 128 * 4 + (16 << 20)


def test_the_shared_gramian_over_a_model_axis_sums_each_shard_pairwise(
    four_chips, no_compile_cache
):
    """The same Gramian with the table's rows sharded over the model axis of
    a 2 x 2 mesh of v5e chips (Explicit axes, as ``mesh_context`` makes
    them): under ``shard_map`` each chip sums the blocks of its own half
    pairwise, and one all-reduce of a [64, 64] array joins the halves; no
    chip holds the other's rows."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from predictionio_tpu.ops import als

    mesh = Mesh(np.array(four_chips).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Explicit,) * 2)
    rows, rank = 1_019_320, 64  # train_als pads the table to the model axis
    compiled = jax.jit(als._gram_all_rows, static_argnums=(1, 2, 3)).lower(
        jax.ShapeDtypeStruct((rows, rank), jnp.float32,
                             sharding=NamedSharding(mesh, PartitionSpec("model", None))),
        jax.lax.Precision.HIGHEST, mesh, "model",
    ).compile()
    text = compiled.as_text()
    blocks = 512  # 509,660 rows a chip
    assert f"f32[{blocks},{als._YTY_BLOCK_ROWS},{rank}]" in text
    assert f"f32[{blocks // 2},2,{rank},{rank}]" in text
    assert f"f32[{2 * blocks},{als._YTY_BLOCK_ROWS},{rank}]" not in text
    reduces = [line for line in text.splitlines() if re.search(r"= \S+ all-reduce", line)]
    assert len(reduces) == 1 and f"f32[{rank},{rank}]" in reduces[0], reduces
    assert "all-gather" not in text and "collective-permute" not in text


@pytest.mark.parametrize("pairs", [128, 1024])
def test_the_filtered_top_k_holds_no_array_of_the_excluded_ids(
    one_chip, no_compile_cache, pairs
):
    """`top_k_items_filtered` at the shape of `ecom_amazon2018` and
    `simprod_amazon2018` (32 rows, 30 tiles of 2^19 items, rank 64, k 16, two
    wanted categories) at its pair bucket's floor and at 1,024 (two chunks
    of `ops.topk._DROP_CHUNK`): no `[tiles, rows, width]` array exists for
    the ids left out, one tile's scores are never copied or relaid flat to
    strike a pair from them, and what the program holds beside its
    arguments is little more than one tile's scores."""
    from predictionio_tpu.ops.als import FILTER_PAIR_FLOOR, FILTER_TILE, top_k_items_filtered

    assert FILTER_PAIR_FLOOR == 128
    rows, tiles, width, rank = 32, 30, FILTER_TILE, 64

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = top_k_items_filtered.lower(
        arg((rows, rank), jnp.float32), arg((tiles, rank, width), jnp.float32),
        arg((tiles, 1, width), jnp.int32), arg((tiles, width), jnp.bool_),
        arg((rows, 2), jnp.int32), arg((tiles, pairs), jnp.int32),
        arg((tiles, pairs), jnp.int32), k=16).compile()
    text = compiled.as_text()
    assert not re.search(rf"\[{tiles},{rows},{width}\]", text)
    score_tile = rf"f32\[{rows},{width}\]"
    assert re.search(score_tile, text)  # the tile's scores are there ...
    assert not re.search(rf"{score_tile}\S* copy\(", text)  # ... never copied
    assert not re.search(rf"f32\[{rows * width}\]", text)  # ... nor relaid flat
    assert re.search(rf"{score_tile}\S* scatter\(", text)  # struck in place
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * rows * width * 4
