"""Two-tower retrieval, TPU-native (the DLRM/two-tower stretch family —
BASELINE.md configs[4]; no reference counterpart exists: PredictionIO has
no deep-retrieval template, so this is parity-plus).

TPU-first design:

* **Sharded embedding tables (EP)** — the user and item tables are
  sharded row-wise over the mesh's ``model`` axis. Lookups use the same
  shard-local-gather + psum pattern as the ALS sweep
  (:func:`predictionio_tpu.ops.als._gram_chunk`): under ``shard_map``
  each device gathers only ids living in its local shard (others masked
  to zero) and the partial embeddings psum over ``model`` — the
  catalog-sized tables never replicate, so table size scales with the
  mesh. The pattern is differentiable: the gather's VJP is a
  scatter-add into the LOCAL shard, so gradients stay sharded too.
* **Data-parallel batches** — interaction batches shard over ``data``;
  the in-batch logits matrix psums gradients across the batch via
  GSPMD's normal propagation.
* **In-batch sampled softmax** — each positive (u, i) pair treats the
  other items in the batch as negatives (symmetric u→i and i→u cross
  entropy). Standard two-tower training; duplicate items inside a batch
  act as false negatives, acceptable at the batch sizes used here.
* **Static shapes** — interactions are padded to a multiple of the
  batch size and each step ``dynamic_slice``s its batch from the
  device-resident permutation, so one compiled step serves the whole
  run.
* **Adam on the rows a step gathered** — on one device the step reads
  and writes ``p``, ``m`` and ``v`` of the distinct rows of its batch and
  of no other (:func:`adam_rows`): O(batch x dim) bytes a step whatever
  the tables hold. A row outside the batch keeps ``p``, ``m`` and ``v``
  as they are, which is what TensorFlow's LazyAdam and PyTorch's
  SparseAdam do; dense Adam (``optax.adam`` over the whole tables, what a
  mesh still runs here) goes on moving such a row by its decaying ``m``.
  The two agree where every row is in every batch. Which of the two a
  train took is in ``info["optimizer"]`` with its why.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import zlib
from types import MappingProxyType
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec, reshard

from predictionio_tpu.utils.spans import CompileLedger, count, span

logger = logging.getLogger(__name__)

__all__ = [
    "TwoTowerConfig",
    "TwoTowerModel",
    "adam_rows",
    "pack_rows",
    "unpack_rows",
    "sharded_embedding_lookup",
    "train_two_tower",
]

#: optax.adam's defaults, which the row update shares with the dense one
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
#: the losses of the first steps kept in ``info`` (a reference replays them)
FIRST_LOSSES = 16


@dataclasses.dataclass(frozen=True)
class TwoTowerConfig:
    dim: int = 32
    batch_size: int = 256
    epochs: int = 5
    learning_rate: float = 0.05
    temperature: float = 0.1
    seed: int = 0
    #: keep a loss-history entry every N steps (losses are computed every
    #: step on device and read back once per epoch)
    log_every: int = 50
    #: matmul input dtype for the in-batch logits ("bfloat16" rides the
    #: MXU at full rate with fp32 accumulation — the TPU-native default;
    #: "float32" for bit-for-bit comparisons)
    gemm_dtype: str = "bfloat16"
    #: the flash-style fused softmax-CE kernel (ops/fused_ce.py): "auto"
    #: uses it on single-device TPU runs with supported shapes, "off"
    #: forces the XLA path, "interpret" runs the kernel in interpreter
    #: mode (CPU tests). The [B, B] logits never touch HBM with it on.
    fused_ce: str = "auto"


class TwoTowerModel(NamedTuple):
    """Serving-ready tower outputs: dot(user_vec, item_vec) ranks items.
    Rows are L2-normalized, so scores are cosine similarities."""

    user_vecs: Any  # [U, D]
    item_vecs: Any  # [I, D]
    loss_history: tuple  # ((step, loss), ...)
    #: phase wall-clock: ingest (interaction upload), train (epoch loop),
    #: finalize (replicate + host readback) — kept apart so the transfers
    #: are not booked against the training loop. Immutable default: a
    #: shared mutable {} would alias across default-built instances.
    timings: Any = MappingProxyType({})


def sharded_embedding_lookup(
    table: jax.Array,  # [N_pad, D], sharded over model axis rows
    ids: jax.Array,  # [B] int32
    mesh: Mesh | None,
    data_axis: str | None = "data",
    model_axis: str | None = "model",
) -> jax.Array:
    """Differentiable embedding lookup from a model-sharded table.

    Each device gathers only the rows of its local shard (out-of-shard
    ids contribute zero) and the partials psum over ``model`` — the
    table never materializes replicated, and the VJP scatter-adds into
    the local shard so gradients stay sharded (VERDICT r2 item 10: the
    sharded-embedding consumer of the ALS chunked-gather machinery)."""
    if mesh is None or model_axis is None or model_axis not in mesh.shape:
        return table[ids]
    S = int(mesh.shape[model_axis])
    if table.shape[0] % S:
        # a floored rps would make trailing rows unreachable and return
        # silently-zero embeddings for their ids
        raise ValueError(
            f"table rows ({table.shape[0]}) must divide the model axis ({S})"
        )
    rps = table.shape[0] // S

    def local(tbl, ids_l):
        me = jax.lax.axis_index(model_axis)
        lidx = ids_l - me * rps
        inr = (lidx >= 0) & (lidx < rps)
        e = tbl[jnp.where(inr, lidx, 0)] * inr[:, None].astype(tbl.dtype)
        return jax.lax.psum(e, model_axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(PartitionSpec(model_axis, None), PartitionSpec(data_axis)),
        out_specs=PartitionSpec(data_axis, None),
    )(table, ids)


def _ce_path(
    mesh: Mesh | None, gemm_dtype_name: str, fused_ce_mode: str,
    B: int, D: int, inv_temp: float,
) -> tuple[str, str]:
    """Which cross-entropy the step runs — ``("pallas" | "interpret" |
    "xla", why)``. The fused kernel (ops/fused_ce.py) is single-device
    (in-batch negatives are global, so the mesh path stays XLA), its
    GEMMs are bf16, it needs a TPU to lower through Mosaic, and it takes
    only the shapes ``fused_ce_supported`` admits. The caller logs the
    outcome: nothing here is chosen silently."""
    from predictionio_tpu.ops.fused_ce import fused_ce_supported

    if fused_ce_mode == "off":
        return "xla", "fusedCe off"
    if mesh is not None:
        return "xla", "mesh training keeps the XLA cross-entropy"
    if gemm_dtype_name != "bfloat16":
        return "xla", f"gemmDtype {gemm_dtype_name} (the kernel's GEMMs are bf16)"
    if not fused_ce_supported(B, D, inv_temp):
        return "xla", (
            f"shape/temperature outside the kernel's range "
            f"(batch {B}, dim {D}, 1/temperature {inv_temp:g})"
        )
    if fused_ce_mode == "interpret":
        return "interpret", "fusedCe interpret"
    # strict platform check: anything but a TPU (cpu, gpu, ...) must take
    # the XLA path rather than attempt a Mosaic lowering
    if jax.devices()[0].platform != "tpu":
        return "xla", f"platform {jax.devices()[0].platform} has no Mosaic"
    return "pallas", "single-device TPU"


def _optimizer_path(mesh: Mesh | None) -> tuple[str, str]:
    """Which Adam the step runs — ``("rows" | "dense", why)``, recorded
    beside :func:`_ce_path`'s decision. One device always updates rows
    (:func:`adam_rows`); a mesh keeps ``optax.adam`` over its sharded
    tables until the row update is sharded too."""
    if mesh is not None:
        return "dense", "mesh training keeps optax.adam over the sharded tables"
    return "rows", "single device: Adam on the rows the batch gathered"


def state_width(dim: int) -> int:
    """Columns of a packed row: ``p | m | v`` and zeros up to a whole
    number of 128-lane tiles (256 at dim 64)."""
    return _pad_rows(3 * dim, 128)


def pack_rows(table: jax.Array) -> jax.Array:
    """``[N, D]`` parameters as the row update's state ``[N, W]``: each
    row ``p | m | v | 0...`` with ``m = v = 0``.

    Why one wide array and not three ``[N, D]``: the chip keeps a
    ``f32[N, 64]`` argument column-major (rows of 64 would be padded to
    128 lanes), and a program that gathers rows of it first copies it
    whole into the padded row-major form — at 7.68 M rows three such
    arrays a table are 5.6 GB as arguments and 11.1 GB more as copies, over
    the chip's 15.75 GB (compiled for a v5e: PERF.md, PR 32). A row of 256
    floats is two whole lane tiles: stored row-major as it stands, no
    copy, and one gather and one scatter move ``p``, ``m`` and ``v``."""
    dim = table.shape[1]
    return jnp.pad(table, ((0, 0), (0, state_width(dim) - dim)))


_pack_rows = jax.jit(pack_rows)


def unpack_rows(state: jax.Array, dim: int) -> tuple[jax.Array, ...]:
    """``(p, m, v)`` of a packed state, each ``[N, D]``."""
    return tuple(state[:, k * dim:(k + 1) * dim] for k in range(3))


def _whole_rows(state: jax.Array, ids: jax.Array) -> jax.Array:
    """``state[ids]`` as a gather of whole rows, kept apart from whatever
    slices them: folded into the gather, a column slice turns it into the
    chip's slow windowed form."""
    return jax.lax.optimization_barrier(
        state.at[ids].get(mode="promise_in_bounds"))


def adam_rows(
    state: jax.Array,  # [N, W] packed rows p | m | v (pack_rows)
    ids: jax.Array,  # [B] int32, duplicates allowed
    grads: jax.Array,  # [B, D]: dL/d(p[ids[j]]) for each slot j
    step: jax.Array,  # the global step, from 1
    learning_rate: float,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One Adam step on the distinct rows of ``ids`` and on no other.

    For each distinct id ``r``: ``g_r`` is the sum of the slots' gradients
    that gathered ``r`` (the row of the dense gradient, which is zero
    elsewhere); ``m_r <- b1 m_r + (1 - b1) g_r``; ``v_r <- b2 v_r +
    (1 - b2) g_r^2``; ``p_r <- p_r - lr (m_r / (1 - b1^t)) /
    (sqrt(v_r / (1 - b2^t)) + eps)``, ``b1``, ``b2``, ``eps`` optax's. Every
    other row keeps its ``p``, ``m`` and ``v`` (lazy Adam). Returns the
    state, the number of distinct rows and ``sum_r |g_r|^2``.

    Nothing of the table's size is read, written or filled: the ids are
    sorted (B keys), duplicates summed into the first ``n`` of B slots, the
    ``n`` rows gathered, updated and scattered back; slots past ``n`` point
    beyond the table and are dropped by the scatter."""
    B, D = grads.shape
    n_rows = state.shape[0]
    slot = jnp.arange(B, dtype=jnp.int32)
    xs, order = jax.lax.sort_key_val(ids, slot)
    first = jnp.concatenate([jnp.ones((1,), bool), xs[1:] != xs[:-1]])
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    g = jax.ops.segment_sum(
        grads[order], seg, num_segments=B, indices_are_sorted=True
    )
    # slot k < n holds the k-th distinct id (its duplicates write the same
    # value); slot k >= n holds n_rows + k: sorted, distinct, out of range
    rows = (n_rows + slot).at[seg].set(xs, indices_are_sorted=True)
    old = state.at[rows].get(mode="fill", fill_value=0)
    p_r, m_r, v_r = unpack_rows(old, D)
    t = step.astype(jnp.float32)
    m_r = ADAM_B1 * m_r + (1.0 - ADAM_B1) * g
    v_r = ADAM_B2 * v_r + (1.0 - ADAM_B2) * (g * g)
    m_hat = m_r / (1.0 - ADAM_B1 ** t)
    v_hat = v_r / (1.0 - ADAM_B2 ** t)
    p_r = p_r - learning_rate * m_hat / (jnp.sqrt(v_hat) + ADAM_EPS)
    new = jnp.concatenate([p_r, m_r, v_r, old[:, 3 * D:]], axis=1)
    # no `indices_are_sorted` / `unique_indices` on this scatter, true as
    # both are: with them the chip takes 14.3 ms for 8,192 rows of 1 KB
    # into 4.57 M, without them 0.73 ms (measured, PR 32)
    state = state.at[rows].set(new, mode="drop")
    return state, seg[-1] + 1, jnp.sum(g * g)


@functools.lru_cache(maxsize=16)
def _epoch_program(
    mesh: Mesh | None,
    data_axis: str | None,
    model_axis: str | None,
    B: int,
    D: int,
    n_pad: int,
    steps_per_epoch: int,
    learning_rate: float,
    inv_temp: float,
    gemm_dtype_name: str,
    ce_path: str,
    optimizer: str,
):
    """Build (and cache) the jitted per-epoch training program.

    The program is keyed on everything that shapes its trace, so repeat
    trains in one process — warm retrains, evaluation sweeps, the bench's
    warm-up/timed pair — reuse the SAME jit object instead of re-tracing
    a fresh closure each call (re-tracing the full-epoch scan costs ~1 s
    even with the persistent compile cache hitting). ``ce_path`` is
    :func:`_ce_path`'s decision and ``optimizer`` :func:`_optimizer_path`'s.

    Returns ``(train_epoch, init_state, tables)``. ``init_state(params)``
    gives the carry ``(p, o)``: the tables and optax's state under
    ``dense``, the packed rows (:func:`pack_rows`) and nothing under
    ``rows``. ``train_epoch(p, o, epoch, r, c, perm_key)`` returns ``(p, o,
    losses, touched, grad_sq)``, a row a step: ``touched`` the distinct rows
    it updated, both tables (under ``dense`` every row, every step),
    ``grad_sq`` the squared norm of each table's gradient (user, item),
    duplicates summed. ``tables(p)`` are the ``[N, D]`` parameters of a
    carry."""
    gemm_dtype = jnp.bfloat16 if gemm_dtype_name == "bfloat16" else jnp.float32
    from predictionio_tpu.ops.fused_ce import SCOPE as ce_scope, fused_inbatch_ce

    rep_sharding = (
        None if mesh is None else NamedSharding(mesh, PartitionSpec())
    )
    tx = optax.adam(learning_rate)

    def _logits(a, b):
        # bf16 operands ride the MXU at full rate; accumulation stays
        # fp32 (preferred_element_type), so the softmax sees fp32 logits
        return (
            jnp.matmul(
                a.astype(gemm_dtype),
                b.astype(gemm_dtype).T,
                preferred_element_type=jnp.float32,
            )
            * inv_temp
        )

    def loss_of_rows(ue, ie):
        """The loss from the batch's gathered rows ``[B, D]``."""
        ue = ue / (jnp.linalg.norm(ue, axis=-1, keepdims=True) + 1e-8)
        ie = ie / (jnp.linalg.norm(ie, axis=-1, keepdims=True) + 1e-8)
        if ce_path != "xla":
            # the kernel's two calls carry the scope pio_tt_ce themselves
            return fused_inbatch_ce(ue, ie, inv_temp, ce_path == "interpret")
        labels = jnp.arange(B)
        if mesh is not None:
            # in-batch logits need every negative on every device: keep
            # the LEFT side batch-sharded and replicate the right side (a
            # tiny [B, D] all-gather) — [B@data, B@data] is not a legal
            # layout, and labels must shard like the logits rows
            rep = NamedSharding(mesh, PartitionSpec(None, None))
            ue_r = reshard(ue, rep)
            ie_r = reshard(ie, rep)
            labels = reshard(
                labels, NamedSharding(mesh, PartitionSpec(data_axis))
            )
        else:
            ue_r, ie_r = ue, ie
        # symmetric in-batch softmax: user->item and item->user
        with jax.named_scope(ce_scope):
            l1 = optax.softmax_cross_entropy_with_integer_labels(
                _logits(ue, ie_r), labels
            )
            l2 = optax.softmax_cross_entropy_with_integer_labels(
                _logits(ie, ue_r), labels
            )
            return 0.5 * (l1.mean() + l2.mean())

    def loss_fn(p, u_ids, i_ids):
        with jax.named_scope("pio_tt_gather"):
            ue = sharded_embedding_lookup(
                p["user"], u_ids, mesh, data_axis, model_axis)
            ie = sharded_embedding_lookup(
                p["item"], i_ids, mesh, data_axis, model_axis)
        return loss_of_rows(ue, ie)

    def dense_step(p, o, u_ids, i_ids, step):
        loss, grads = jax.value_and_grad(loss_fn)(p, u_ids, i_ids)
        with jax.named_scope("pio_tt_update"):
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
        grad_sq = jnp.stack([jnp.sum(grads[k] ** 2) for k in ("user", "item")])
        rows = jnp.int32(p["user"].shape[0] + p["item"].shape[0])
        return p, o, loss, rows, grad_sq

    def rows_step(p, o, u_ids, i_ids, step):
        with jax.named_scope("pio_tt_gather"):
            # whole packed rows, then the slice: `table[ids, :D]` is one
            # gather of [1, D] windows at two-part indices, which the
            # chip runs as a loop of 8,192 dynamic slices (10 ms a table,
            # measured, PR 32); whole rows are one vectorised gather
            ue = unpack_rows(_whole_rows(p["user"], u_ids), D)[0]
            ie = unpack_rows(_whole_rows(p["item"], i_ids), D)[0]
        loss, grads = jax.value_and_grad(loss_of_rows, argnums=(0, 1))(ue, ie)
        with jax.named_scope("pio_tt_update"):
            user, n_u, sq_u = adam_rows(
                p["user"], u_ids, grads[0], step + 1, learning_rate)
            item, n_i, sq_i = adam_rows(
                p["item"], i_ids, grads[1], step + 1, learning_rate)
        return ({"user": user, "item": item}, o, loss, n_u + n_i,
                jnp.stack([sq_u, sq_i]))

    one_step = rows_step if optimizer == "rows" else dense_step

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_epoch(p, o, epoch, r, c, perm_key):
        """ONE device program per epoch: permutation gather + a lax.scan
        over every step, so the host dispatches once per epoch instead of
        once per step. Returns per-step losses (read back once per
        epoch).

        Fresh permutation per epoch: in-batch softmax draws its negatives
        from the batch, so replaying one fixed batching would freeze
        every positive's negative set for the whole run."""
        perm = jax.random.permutation(jax.random.fold_in(perm_key, epoch), n_pad)
        r_all, c_all = r[perm], c[perm]
        if rep_sharding is not None:
            r_all = reshard(r_all, rep_sharding)
            c_all = reshard(c_all, rep_sharding)

        def body(carry, step):
            p, o = carry
            off = step * B
            u_ids = jax.lax.dynamic_slice(r_all, (off,), (B,))
            i_ids = jax.lax.dynamic_slice(c_all, (off,), (B,))
            if mesh is not None:
                # reshard, not with_sharding_constraint: make_mesh axes
                # are Explicit in current jax, and the batch must be
                # data-sharded before entering the shard_map lookups
                bspec = NamedSharding(mesh, PartitionSpec(data_axis))
                u_ids = reshard(u_ids, bspec)
                i_ids = reshard(i_ids, bspec)
            p, o, loss, touched, grad_sq = one_step(
                p, o, u_ids, i_ids, epoch * steps_per_epoch + step
            )
            return (p, o), (loss, touched, grad_sq)

        (p, o), per_step = jax.lax.scan(
            body, (p, o), jnp.arange(steps_per_epoch, dtype=jnp.int32)
        )
        return (p, o, *per_step)

    def init_state(params):
        if optimizer == "dense":
            return params, tx.init(params)
        return {k: _pack_rows(t) for k, t in params.items()}, ()

    def tables(p):
        if optimizer == "dense":
            return p
        return {k: s[:, :D] for k, s in p.items()}

    return train_epoch, init_state, tables


def _pad_rows(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def train_two_tower(
    rows: np.ndarray,
    cols: np.ndarray,
    num_users: int,
    num_items: int,
    config: TwoTowerConfig = TwoTowerConfig(),
    mesh: Mesh | None = None,
    data_axis: str = "data",
    model_axis: str = "model",
    init_user: np.ndarray | None = None,
    init_item: np.ndarray | None = None,
    info: dict | None = None,
) -> TwoTowerModel:
    """Train user/item towers from implicit interaction pairs.

    ``info``, when given, receives the kernel decisions this train took
    (backend, cross-entropy path and why, optimizer and why, GEMM dtype,
    batch, dim, mesh) and what it measured: ``initSeconds`` (span
    ``train.init``: seeding the tables and padding the pairs before the
    upload, packing the tables after it), ``epochSeconds`` (one dispatch
    and one sync an epoch; the first carries the compile), ``stepMs`` (the
    median epoch but the first over its steps), ``firstLosses`` and
    ``firstGradNorms`` (of the first steps: each table's gradient norm,
    duplicates summed: user, item), ``lastLosses``,
    ``rowsTouched`` / ``rowsTouchedPerStep`` (distinct rows updated, both
    tables, counted on the device), ``pairsChecksum`` (CRC-32 of the id
    arrays as uploaded: another order of pairs is told from other
    mathematics by it), ``epochProgram`` (the compile ledger's entry) and
    ``epochProgramSeconds`` (its trace + lower + load).

    ``rows[i]``/``cols[i]`` is one (user, item) interaction. Returns
    L2-normalized tower vectors as replicated host-readable arrays.
    ``init_user``/``init_item`` ([num_users, D] / [num_items, D]) seed
    the embedding tables (warm retrain carry-over); rows beyond them
    (shard padding) keep the random draw.
    """
    rows = np.asarray(rows, dtype=np.int64)
    cols = np.asarray(cols, dtype=np.int64)
    if rows.shape != cols.shape or rows.ndim != 1:
        raise ValueError("rows/cols must be equal-length 1-D arrays")
    if config.fused_ce not in ("auto", "off", "interpret"):
        raise ValueError(
            "TwoTowerConfig.fused_ce must be 'auto', 'off' or 'interpret', "
            f"got {config.fused_ce!r}"
        )
    if rows.size == 0:
        raise ValueError("two-tower training needs at least one interaction")
    if rows.min() < 0 or rows.max() >= num_users:
        raise ValueError("row index out of range")
    if cols.min() < 0 or cols.max() >= num_items:
        raise ValueError("column index out of range")

    S = 1
    if mesh is not None and model_axis in mesh.shape:
        S = int(mesh.shape[model_axis])
    elif mesh is not None:
        model_axis = None
    D = config.dim
    n_u = _pad_rows(num_users, S)
    n_i = _pad_rows(num_items, S)

    B = config.batch_size
    if mesh is not None:
        d_size = int(mesh.shape.get(data_axis, 1))
        B = _pad_rows(B, d_size)

    # seeding the two tables and padding the pairs: a handful of eager
    # programs, each traced and loaded on its first call, and host numpy.
    # Closed by a sync, like every phase here, so the next is not charged
    # for it
    seeding = span("train.init").start()
    key = jax.random.PRNGKey(config.seed)
    k_u, k_i, k_perm = jax.random.split(key, 3)
    scale = 1.0 / np.sqrt(D)

    def _draw(k, n_real, n_padded):
        # draw at the canonical (n_real, D) shape and zero-pad the shard
        # rows — the jax PRNG keys its stream on the SHAPE, so drawing at
        # the padded shape would give a mesh whose model axis does not
        # divide the catalog a different init (hence a different trained
        # model) than single-device. Same rule as the ALS factor tables.
        base = jax.random.normal(k, (n_real, D), jnp.float32) * scale
        return jnp.pad(base, ((0, n_padded - n_real), (0, 0)))

    params = {
        "user": _draw(k_u, num_users, n_u),
        "item": _draw(k_i, num_items, n_i),
    }
    for name, init, n_real in (
        ("user", init_user, num_users), ("item", init_item, num_items)
    ):
        if init is None:
            continue
        init = np.asarray(init, np.float32)
        if init.shape != (n_real, D):
            raise ValueError(
                f"init_{name} must have shape {(n_real, D)}, got {init.shape}"
            )
        base = np.array(params[name])  # copy: asarray of a jax array is read-only
        base[:n_real] = init
        params[name] = jnp.asarray(base)
    if mesh is not None:
        spec = (
            PartitionSpec(model_axis, None)
            if model_axis
            else PartitionSpec(None, None)
        )
        sharded = NamedSharding(mesh, spec)
        params = {k: jax.device_put(v, sharded) for k, v in params.items()}

    # pad interactions to a whole number of batches by resampling real
    # pairs (padding with a sentinel would inject a fake item)
    nnz = rows.size
    n_pad = _pad_rows(nnz, B)
    reps = np.arange(n_pad) % nnz
    rep_sharding = None if mesh is None else NamedSharding(mesh, PartitionSpec())

    # upload the padded interaction set ONCE; every epoch's shuffle is a
    # device-side permutation gather (the previous per-epoch host
    # permutation + re-upload was a full-dataset transfer stall per epoch
    # — VERDICT r3 weak #6)
    r_host = rows[reps].astype(np.int32)
    c_host = cols[reps].astype(np.int32)
    pairs_checksum = zlib.crc32(c_host, zlib.crc32(r_host))
    jax.block_until_ready(params)
    seeding.stop()
    with span("train.ingest") as ingest:
        r_base = jnp.asarray(r_host)
        c_base = jnp.asarray(c_host)
        if rep_sharding is not None:
            r_base = jax.device_put(r_base, rep_sharding)
            c_base = jax.device_put(c_base, rep_sharding)
        int(c_base[-1])  # hard sync: the upload is complete, not just enqueued
    del r_host, c_host

    steps_per_epoch = n_pad // B
    inv_temp = 1.0 / config.temperature
    # the step's two decisions (the first imports the kernel's module: a
    # second on the chip's host), then packing: the tables into the layout
    # the epoch program updates in place, the optimizer's state beside
    # them; the same phase as the seeding, on the other side of the upload
    with span("train.init") as packing:
        ce_path, ce_why = _ce_path(
            mesh, config.gemm_dtype, config.fused_ce, B, D, inv_temp
        )
        optimizer, optimizer_why = _optimizer_path(mesh)
        decisions = {
            "backend": jax.default_backend(),
            "fusedCe": ce_path,
            "fusedCeWhy": ce_why,
            "optimizer": optimizer,
            "optimizerWhy": optimizer_why,
            "gemmDtype": config.gemm_dtype,
            "batch": B,
            "dim": D,
            "mesh": None if mesh is None else dict(mesh.shape),
        }
        logger.info("two-tower kernel decisions: %s", decisions)
        info = {} if info is None else info
        info.update(decisions)
        train_epoch, init_state, tables = _epoch_program(
            mesh, data_axis, model_axis, B, D, n_pad, steps_per_epoch,
            config.learning_rate, inv_temp, config.gemm_dtype, ce_path,
            optimizer,
        )
        params, opt_state = init_state(params)
        jax.block_until_ready((params, opt_state))
    info["initSeconds"] = round(seeding.seconds + packing.seconds, 3)
    ledger = CompileLedger.install()
    compiled_before = ledger.snapshot()

    history = []
    total_steps = config.epochs * steps_per_epoch
    epoch_seconds = []  # the first carries the compile
    rows_touched = 0
    for epoch in range(config.epochs):
        with span("train.epoch") as one_epoch:
            params, opt_state, losses, touched, grad_sq = train_epoch(
                params, opt_state, jnp.int32(epoch), r_base, c_base, k_perm
            )
            losses_np = np.asarray(losses)  # one readback per epoch
        epoch_seconds.append(round(one_epoch.seconds, 3))
        rows_touched += int(np.asarray(touched, np.int64).sum())
        if epoch == 0:
            info["firstLosses"] = [float(x) for x in losses_np[:FIRST_LOSSES]]
            info["firstGradNorms"] = np.sqrt(
                np.asarray(grad_sq[:FIRST_LOSSES], np.float64)).tolist()
        for i, loss in enumerate(losses_np):
            step = epoch * steps_per_epoch + i
            if step % config.log_every == 0 or step == total_steps - 1:
                history.append((step, float(loss)))
    info["epochSeconds"] = epoch_seconds
    info["stepsPerEpoch"] = steps_per_epoch
    info["stepMs"] = round(
        1e3 * float(np.median(epoch_seconds[1:] or epoch_seconds))
        / steps_per_epoch, 4)
    info["lastLosses"] = [float(x) for x in losses_np[-FIRST_LOSSES:]]
    info["rowsTouched"] = rows_touched
    count("rowsTouched", rows_touched)
    info["rowsTouchedPerStep"] = round(rows_touched / total_steps, 2)
    info["pairsChecksum"] = pairs_checksum
    entry = ledger.table(since=compiled_before).get("train_epoch", {})
    info["epochProgram"] = entry
    info["epochProgramSeconds"] = round(sum(
        entry.get(k, 0.0) for k in ("traceSeconds", "lowerSeconds", "loadSeconds")
    ), 3)
    # dense Adam's state is the tables' size twice over: gone before the
    # normalised copies are made
    del opt_state

    def _finalize(p):
        with jax.named_scope("pio_tt_finalize"):
            p = tables(p)
            u = p["user"] / (
                jnp.linalg.norm(p["user"], axis=-1, keepdims=True) + 1e-8)
            v = p["item"] / (
                jnp.linalg.norm(p["item"], axis=-1, keepdims=True) + 1e-8)
        return u, v

    finalize = span("train.finalize").start()
    if mesh is not None and jax.process_count() > 1:
        # multi-host: replicate before the host reads the (possibly
        # model-sharded) tables; slicing off padding happens host-side
        u, v = jax.jit(
            _finalize, out_shardings=NamedSharding(mesh, PartitionSpec())
        )(params)
    else:
        # single host: keep the tables in their (possibly model-sharded)
        # layout and let np.asarray assemble per-device shards on HOST —
        # forcing replication here materialized the full tables on every
        # device at the finish line, the lone O(catalog)-per-device step
        # of an otherwise O(catalog / model_axis) training run
        u, v = jax.jit(_finalize)(params)
    user_vecs = np.asarray(u)[:num_users]
    item_vecs = np.asarray(v)[:num_items]
    finalize.stop()
    timings = {
        "ingest_seconds": round(ingest.seconds, 4),
        "train_seconds": round(sum(epoch_seconds), 4),
        "finalize_seconds": round(finalize.seconds, 4),
    }
    info["ingestSeconds"] = timings["ingest_seconds"]
    info["finalizeSeconds"] = timings["finalize_seconds"]
    return TwoTowerModel(
        user_vecs=user_vecs,
        item_vecs=item_vecs,
        loss_history=tuple(history),
        timings=timings,
    )
