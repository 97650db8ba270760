"""Streaming mini-batch trainer — the two-tower half of online learning.

Matrix-factorization rows admit a closed-form fold-in
(:mod:`~predictionio_tpu.online.foldin`); embedding towers do not, so
their online path is the streaming analog of training: small SGD steps
on the fresh (user, item) pairs with in-batch sampled-softmax — the
same objective ``ops.twotower`` trains with — touching ONLY the rows
the batch names. A :class:`StreamingTrainer` runs in its own background
daemon thread consuming the runner's delta stream from a bounded queue
(a burst drops oldest batches rather than stalling the follower), and
pushes each step's updated rows through the same
``apply_online_update`` hot-swap path the fold-in side uses.

The jitted step computes gradients w.r.t. the GATHERED rows only (the
rest of the tables are fixed for the step), so its cost scales with the
mini-batch, not the catalog; per-id gradient accumulation and the SGD
update run host-side on the handful of touched rows. Rows re-normalize
after each step — the serving contract is L2-normalized towers.
"""

from __future__ import annotations

import functools
import logging
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.online.types import OnlineUpdate
from predictionio_tpu.parallel.sharding import take_rows

__all__ = ["StreamingTrainer", "sgd_step"]

logger = logging.getLogger(__name__)

#: padded mini-batch bucket floor (one compiled program per bucket)
_MIN_BUCKET = 8


def _bucket(n: int) -> int:
    return max(_MIN_BUCKET, 1 << (max(1, n) - 1).bit_length())


@jax.jit
def _grad_kernel(ue, ie, mask, inv_temp):
    """Masked symmetric in-batch softmax-CE over gathered rows
    ``[B, D]``; returns (loss, grad_ue, grad_ie). Padding rows (mask 0)
    contribute no loss and are excluded from every negative set."""

    def loss_fn(u_raw, i_raw):
        un = u_raw / (jnp.linalg.norm(u_raw, axis=-1, keepdims=True) + 1e-8)
        inn = i_raw / (jnp.linalg.norm(i_raw, axis=-1, keepdims=True) + 1e-8)
        real = mask > 0
        n_real = jnp.maximum(mask.sum(), 1.0)
        B = u_raw.shape[0]
        labels = jnp.arange(B)
        # padding columns leave every negative set; the diagonal stays
        # unmasked so a padding ROW's own label is finite (its loss is
        # then select-dropped — a -inf diagonal would make it +inf and
        # poison the mean with inf*0)
        allow = real[None, :] | jnp.eye(B, dtype=bool)

        def ce(a, b):
            logits = (a @ b.T) * inv_temp
            logits = jnp.where(allow, logits, -jnp.inf)
            logp = jax.nn.log_softmax(logits, axis=1)
            return -logp[labels, labels]

        per = jnp.where(real, 0.5 * (ce(un, inn) + ce(inn, un)), 0.0)
        return per.sum() / n_real

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(ue, ie)
    return loss, grads[0], grads[1]


def sgd_step(
    user_vecs,
    item_vecs,
    u_idx: np.ndarray,
    i_idx: np.ndarray,
    lr: float,
    temperature: float,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray, float]:
    """One streaming step over pairs ``(u_idx[j], i_idx[j])``.

    Gathers the touched rows (device gather when the tables are pinned),
    runs the jitted masked-CE gradient kernel, accumulates per-id
    gradients host-side (one id may appear in several pairs), applies
    SGD, and re-normalizes. Returns ``(unique user rows idx, updated
    rows, unique item rows idx, updated rows, loss)``."""
    B = len(u_idx)
    Bp = _bucket(B)
    up = np.zeros(Bp, np.int64)
    ip = np.zeros(Bp, np.int64)
    up[:B] = u_idx
    ip[:B] = i_idx
    mask = np.zeros(Bp, np.float32)
    mask[:B] = 1.0
    ue = np.asarray(take_rows(user_vecs, up), np.float32)
    ie = np.asarray(take_rows(item_vecs, ip), np.float32)
    loss, gu, gi = _grad_kernel(
        jnp.asarray(ue), jnp.asarray(ie), jnp.asarray(mask),
        jnp.float32(1.0 / max(temperature, 1e-6)),
    )
    gu = np.asarray(gu)[:B]
    gi = np.asarray(gi)[:B]

    def fold(idx: np.ndarray, rows: np.ndarray, grad: np.ndarray):
        uniq, inv = np.unique(idx, return_inverse=True)
        acc = np.zeros((uniq.size, rows.shape[1]), np.float32)
        np.add.at(acc, inv, grad)
        first = np.zeros(uniq.size, np.int64)
        first[inv[::-1]] = np.arange(idx.size - 1, -1, -1)
        new = rows[first] - lr * acc
        new /= np.linalg.norm(new, axis=1, keepdims=True) + 1e-8
        return uniq, new

    uu, new_u = fold(up[:B], ue[:B], gu)
    ui, new_i = fold(ip[:B], ie[:B], gi)
    return uu, new_u, ui, new_i, float(loss)


class StreamingTrainer:
    """Background daemon consuming delta pair batches for ONE deployed
    two-tower pair. The runner enqueues ``(pairs, new_users, new_items)``
    work items; the thread turns each into one or more SGD steps and
    hands the updated rows to ``apply`` (the runner's hot-swap bridge
    into ``QueryService.apply_online_update``)."""

    def __init__(
        self,
        model,
        apply,
        batch_size: int = 256,
        lr: float = 0.05,
        temperature: float = 0.1,
        seed: int = 0,
        queue_size: int = 64,
    ):
        self._model = model
        self._apply = apply
        self._batch = max(1, int(batch_size))
        self._lr = float(lr)
        self._temp = float(temperature)
        self._rng = np.random.default_rng(seed)
        self._queue: "queue.Queue" = queue.Queue(maxsize=queue_size)
        self._lock = threading.Lock()
        self.steps = 0
        self.pairs_trained = 0
        self.dropped_batches = 0
        self.last_loss: float | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="pio-online-trainer"
        )
        self._thread.start()

    # --------------------------------------------------------------- intake
    def submit(
        self, pairs: list[tuple[str, str]], newest_us: int = 0
    ) -> None:
        """Enqueue fresh (user id, item id) pairs; drops the OLDEST
        queued batch on overflow so a burst degrades to sampling recent
        data instead of stalling the follower thread. ``newest_us``
        (the batch's newest event time) rides along so the runner can
        measure event->serving-visible freshness when the async apply
        lands."""
        if not pairs:
            return
        while True:
            try:
                self._queue.put_nowait((pairs, newest_us))
                return
            except queue.Full:
                try:
                    self._queue.get_nowait()
                    with self._lock:
                        self.dropped_batches += 1
                except queue.Empty:
                    continue

    def stop(self) -> None:
        self._stop.set()
        self._queue.put(None)  # wake the consumer
        self._thread.join(timeout=5.0)

    # ----------------------------------------------------------------- loop
    def _cold_rows(self, n: int, dim: int) -> np.ndarray:
        rows = self._rng.standard_normal((n, dim)).astype(np.float32)
        rows /= np.linalg.norm(rows, axis=1, keepdims=True) + 1e-8
        return rows

    def _run(self) -> None:
        while not self._stop.is_set():
            item = self._queue.get()
            if item is None or self._stop.is_set():
                break
            try:
                self._train_one(*item)
            except Exception:
                logger.exception("streaming trainer step failed; continuing")

    def _train_one(
        self, pairs: list[tuple[str, str]], newest_us: int = 0
    ) -> None:
        model = self._model
        dim = int(np.asarray(model.item_vecs).shape[1]) if len(
            model.item_index
        ) else 0
        # cold-start injection first: unseen entities get a normalized
        # random row so the SGD step (and serving) can address them
        new_users = sorted(
            {u for u, _ in pairs if model.user_index.get(u) is None}
        )
        new_items = sorted(
            {i for _, i in pairs if model.item_index.get(i) is None}
        )
        if new_users or new_items:
            res = self._apply(
                OnlineUpdate(
                    user_ids=new_users,
                    user_rows=self._cold_rows(len(new_users), dim),
                    item_ids=new_items,
                    item_rows=self._cold_rows(len(new_items), dim),
                    seen_pairs=(),
                    info={"coldStart": True, "newestUs": newest_us},
                )
            ) or {}
            if not res.get("applied"):
                # a concurrent /reload superseded the generation this
                # trainer was bound to — the cold rows were NOT injected
                # (model.user_index[u] below would KeyError) and the
                # runner's rebind is about to replace this trainer;
                # abandon the work item instead of crashing on it
                return
        for lo in range(0, len(pairs), self._batch):
            chunk = pairs[lo : lo + self._batch]
            u_idx = np.asarray(
                [model.user_index[u] for u, _ in chunk], np.int64
            )
            i_idx = np.asarray(
                [model.item_index[i] for _, i in chunk], np.int64
            )
            uu, new_u, ui, new_i, loss = sgd_step(
                model.user_vecs, model.item_vecs, u_idx, i_idx,
                self._lr, self._temp,
            )
            inv_u = model.user_index.inverse
            inv_i = model.item_index.inverse
            res = self._apply(
                OnlineUpdate(
                    user_ids=[inv_u(int(r)) for r in uu],
                    user_rows=new_u,
                    item_ids=[inv_i(int(r)) for r in ui],
                    item_rows=new_i,
                    seen_pairs=chunk,
                    info={"loss": round(loss, 5), "newestUs": newest_us},
                )
            ) or {}
            with self._lock:
                self.steps += 1
                self.pairs_trained += len(chunk)
                self.last_loss = loss
            if not res.get("applied") and res.get("reason"):
                # superseded mid-item: later chunks would be dropped too
                return

    def stats_json(self) -> dict:
        with self._lock:
            return {
                "steps": self.steps,
                "pairsTrained": self.pairs_trained,
                "droppedBatches": self.dropped_batches,
                "lastLoss": self.last_loss,
                "queued": self._queue.qsize(),
            }
