"""The plain reference of the trained-embedding retrieval engine, and the
comparison that decides ``correct``. Imports nothing of
``predictionio_tpu/ops``: ``jax.numpy`` on the CPU in float64 for the loss
(two ``[B, B]`` softmaxes, no kernel, no scan), numpy for the optimizer.

The model. Two tables ``U [users, D]`` and ``V [items, D]``. A step ``t``
(global, from 1) takes a batch of pairs ``a[0..B)``, ``b[0..B)``; with
``x_j = U[a_j] / (|U[a_j]| + 1e-8)``, ``y_j = V[b_j] / (|V[b_j]| + 1e-8)`` and
``L = x y^T / temperature`` its loss is

    0.5 * ( mean_j -log softmax(L[j, :])[j]  +  mean_j -log softmax(L[:, j])[j] )

For each table and each *distinct* id ``r`` of the batch, ``g_r`` the sum of
the gradients of the slots that hold ``r``: ``m_r <- b1 m_r + (1 - b1) g_r``;
``v_r <- b2 v_r + (1 - b2) g_r^2``; ``T_r <- T_r - lr (m_r / (1 - b1^t)) /
(sqrt(v_r / (1 - b2^t)) + eps)``, ``b1`` 0.9, ``b2`` 0.999, ``eps`` 1e-8. A row
outside the batch keeps ``T``, ``m`` and ``v`` (lazy Adam).

What is drawn as the program draws it, by the same ``jax.random`` calls on
the CPU: the initial tables (normal, scale ``1 / sqrt(D)``) and each epoch's
permutation of the padded pairs. The program's order of pairs is rebuilt
from the blob's id maps by the data source's rule (distinct pairs sorted by
user row, then item row); the instance's ``pairsChecksum`` tells another
order of pairs from other mathematics.

Two *controls* are replayed beside the reference in the run itself and must
come out over a limit: the loss without its item-to-user half, and a
duplicate id's gradient applied once instead of summed. A third is made of
the stored tables: rounded to bfloat16, the nearest precision below the
float32 the configuration states for them, their rows' norms must leave 1
by more than the limit the float32 rows are held to. (Tables held in
bfloat16 *during* the replay read 6.7e-5 to 1.9e-4 of loss gap by seed,
beside 2.7e-5 to 4.9e-5 of the program's own bf16 operands: no limit on the
first losses separates the two on every seed; PERF.md section 6, PR 32.)
"""

from __future__ import annotations

import threading
import zlib

import numpy as np

from benchmark.references.als import Checks, bf16, load_models

B1, B2, EPS = 0.9, 0.999, 1e-8


def model_seed(seed: int) -> int:
    """The engine's ``seed`` parameter for a run's ``--seed``."""
    return seed % (2**31 - 1)


# ------------------------------------------------------------------ the draws


def _keys(seed: int):
    import jax

    return jax.random.split(jax.random.PRNGKey(seed), 3)  # user, item, permutation


def initial_tables(seed: int, n_users: int, n_items: int, dim: int):
    import jax
    import jax.numpy as jnp

    k_u, k_i, _ = _keys(seed)
    scale = 1.0 / np.sqrt(dim)
    return tuple(
        np.asarray(jax.random.normal(k, (n, dim), jnp.float32) * scale)
        for k, n in ((k_u, n_users), (k_i, n_items)))


def epoch_permutation(seed: int, epoch: int, n_pad: int) -> np.ndarray:
    import jax

    key = jax.random.fold_in(_keys(seed)[2], epoch)
    return np.asarray(jax.random.permutation(key, n_pad))


class Draws(threading.Thread):
    """What a check draws from the seed alone, on a thread of its own so
    that a kind can have it made beside its set-up: ``user0``, ``item0``
    and ``perm`` (the first epoch's permutation of the padded pairs)."""

    def __init__(self, seed: int, shape: dict, model: dict):
        super().__init__(daemon=True)
        self.seed, self.shape, self.model = seed, shape, model
        self.user0 = self.item0 = self.perm = self.error = None

    def run(self) -> None:
        try:
            batch = self.model["batch"]
            self.user0, self.item0 = initial_tables(
                self.seed, self.shape["users"], self.shape["items"], self.model["dim"])
            self.perm = epoch_permutation(
                self.seed, 0, -(-self.shape["pairs"] // batch) * batch)
        except BaseException as e:  # handed to the thread that joins
            self.error = e

    def result(self):
        self.join()
        if self.error is not None:
            raise self.error
        return self.user0, self.item0, self.perm


def padded_pairs(rows: np.ndarray, cols: np.ndarray, batch: int):
    """The id arrays as the program uploads them: whole batches, the tail
    filled with the first pairs again. And their CRC-32."""
    n_pad = -(-rows.size // batch) * batch
    reps = np.arange(n_pad) % rows.size
    r, c = rows[reps].astype(np.int32), cols[reps].astype(np.int32)
    return r, c, zlib.crc32(c, zlib.crc32(r))


# ------------------------------------------------------------------- the loss


def _loss(ue, ie, inv_temp: float, both_halves: bool):
    import jax.numpy as jnp

    x = ue / (jnp.linalg.norm(ue, axis=-1, keepdims=True) + 1e-8)
    y = ie / (jnp.linalg.norm(ie, axis=-1, keepdims=True) + 1e-8)
    logits = (x @ y.T) * inv_temp
    diag = jnp.sum(x * y, axis=-1) * inv_temp
    user_to_item = jnp.mean(_logsumexp(logits, 1) - diag)
    # the control leaves the second half out and keeps the first's weight
    item_to_user = jnp.mean(_logsumexp(logits, 0) - diag) if both_halves else 0.0
    return 0.5 * (user_to_item + item_to_user)


def _logsumexp(z, axis: int):
    import jax.numpy as jnp

    top = jnp.max(z, axis=axis, keepdims=True)
    return jnp.log(jnp.sum(jnp.exp(z - top), axis=axis)) + jnp.squeeze(top, axis)


_compiled: dict = {}


def loss_and_grads(ue: np.ndarray, ie: np.ndarray, temperature: float,
                   both_halves: bool = True):
    """Float64 loss of a batch's raw rows ``[B, D]`` and its gradients with
    respect to them."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        key = (float(temperature), bool(both_halves))
        if key not in _compiled:
            _compiled[key] = jax.jit(jax.value_and_grad(
                lambda a, b: _loss(a, b, 1.0 / key[0], key[1]), argnums=(0, 1)))
        loss, (gu, gi) = _compiled[key](
            jnp.asarray(ue, jnp.float64), jnp.asarray(ie, jnp.float64))
        return float(loss), np.asarray(gu), np.asarray(gi)


# -------------------------------------------------------------- the optimizer


class Rows:
    """A table under the row update: the float32 draw, and float64 ``p``,
    ``m``, ``v`` of the rows a step has touched so far."""

    def __init__(self, base: np.ndarray):
        self.base = base
        self.slot = np.full(base.shape[0], -1, np.int64)
        d = base.shape[1]
        self.p, self.m, self.v = (np.zeros((0, d)) for _ in range(3))

    def read(self, ids: np.ndarray) -> np.ndarray:
        out = self.base[ids].astype(np.float64)
        slot = self.slot[ids]
        out[slot >= 0] = self.p[slot[slot >= 0]]
        return out

    def dense(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``p``, ``m``, ``v`` of every row (the tests' sizes only)."""
        p = self.base.astype(np.float64)
        m, v = np.zeros_like(p), np.zeros_like(p)
        at = np.flatnonzero(self.slot >= 0)
        p[at], m[at], v[at] = (a[self.slot[at]] for a in (self.p, self.m, self.v))
        return p, m, v

    def update(self, ids: np.ndarray, grads: np.ndarray, t: int, lr: float,
               sum_duplicates: bool = True) -> float:
        """One step on the distinct ``ids``; returns ``sum_r |g_r|^2``."""
        distinct, first, inverse = np.unique(ids, return_index=True, return_inverse=True)
        if sum_duplicates:
            g = np.zeros((distinct.size, grads.shape[1]))
            np.add.at(g, inverse, grads)
        else:  # the control: a duplicate's gradient applied once
            g = grads[first]
        new = distinct[self.slot[distinct] < 0]
        self.slot[new] = self.p.shape[0] + np.arange(new.size)
        self.p = np.concatenate([self.p, self.base[new].astype(np.float64)])
        self.m = np.concatenate([self.m, np.zeros((new.size, g.shape[1]))])
        self.v = np.concatenate([self.v, np.zeros((new.size, g.shape[1]))])
        s = self.slot[distinct]
        self.m[s] = B1 * self.m[s] + (1.0 - B1) * g
        self.v[s] = B2 * self.v[s] + (1.0 - B2) * g * g
        m_hat = self.m[s] / (1.0 - B1**t)
        v_hat = self.v[s] / (1.0 - B2**t)
        self.p[s] -= lr * m_hat / (np.sqrt(v_hat) + EPS)
        return float(np.sum(g * g))


class Replay:
    """The training from its first step on, one :meth:`step` a batch."""

    def __init__(self, user0: np.ndarray, item0: np.ndarray, lr: float,
                 temperature: float, both_halves: bool = True,
                 sum_duplicates: bool = True):
        self.user, self.item = Rows(user0), Rows(item0)
        self.lr, self.temperature = lr, temperature
        self.both_halves, self.sum_duplicates = both_halves, sum_duplicates
        self.t = 0
        #: a step's gradient norms, duplicates summed: [user, item]
        self.grad_norms: list = []

    def step(self, u_ids: np.ndarray, i_ids: np.ndarray) -> float:
        self.t += 1
        loss, gu, gi = loss_and_grads(
            self.user.read(u_ids), self.item.read(i_ids), self.temperature,
            self.both_halves)
        self.grad_norms.append([
            np.sqrt(self.user.update(u_ids, gu, self.t, self.lr, self.sum_duplicates)),
            np.sqrt(self.item.update(i_ids, gi, self.t, self.lr, self.sum_duplicates))])
        return loss


def replay_steps(replay: Replay, r: np.ndarray, c: np.ndarray, perm: np.ndarray,
                 batch: int, steps: int, until=None) -> list:
    """The losses of the first ``steps`` batches of ``r[perm]``, ``c[perm]``
    (the gradient norms are in ``replay.grad_norms``); ``until(losses,
    grad_norms)`` true ends it early (a control need not be run to its end)."""
    losses = []
    for k in range(steps):
        at = perm[k * batch:(k + 1) * batch]
        losses.append(replay.step(r[at], c[at]))
        if until is not None and until(losses, replay.grad_norms):
            break
    return losses


def loss_gap(losses: list, against: list) -> float:
    """Largest distance of a step's loss from the other's."""
    return max(abs(a - b) for a, b in zip(losses, against))


def norm_gap(norms: list, against: list) -> float:
    """Largest relative distance of a step's gradient norm (either table)
    from the other's."""
    return max(abs(a / b - 1.0) for x, y in zip(norms, against) for a, b in zip(x, y))


# ------------------------------------------------------------------ the check


def _rows_of(index, n: int) -> np.ndarray:
    """``row[code]`` of the benchmark's ids ``"0".."n-1"`` in a stored BiMap."""
    fwd = index.to_dict()
    keys = np.fromiter((int(k) for k in fwd), np.int64, len(fwd))
    row = np.full(n, -1, np.int64)
    row[keys] = np.fromiter(fwd.values(), np.int64, len(fwd))
    return row


def check_train(run, events: dict, instance: dict, blob: bytes) -> bool:
    cfg, c = run.config, Checks(run.say)
    model, shape, lim, chk = cfg["model"], cfg["shape"], cfg["limits"], cfg["check"]
    tt, dev = instance["kernels"].get("twotower", {}), instance["device"]
    batch, dim = model["batch"], model["dim"]
    steps_per_epoch = -(-shape["pairs"] // batch)
    for key, want in cfg["expect"].items():
        c.equal(f"instance {key}", dev.get(key) if key == "platform" else tt.get(key), want)
    c.equal("epochs run", len(tt.get("epochSeconds", [])), model["epochs"])
    c.equal("steps an epoch", tt.get("stepsPerEpoch"), steps_per_epoch)

    m = load_models(blob)[0]
    user = np.asarray(m.user_vecs, np.float32)
    item = np.asarray(m.item_vecs, np.float32)
    c.equal("stored table shapes", [list(user.shape), list(item.shape)],
            [[shape["users"], dim], [shape["items"], dim]])
    if not c.ok:
        return False
    c.equal("stored tables finite",
            bool(np.isfinite(user).all() and np.isfinite(item).all()), True)
    def off_unit(tables) -> float:
        return max(float(np.abs(np.linalg.norm(t, axis=1) - 1.0).max()) for t in tables)

    c.leq("largest distance of a stored row's norm from 1", off_unit((user, item)),
          lim["row_norm_err"])
    c.control("the same of the stored tables rounded to bfloat16",
              off_unit((bf16(user), bf16(item))), lim["row_norm_err"])

    # the program's pairs, in its order: distinct, sorted by user row, item row
    user_row, item_row = _rows_of(m.user_index, shape["users"]), _rows_of(m.item_index, shape["items"])
    c.equal("every id of the events has a row", bool(user_row.min() >= 0 and item_row.min() >= 0), True)
    if not c.ok:
        return False
    ur, ic = user_row[events["rows"]], item_row[events["cols"]]
    order = np.lexsort((ic, ur))
    r, cc, checksum = padded_pairs(ur[order], ic[order], batch)
    c.equal("checksum of the id arrays the program uploaded", tt.get("pairsChecksum"), checksum)

    seed = events["model_seed"]
    steps = int(chk["replay_steps"])
    got = [float(x) for x in tt.get("firstLosses", [])[:steps]]
    got_norms = [[float(x) for x in pair] for pair in tt.get("firstGradNorms", [])[:steps]]
    c.equal("losses and gradient norms recorded for the replay",
            [len(got), len(got_norms)], [steps, steps])
    if not c.ok:
        return False
    draws = events.get("draws")
    if draws is None:
        draws = Draws(seed, shape, model)
        draws.start()
    user0, item0, perm = draws.result()
    lr, temp = model["learning_rate"], model["temperature"]
    replay = Replay(user0, item0, lr, temp)
    ref = replay_steps(replay, r, cc, perm, batch, steps)
    run.say(f"replay: program losses {[round(x, 5) for x in got]}")
    run.say(f"replay: float64 losses {[round(x, 5) for x in ref]}")
    run.say(f"replay: program gradient norms (user, item) {np.round(got_norms, 6).tolist()}")
    run.say(f"replay: float64 gradient norms (user, item) {np.round(replay.grad_norms, 6).tolist()}")
    c.leq(f"largest gap of the first {steps} losses to the float64 replay",
          loss_gap(ref, got), lim["replay_loss_gap"])
    c.leq(f"largest relative gap of the first {steps} steps' gradient norms",
          norm_gap(replay.grad_norms, got_norms), lim["replay_norm_gap"])

    # the controls: over one of the two limits is enough, as for the program
    # under both is required
    def over(losses, norms):
        return max(loss_gap(losses, got) / lim["replay_loss_gap"],
                   norm_gap(norms, got_norms) / lim["replay_norm_gap"])

    for name, kw in (("without the item-to-user half", {"both_halves": False}),
                     ("with a duplicate's gradient applied once", {"sum_duplicates": False})):
        control = Replay(user0, item0, lr, temp, **kw)
        ctl = replay_steps(control, r, cc, perm, batch, steps,
                           until=lambda losses, norms: over(losses, norms) > 1.0)
        run.say(f"control {name}: loss gap {loss_gap(ctl, got):.3g}, gradient norm gap "
                f"{norm_gap(control.grad_norms, got_norms):.3g} after {len(ctl)} step(s)")
        c.control(f"the replay {name}: its larger gap over its limit",
                  over(ctl, control.grad_norms), 1.0)

    # the published tables: their float64 loss on seeded batches of the pairs
    rng = np.random.default_rng(run.seed + 7)
    final = []
    for _ in range(int(chk["final_batches"])):
        at = rng.choice(shape["pairs"], batch, replace=False)
        final.append(loss_and_grads(user[r[at]], item[cc[at]], temp)[0])
    last = [float(x) for x in tt.get("lastLosses", [])]
    run.say(f"final: float64 loss of the stored tables on {len(final)} seeded batches "
            f"{[round(x, 4) for x in final]}; the program's last losses "
            f"{[round(x, 4) for x in last[-4:]]}")
    c.leq("stored tables' loss over the replay's first", max(final) / ref[0],
          lim["final_over_first"])
    # no last losses recorded: NaN, which fails both
    over_last = float(np.mean(final) / np.mean(last)) if last else float("nan")
    c.leq("stored tables' loss over the program's last losses", over_last,
          lim["final_over_last_max"])
    c.geq("stored tables' loss over the program's last losses", over_last,
          lim["final_over_last_min"])
    return c.ok
