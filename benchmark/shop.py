"""What the filtered serve kind sets up: an E-Commerce Recommendation
deployment made from the seed (factor tables, a category per item, the
items out of stock, the view / buy history of every user the window may
draw, and the queries), published as a trained ``ECommModel``, written to
the app's event store through the storage API, and served by `pio deploy`.
"""

from __future__ import annotations

import datetime
import json
import os
import socket
import time
import urllib.error
import uuid

import numpy as np

from benchmark import data, loadgen, serving

APP = "bench"


def deployment(run, n_queries: int) -> dict:
    """Everything the seed decides. ``n_queries`` distinct users are drawn
    (uniform, no repeats) and query ``n`` is user ``query_user[n]``'s."""
    cfg, traffic = run.config, run.traffic
    shape = {**cfg["shape"], "rank": cfg["model"]["rank"]}
    n_items = shape["items"]
    user, item = data.factor_tables(shape, run.seed)
    rng = np.random.default_rng(run.seed + 5)
    names = list(cfg["category_products"])
    share = np.asarray(list(cfg["category_products"].values()), np.float64)
    share /= share.sum()
    codes = rng.choice(len(names), size=n_items, p=share).astype(np.int32)[:, None]
    unavailable = np.sort(rng.choice(n_items, cfg["unavailable_items"], replace=False))
    query_user = loadgen.distinct_users(shape["users"], n_queries, run.seed + 2)
    # histories: a log-normal number of view / buy events over uniform items
    h = cfg["history"]
    mu = np.log(h["mean"]) - h["sigma"] ** 2 / 2
    lengths = np.clip(np.rint(rng.lognormal(mu, h["sigma"], n_queries)), 1,
                      h["max"]).astype(np.int64)
    seen_at = np.concatenate([[0], np.cumsum(lengths)])
    seen = rng.integers(0, n_items, int(seen_at[-1]), dtype=np.int64)
    bought = rng.random(seen.size) < h["buy_share"]
    # the queries' own rules
    r = rng.random(n_queries)
    one, two = traffic["categories_share"]["one"], traffic["categories_share"]["two"]
    n_wanted = np.where(r < one, 1, np.where(r < one + two, 2, 0))
    first = rng.choice(len(names), size=n_queries, p=share)
    second = rng.choice(len(names), size=n_queries, p=share)
    again = second == first  # two different categories
    second[again] = (first[again] + 1 + rng.integers(0, len(names) - 1, int(again.sum()))) % len(names)
    b = traffic["blacklist"]
    n_black = np.where(rng.random(n_queries) < b["share"],
                       rng.integers(b["min"], b["max"] + 1, n_queries), 0)
    black_at = np.concatenate([[0], np.cumsum(n_black)])
    black = rng.integers(0, n_items, int(black_at[-1]), dtype=np.int64)

    def rules(n: int) -> dict:
        """Query ``n`` as the reference takes it."""
        return {"seen": seen[seen_at[n]:seen_at[n + 1]],
                "black": black[black_at[n]:black_at[n + 1]],
                "wanted": np.asarray([first[n], second[n]][:n_wanted[n]], np.int64)}

    def body(n: int) -> bytes:
        q = {"user": str(int(query_user[n])), "num": int(traffic["num"])}
        if n_wanted[n]:
            q["categories"] = [names[c] for c in rules(n)["wanted"]]
        if n_black[n]:
            q["blackList"] = [str(i) for i in rules(n)["black"].tolist()]
        return json.dumps(q).encode()

    return {"user": user, "item": item, "codes": codes, "names": names,
            "unavailable": unavailable, "query_user": query_user, "rules": rules,
            "body": body, "seen": seen, "seen_at": seen_at, "bought": bought}


def publish(run, dep: dict) -> str:
    """A COMPLETED engine instance and its model blob, through the storage
    API and the program's own serializer: what `pio train` leaves behind."""
    from predictionio_tpu.data.aggregator import BiMap
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model
    from predictionio_tpu.templates.ecommerce.engine import ECommModel
    from predictionio_tpu.utils.serialization import dumps_model

    user, item = dep["user"], dep["item"]
    model = ECommModel(
        user_factors=user, item_factors=item,
        user_index=BiMap({str(i): i for i in range(user.shape[0])}),
        item_index=BiMap({str(i): i for i in range(item.shape[0])}),
        categories={}, popularity=np.zeros(item.shape[0], np.float32),
        category_codes=dep["codes"],
        category_index=BiMap({name: i for i, name in enumerate(dep["names"])}),
    )
    now = datetime.datetime.now(datetime.timezone.utc)
    inst = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now, end_time=now,
        engine_id="bench", engine_version="1", engine_variant="bench",
        engine_factory=run.config["engine_factory"],
        env={"published_by": "benchmark (seeded tables, no training)"},
    )
    # the blob is the tables over again: it goes as soon as it is stored
    Storage.get_model_data_models().insert(
        Model(id=inst.id, models=dumps_model([("pickle", model)])))
    Storage.get_meta_data_engine_instances().insert(inst)
    return inst.id


def write_store(run, dep: dict) -> int:
    """The histories as view / buy events (one bulk write) and the constraint
    entity (one ``$set``), in the app the engine reads at query time."""
    from predictionio_tpu.data.event import DataMap, Event
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.tools import commands

    app_id = commands.app_new(APP, out=lambda *_: None)[0].id
    lengths = np.diff(dep["seen_at"])
    items, item_codes = np.unique(dep["seen"], return_inverse=True)
    rng = np.random.default_rng(run.seed + 6)
    n = Storage.get_p_events().write_columns(
        app_id,
        event=(dep["bought"].astype(np.int32), np.asarray(["view", "buy"])),
        entity_type="user",
        entity_codes=np.repeat(np.arange(lengths.size, dtype=np.int32), lengths),
        entity_vocab=np.asarray([str(int(u)) for u in dep["query_user"]]),
        target_entity_type="item",
        target_codes=item_codes.astype(np.int32),
        target_vocab=np.asarray([str(int(i)) for i in items]),
        event_time_us=1_600_000_000_000_000
        + rng.integers(0, 10**9, dep["seen"].size, dtype=np.int64),
    )
    if n != dep["seen"].size:
        raise RuntimeError(f"wrote {n} of {dep['seen'].size} events")
    Storage.get_l_events().insert(
        Event(event="$set", entity_type="constraint", entity_id="unavailableItems",
              properties=DataMap({"items": [str(int(i)) for i in dep["unavailable"]]})),
        app_id)
    return n


def engine_json(run) -> str:
    path = os.path.join(run.workdir, "bench.json")
    with open(path, "w") as f:
        json.dump({
            "id": "bench", "version": "1",
            "engineFactory": run.config["engine_factory"],
            "datasource": {"params": {"appName": APP}},
            "algorithms": [{"name": "ecomm", "params": {
                "appName": APP, "rank": run.config["model"]["rank"]}}],
        }, f)
    return path


class Server(serving.Server):
    """`pio deploy` of the e-commerce engine: ``serving.Server`` with this
    engine's variant file."""

    def __init__(self, run, warm_user: int):  # noqa: D107 - see the class
        self.run = run
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            self.port = sock.getsockname()[1]
        t0 = time.monotonic()
        self.proc, self.report = run.spawn_pio("deploy", [
            "deploy", "--engine-json", engine_json(run), "--port", str(self.port),
            *run.traffic["deploy_flags"],
            "--batch-warmup-query",
            json.dumps({"user": str(int(warm_user)), "num": int(run.traffic["num"])}),
        ])
        self.status = None
        while self.status is None:
            if self.proc.poll() is not None:
                run.reap(self.proc, "deploy", self.report)  # raises with the log
                raise RuntimeError("deploy exited before serving")
            if time.monotonic() - t0 > 900:
                raise RuntimeError("deploy did not answer GET / in 900 s")
            try:
                self.status = serving.http_json(self.port, "/", timeout=2.0)
            except (urllib.error.URLError, ConnectionError, socket.timeout, OSError):
                time.sleep(0.25)
        self.boot_s = time.monotonic() - t0
