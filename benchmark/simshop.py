"""What the similar-product serve kind sets up: a Similar Product deployment
made from the seed (a table of unit-length item rows, a category per item,
and the queries: item sets drawn by popularity and category, with
categories and black lists), published as a trained ``SimilarProductModel``
and served by `pio deploy`. The model has no user table and the engine
reads no event store at query time, so there is neither here.
"""

from __future__ import annotations

import datetime
import json
import os
import socket
import time
import urllib.error
import uuid
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import serving


def _unit_rows(n_items: int, rank: int, seed: int) -> np.ndarray:
    """Normal float32 rows scaled to unit length in float32, as the engine's
    ``train`` stores them (``item / norms``). Made a block of 2^20 rows at a
    time, each block from a stream of its own spawned from the seed, the
    blocks side by side on the host's cores (numpy draws without the
    interpreter lock): one stream takes 40 s for 15.5 M rows, and set-up is
    a number the benchmark reports."""
    item = np.empty((n_items, rank), np.float32)
    block = 1 << 20
    streams = np.random.SeedSequence(seed).spawn(-(-n_items // block))

    def fill(b: int) -> None:
        rows = item[b * block : (b + 1) * block]
        np.random.default_rng(streams[b]).standard_normal(out=rows, dtype=np.float32)
        rows /= np.linalg.norm(rows, axis=1, keepdims=True)

    with ThreadPoolExecutor(max_workers=os.cpu_count()) as pool:
        list(pool.map(fill, range(len(streams))))
    return item


def deployment(run, n_queries: int) -> dict:
    """Everything the seed decides. At most ``n_queries`` queries, all
    different (a query drawn a second time is dropped); query ``n`` is
    ``rules(n)`` to the reference and ``bodies[n]`` on the wire."""
    cfg, traffic = run.config, run.traffic
    n_items, rank = cfg["shape"]["items"], cfg["model"]["rank"]
    item = _unit_rows(n_items, rank, run.seed)
    rng = np.random.default_rng(run.seed + 5)
    names = list(cfg["category_products"])
    share = np.asarray(list(cfg["category_products"].values()), np.float64)
    share /= share.sum()
    code_of = rng.choice(len(names), size=n_items, p=share).astype(np.int32)
    by_category = np.argsort(code_of, kind="stable")  # the items, category by category
    starts = np.searchsorted(code_of[by_category], np.arange(len(names) + 1))
    # page views: item of popularity rank r with weight (r + shift)^-exponent
    pop = cfg["popularity"]
    weight = (np.arange(n_items, dtype=np.float64) + pop["shift"]) ** -pop["exponent"]
    item_of_rank = rng.permutation(n_items)
    first = item_of_rank[rng.choice(n_items, size=n_queries, p=weight / weight.sum())]
    del weight, item_of_rank
    # items a query: one, or min..max with the others uniform over the first's category
    qi = traffic["query_items"]
    n_more = np.where(rng.random(n_queries) < qi["one_share"], 0,
                      rng.integers(qi["min"], qi["max"] + 1, n_queries) - 1)
    more_at = np.concatenate([[0], np.cumsum(n_more)])
    own = code_of[first]
    size = (starts[own + 1] - starts[own]).repeat(n_more)
    at = np.minimum((rng.random(int(more_at[-1])) * size).astype(np.int64), size - 1)
    more = by_category[starts[own].repeat(n_more) + at]
    del by_category
    # categories: the first item's own, that and a second (by size), or none
    r = rng.random(n_queries)
    cs = traffic["categories_share"]
    n_wanted = np.where(r < cs["own"], 1, np.where(r < cs["own"] + cs["own_and_second"], 2, 0))
    second = rng.choice(len(names), size=n_queries, p=share)
    again = second == own  # two different categories
    second[again] = (own[again] + 1 + rng.integers(0, len(names) - 1, int(again.sum()))) % len(names)
    b = traffic["blacklist"]
    n_black = np.where(rng.random(n_queries) < b["share"],
                       rng.integers(b["min"], b["max"] + 1, n_queries), 0)
    black_at = np.concatenate([[0], np.cumsum(n_black)])
    black = rng.integers(0, n_items, int(black_at[-1]), dtype=np.int64)

    def draw(n: int) -> dict:
        """Draw ``n`` as the reference takes it: distinct ``items``, the
        first by popularity; ``black`` ids; ``wanted`` category codes."""
        items = [int(first[n])]
        for i in more[more_at[n]:more_at[n + 1]].tolist():
            if i not in items:
                items.append(i)
        return {"items": np.asarray(items, np.int64),
                "black": black[black_at[n]:black_at[n + 1]],
                "wanted": np.asarray([own[n], second[n]][:n_wanted[n]], np.int64)}

    def wire(q: dict) -> bytes:
        doc = {"items": [str(i) for i in q["items"].tolist()], "num": int(traffic["num"])}
        if q["wanted"].size:
            doc["categories"] = [names[c] for c in q["wanted"]]
        if q["black"].size:
            doc["blackList"] = [str(i) for i in q["black"].tolist()]
        return json.dumps(doc).encode()

    # no query twice in a run: a popular item's page is drawn many times
    bodies, kept = {}, []
    for n in range(n_queries):
        payload = wire(draw(n))
        if payload not in bodies:
            bodies[payload] = None
            kept.append(n)
    bodies = list(bodies)
    return {"item": item, "codes": code_of[:, None], "names": names,
            "bodies": bodies, "rules": lambda n: draw(kept[n]),
            "warm_item": int(first[0])}


def publish(run, dep: dict) -> str:
    """A COMPLETED engine instance and its model blob, through the storage
    API and the program's own serializer: what `pio train` leaves behind."""
    from predictionio_tpu.data.aggregator import BiMap
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model
    from predictionio_tpu.templates.similarproduct.engine import SimilarProductModel
    from predictionio_tpu.utils.serialization import dumps_model

    item = dep["item"]
    model = SimilarProductModel(
        item_factors=item,
        item_index=BiMap({str(i): i for i in range(item.shape[0])}),
        categories={}, category_codes=dep["codes"],
        category_index=BiMap({name: i for i, name in enumerate(dep["names"])}),
    )
    now = datetime.datetime.now(datetime.timezone.utc)
    inst = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now, end_time=now,
        engine_id="bench", engine_version="1", engine_variant="bench",
        engine_factory=run.config["engine_factory"],
        env={"published_by": "benchmark (seeded tables, no training)"},
    )
    # the blob is the table over again: it goes as soon as it is stored
    Storage.get_model_data_models().insert(
        Model(id=inst.id, models=dumps_model([("pickle", model)])))
    Storage.get_meta_data_engine_instances().insert(inst)
    return inst.id


class Server(serving.Server):
    """`pio deploy` of the similar-product engine: ``serving.Server`` (its
    variant file names the algorithm ``als``, which is this engine's too)
    warmed by a query of this engine's shape."""

    def __init__(self, run, warm_item: int):  # noqa: D107 - see the class
        self.run = run
        with socket.socket() as sock:
            sock.bind(("127.0.0.1", 0))
            self.port = sock.getsockname()[1]
        t0 = time.monotonic()
        self.proc, self.report = run.spawn_pio("deploy", [
            "deploy", "--engine-json", serving.engine_json(run), "--port", str(self.port),
            *run.traffic["deploy_flags"],
            "--batch-warmup-query",
            json.dumps({"items": [str(int(warm_item))], "num": int(run.traffic["num"])}),
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
