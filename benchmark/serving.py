"""What the serve kinds share: publish seeded factor tables as a trained
model, boot `pio deploy`, read its counters, trace a slice, stop it, and
check the answers. The traffic itself is the kind's (open or closed loop).
"""

from __future__ import annotations

import datetime
import json
import os
import socket
import threading
import time
import urllib.error
import urllib.request
import uuid

import numpy as np

from benchmark import data, harness, loadgen, xplane


def http_json(port: int, path: str, body: dict | None = None, timeout: float = 10.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
        method="GET" if body is None else "POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def publish_tables(run, user: np.ndarray, item: np.ndarray) -> str:
    """A COMPLETED engine instance and its model blob, through the storage
    API and the program's own serializer: what `pio train` leaves behind."""
    from predictionio_tpu.data.aggregator import BiMap
    from predictionio_tpu.data.storage import Storage
    from predictionio_tpu.data.storage.base import EngineInstance, Model
    from predictionio_tpu.templates.recommendation.engine import ALSModel
    from predictionio_tpu.utils.serialization import dumps_model

    model = ALSModel(
        user_factors=user, item_factors=item,
        user_index=BiMap({str(i): i for i in range(user.shape[0])}),
        item_index=BiMap({str(i): i for i in range(item.shape[0])}),
    )
    now = datetime.datetime.now(datetime.timezone.utc)
    inst = EngineInstance(
        id=uuid.uuid4().hex, status="COMPLETED", start_time=now, end_time=now,
        engine_id="bench", engine_version="1", engine_variant="bench",
        engine_factory=run.config["engine_factory"],
        env={"published_by": "benchmark (seeded tables, no training)"},
    )
    Storage.get_model_data_models().insert(
        Model(id=inst.id, models=dumps_model([("pickle", model)])))
    Storage.get_meta_data_engine_instances().insert(inst)
    return inst.id


def engine_json(run) -> str:
    path = os.path.join(run.workdir, "bench.json")
    with open(path, "w") as f:
        json.dump({
            "id": "bench", "version": "1",
            "engineFactory": run.config["engine_factory"],
            "datasource": {"params": {"appName": "bench"}},
            "algorithms": [{"name": "als", "params": {
                "rank": run.config["model"]["rank"]}}],
        }, f)
    return path


class Server:
    """`pio deploy` as a child, from boot to `pio undeploy`."""

    def __init__(self, run, warm_user: int):
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
                self.status = http_json(self.port, "/", timeout=2.0)
            except (urllib.error.URLError, ConnectionError, socket.timeout, OSError):
                time.sleep(0.25)
        self.boot_s = time.monotonic() - t0

    def stats(self) -> dict:
        return http_json(self.port, "/stats.json")

    def stop(self) -> dict:
        """`pio undeploy`, wait for the child, and its report."""
        from predictionio_tpu.tools import commands

        commands.undeploy(port=self.port, out=lambda *_: None)
        rep = self.run.reap(self.proc, "deploy", self.report, timeout=120)
        with open(os.path.join(self.run.workdir, "deploy.log"), errors="replace") as f:
            loud = [ln.rstrip()[:300] for ln in f
                    if any(w in ln for w in ("WARNING", "ERROR", "Traceback", "Exception"))]
        if loud:
            self.run.say(f"server log: {len(loud)} loud lines, the last: {loud[-3:]}")
        return rep


def trace_slice(server: Server, after_s: float, length_s: float, out: dict) -> threading.Thread:
    """A thread that, ``after_s`` into the window, profiles the server for
    ``length_s`` through its own /profiler routes and notes the batcher's
    counters at both ends."""
    trace_dir = os.path.join(server.run.workdir, "trace")

    def go() -> None:
        time.sleep(after_s)
        http_json(server.port, "/profiler/start", {"logDir": trace_dir}, timeout=60)
        t0 = time.monotonic()  # the profiler runs from here ...
        b0 = server.stats()["batcher"]
        time.sleep(length_s)
        b1 = server.stats()["batcher"]
        out["window_s"] = time.monotonic() - t0  # ... to here
        http_json(server.port, "/profiler/stop", {}, timeout=120)
        out["batches"] = b1["batches"] - b0["batches"]
        out["queries"] = b1["batchedQueries"] - b0["batchedQueries"]
        out["dir"] = trace_dir

    t = threading.Thread(target=go, daemon=True)
    t.start()
    return t


def drive_window(run, server: Server, spec: dict, users: np.ndarray,
                 due: np.ndarray | None = None) -> tuple[dict, dict | None]:
    """The measured window: the load generator's child and, in a traced
    run, a 2 s profiler slice a quarter into it (so the batcher's closing
    percentiles, which keep the last 4096 samples, come from after it)."""
    sl, thread = None, None
    if run.trace:
        sl = {}
        thread = trace_slice(server, 0.25 * run.seconds, 2.0, sl)
    res = loadgen.drive(run.workdir, "window", spec, users, due)
    if thread is not None:
        thread.join(timeout=180)
    return res, sl


def status_counts(out: list) -> dict:
    counts: dict = {}
    for r in out:
        counts[r[0]] = counts.get(r[0], 0) + 1
    return counts


def stalls_text(stalls: list) -> str:
    return (f"the generator's loop stalled over 100 ms {len(stalls)} times "
            f"{[(round(a, 2), round(b, 3)) for a, b in stalls[:6]]}")


def reduce_slice(run, sl: dict) -> dict:
    path = xplane.find_xplane(sl["dir"])
    if path is None:
        raise RuntimeError("the server's profiler left no .xplane.pb")
    red = xplane.reduce_trace(path)
    # the window is the host-clock span in which the profiler ran. Batches
    # are counted where the busy time is: every device op of a pinned exact
    # deploy belongs to the scoring program, which runs once a batch (the
    # batcher's own counter, read a moment inside the span, says how full)
    red["window_s"] = sl["window_s"]
    red["idle_pct"] = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    red["batches"] = sum(red["module_runs"].values())
    if red["batches"]:
        red["device_ms_per_batch"] = 1e3 * red["busy_s"] / red["batches"]
    if sl["batches"] > 0:
        red["fill"] = sl["queries"] / sl["batches"]
    run.say(f"trace: {os.path.getsize(path):,} bytes; busy {red['busy_s']:.3f} s of "
            f"{red['window_s']:.3f} s; programs run {red['module_runs']}; the "
            f"batcher counted {sl['batches']} batches of {sl['queries']} queries")
    return red


def batch_window(b0: dict, b1: dict) -> dict:
    """The batcher's monotonic counters over the window."""
    batches = b1["batches"] - b0["batches"]
    queries = b1["batchedQueries"] - b0["batchedQueries"]
    hist = {k: v - b0["batchSizeHist"].get(k, 0) for k, v in b1["batchSizeHist"].items()}
    return {"batches": batches, "queries": queries,
            "batch_fill": queries / batches if batches else None,
            "hist": {k: v for k, v in hist.items() if v},
            "rejected": b1["rejected"] - b0["rejected"],
            "bucket_misses": b1["bucketMisses"] - b0["bucketMisses"]}


def parse_answers(run, answers: list, n_items: int) -> tuple[list, int]:
    """``answers``: [(status, body bytes, user code)]. Returns the good ones
    as [(user code, payload)] and the count of bad ones: a non-200, a
    timeout, or an answer that is not ``num`` distinct known items with
    finite descending scores."""
    reference = harness.load_module("references", run.config["reference"])
    good, bad, num = [], 0, int(run.traffic["num"])
    for status, body, code in answers:
        payload = None
        if status == 200:
            try:
                payload = json.loads(body)
            except ValueError:
                payload = None
        if payload is not None and reference.answer_shape_ok(payload, num, n_items):
            good.append((int(code), payload))
        else:
            bad += 1
    return good, bad


def check_answers(run, user: np.ndarray, item: np.ndarray, good: list) -> bool:
    """A seeded sample of the answered queries against the reference."""
    reference = harness.load_module("references", run.config["reference"])
    n = int(run.config["check"]["serve_queries"])
    rng = np.random.default_rng(run.seed + 11)
    pick = rng.choice(len(good), size=min(n, len(good)), replace=False) if good else []
    return reference.check_serve(run, user, item, [good[i] for i in pick])


def tables(run) -> tuple[np.ndarray, np.ndarray]:
    shape = {**run.config["shape"], "rank": run.config["model"]["rank"]}
    return data.factor_tables(shape, run.seed)


def setup(run, warm_users: np.ndarray, warm_fn) -> tuple[Server, np.ndarray, np.ndarray, dict]:
    """Tables from the seed, published, served, warmed by ``warm_fn(server)``
    (the kind's own traffic, short). Returns the server, the tables and the
    set-up's parts in seconds."""
    t0 = run.elapsed()
    user, item = tables(run)
    t_tables = run.elapsed()
    inst = publish_tables(run, user, item)
    t_publish = run.elapsed()
    run.say(f"publish: instance {inst} from seeded tables "
            f"{user.shape} {item.shape}")
    server = Server(run, int(warm_users[0]))
    dev = server.status.get("device", {})
    run.say(f"server: GET / after {server.boot_s:.1f} s; device {dev}")
    warm_fn(server)
    parts = {"tables_s": t_tables - t0, "publish_s": t_publish - t_tables,
             "boot_s": server.boot_s,
             "warmup_s": run.elapsed() - t_publish - server.boot_s}
    return server, user, item, parts


def finish(run, server: Server, facts: dict, b0: dict, good: list, bad: int,
           user, item, sl: dict | None) -> dict:
    """Counters, stop the server, memory, the check; fills ``facts``."""
    stats1 = server.stats()
    status1 = http_json(server.port, "/")
    win = batch_window(b0, stats1["batcher"])
    run.say(f"window: batcher {json.dumps(stats1['batcher']['latencyMs'])}; "
            f"batches {win['batches']} fill {win['batch_fill']}; sizes {win['hist']}; "
            f"rejected {win['rejected']}")
    rep = server.stop()
    memory = harness.memory_peak([rep])
    run.say(f"memory: most held at once {memory}; allocator at exit "
            f"{rep['memory'][0]}; GET / {status1.get('device')}")
    c_ok = True
    dev = status1.get("device", {})
    want = run.config.get("expect", {}).get("platform", "tpu")
    if dev.get("servedFrom") != "device" or (run.platforms == "tpu" and dev.get("platform") != want):
        run.say(f"check servedFrom: {dev} -> FAILED")
        c_ok = False
    if win["bucket_misses"]:
        run.say(f"check bucket misses inside the window: {win['bucket_misses']} -> FAILED")
        c_ok = False
    ok = check_answers(run, user, item, good) and c_ok
    facts.update({
        "correct": ok, "failed": bad, "device": rep["device"],
        "memory": memory, "stats": stats1, "window": win,
        "config": run.config, "traffic": run.traffic,
        "shape": {"items": int(item.shape[0]), "rank": int(item.shape[1]),
                  "num": int(run.traffic["num"])},
    })
    if sl is not None:
        facts["trace"] = reduce_slice(run, sl)
    return facts
