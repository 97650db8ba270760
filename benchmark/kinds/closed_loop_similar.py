"""Traffic kind ``closed_loop_similar``: ``closed_loop`` against the Similar
Product engine. ``clients`` callers, each sending its next query when the
last is answered; every query names one or more items and is answered with
the items most like them, under the template's rules (the query's own items
and its black list left out; a share with categories), so each answer is
selected under a mask of its own. ``served_qps`` is the correct 200 answers
completed inside the window over the window's length.

The set-up (benchmark/simshop.py) makes the deployment from the seed,
publishes it as a trained model and boots `pio deploy`; the queries go out
as prepared bodies (benchmark/bodygen.py). Afterwards the reference named
by the configuration checks a sample of the window's answers against the
same rules.
"""

from __future__ import annotations

from benchmark import bodygen, serving, simshop
from benchmark.stats import percentile

#: no chip answers this traffic faster; sizes the pool of distinct queries
_MAX_QPS = 4000


def _counters_window(b0: dict, b1: dict, block: str, per_query: dict) -> dict:
    """The handler's ``block`` counters over the window, and those named in
    ``per_query`` (counter -> name) a batched query."""
    queries = b1["batchedQueries"] - b0["batchedQueries"]
    c0, c1 = b0.get(block), b1.get(block)
    if c0 is None or c1 is None or not queries:
        return {}
    moved = {k: c1[k] - c0.get(k, 0) for k in c1}
    return {**moved, **{name: moved[k] / queries for k, name in per_query.items()}}


def run(run) -> dict:
    from predictionio_tpu.templates.similarproduct.engine import ALSAlgorithm

    if not hasattr(ALSAlgorithm, "pin_model_for_serving"):
        # a program from before the engine had a device path answers a query
        # of this catalog in seconds on the host, one by one: say so now,
        # before a table is made, not after a window of time-outs
        raise RuntimeError("this program's similar-product engine has no "
                           "pin_model_for_serving: it cannot serve this cell")
    clients = int(run.traffic["clients"])
    timeout = float(run.traffic["timeout_s"])
    warm_s = float(run.traffic["warmup_s"])
    # more queries than any window can serve; the loop stops at the time
    budget = int(_MAX_QPS * (run.seconds + warm_s))

    t0 = run.elapsed()
    dep = run.deployment = simshop.deployment(run, budget)
    bodies = dep["bodies"]
    n_warm = max(clients, int(len(bodies) * warm_s / (run.seconds + warm_s)))
    t_made = run.elapsed()
    inst = simshop.publish(run, dep)
    t_publish = run.elapsed()
    run.say(f"deployment: table {dep['item'].shape}, {len(dep['names'])} categories; "
            f"instance {inst}; {len(bodies)} different queries of {budget} drawn")
    server = simshop.Server(run, dep["warm_item"])
    run.say(f"server: GET / after {server.boot_s:.1f} s; device "
            f"{server.status.get('device', {})}")

    def spec(seconds: float) -> dict:
        return {"port": server.port, "timeout_s": timeout, "clients": clients,
                "seconds": seconds}

    t_boot = run.elapsed()
    out = bodygen.drive(run.workdir, "warm", spec(warm_s), bodies[:n_warm])["out"]
    run.say(f"warm-up: {len(out)} requests from {clients} clients, "
            f"{sum(1 for r in out if r[0] != 200)} not 200")
    parts = {"tables_s": t_made - t0, "publish_s": t_publish - t_made,
             "boot_s": server.boot_s, "warmup_s": run.elapsed() - t_boot}
    b0 = server.stats()["batcher"]
    setup_s = run.elapsed()
    run.say(f"window: {clients} clients for {run.seconds:g} s "
            f"(set-up {setup_s:.2f} s: {({k: round(v, 2) for k, v in parts.items()})})")
    sl, thread = None, None
    if run.trace:
        sl = {}
        thread = serving.trace_slice(server, 0.25 * run.seconds, 2.0, sl)
    res = bodygen.drive(run.workdir, "window", spec(run.seconds), bodies[n_warm:])
    if thread is not None:
        thread.join(timeout=180)
    out, wall = res["out"], res["wall"]
    setup_s += res["startup_s"]  # the generator's own start, before its first request
    # an answer is known by its query's number, which finds its rules again
    good, bad = serving.parse_answers(
        run, [(r[0], r[1], n_warm + r[2]) for r in out], int(dep["item"].shape[0]))
    good_numbers = {n for n, _ in good}
    inside = [r for r in out if r[0] == 200 and n_warm + r[2] in good_numbers
              and r[4] <= run.seconds]
    qps = len(inside) / run.seconds
    lat = sorted(1e3 * r[3] for r in inside)
    run.say(f"window: {len(out)} sent in {wall:.2f} s, {bad} failed "
            f"{serving.status_counts(out)}; "
            f"{len(inside)} good answers inside the window -> {qps:.3f} queries/s; "
            f"latency p50 {percentile(lat, 50):.3f} p95 {percentile(lat, 95):.3f} ms "
            f"(recorded, not judged); {serving.stalls_text(res['stalls'])}")
    facts = {
        "attempted": len(out),
        "end_to_end": {"setup_s": setup_s, "served_qps": qps},
        "client": {"p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95)},
        "setup_parts": parts,
    }
    t_check = run.elapsed()
    facts = serving.finish(run, server, facts, b0, good, bad, None, dep["item"], sl)
    b1 = facts["stats"]["batcher"]
    facts["filter"] = _counters_window(b0, b1, "filter",
                                       {"excludedIds": "excluded_per_query"})
    facts["similar"] = _counters_window(b0, b1, "similar",
                                        {"queryItems": "query_items_per_query"})
    run.say(f"counters over the window: filter {facts['filter']}; similar "
            f"{facts['similar']}; stop and check {run.elapsed() - t_check:.1f} s")
    if facts["filter"].get("hostPath"):
        run.say(f"check queries on the host path: {facts['filter']['hostPath']} -> FAILED")
        facts["correct"] = False
    if facts["similar"].get("unknownItems"):
        run.say("check query items the model does not hold: "
                f"{facts['similar']['unknownItems']} -> FAILED")
        facts["correct"] = False
    return facts
