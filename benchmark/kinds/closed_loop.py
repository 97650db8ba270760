"""Traffic kind ``closed_loop``: ``clients`` callers, each sending its next
query when the last is answered, so the queue never empties.
``served_qps`` is the correct 200 answers completed inside the window over
the window's length."""

from __future__ import annotations

from benchmark import loadgen, serving
from benchmark.stats import percentile

#: no chip answers faster than this; sizes the pool of distinct users
_MAX_QPS = 4000


def run(run) -> dict:
    clients, num = int(run.traffic["clients"]), int(run.traffic["num"])
    timeout = float(run.traffic["timeout_s"])
    warm_s = float(run.traffic["warmup_s"])
    n_users = run.config["shape"]["users"]
    # more users than any window can serve; the loop stops at the time
    budget = int(_MAX_QPS * (run.seconds + warm_s))
    users = loadgen.distinct_users(n_users, min(budget, n_users), run.seed + 2)
    n_warm = max(clients, int(len(users) * warm_s / (run.seconds + warm_s)))
    warm_users, users = users[:n_warm], users[n_warm:]

    def spec(server, seconds: float) -> dict:
        return {"mode": "closed", "port": server.port, "num": num, "timeout_s": timeout,
                "clients": clients, "seconds": seconds}

    def warm(server) -> None:
        out = loadgen.drive(run.workdir, "warm", spec(server, warm_s), warm_users)["out"]
        bad = sum(1 for r in out if r[0] != 200)
        run.say(f"warm-up: {len(out)} requests from {clients} clients, {bad} not 200")

    server, user, item, parts = serving.setup(run, warm_users, warm)
    b0 = server.stats()["batcher"]
    setup_s = run.elapsed()
    run.say(f"window: {clients} clients for {run.seconds:g} s "
            f"(set-up {setup_s:.2f} s: {({k: round(v, 2) for k, v in parts.items()})})")
    res, sl = serving.drive_window(run, server, spec(server, run.seconds), users)
    out, wall = res["out"], res["wall"]
    setup_s += res["startup_s"]  # the generator's own start, before its first request
    good, bad = serving.parse_answers(
        run, [(r[0], r[1], users[r[2]]) for r in out], int(item.shape[0]))
    good_codes = {code for code, _ in good}
    inside = [r for r in out if r[0] == 200 and int(users[r[2]]) in good_codes
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
    return serving.finish(run, server, facts, b0, good, bad, user, item, sl)
