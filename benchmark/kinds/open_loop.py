"""Traffic kind ``open_loop``: arrivals on a schedule from the seed at the
rate fixed in the traffic file, whatever the server does. Latency is timed
from the instant a request was *due*; a non-200, a wrong answer or a
timeout counts as missing (infinitely slow)."""

from __future__ import annotations

from benchmark import loadgen, serving
from benchmark.stats import percentile


def run(run) -> dict:
    rate, num = float(run.traffic["rate_per_s"]), int(run.traffic["num"])
    timeout = float(run.traffic["timeout_s"])
    warm_s = float(run.traffic["warmup_s"])
    n_users = run.config["shape"]["users"]
    due = loadgen.open_loop_schedule(rate, run.seconds, run.seed)
    warm_due = loadgen.open_loop_schedule(rate, warm_s, run.seed + 1)
    users = loadgen.distinct_users(n_users, due.size + warm_due.size, run.seed + 2)
    warm_users, users = users[: warm_due.size], users[warm_due.size:]

    def spec(server) -> dict:
        return {"mode": "open", "port": server.port, "num": num, "timeout_s": timeout}

    def warm(server) -> None:
        out = loadgen.drive(run.workdir, "warm", spec(server), warm_users, warm_due)["out"]
        bad = sum(1 for r in out if r[0] != 200)
        run.say(f"warm-up: {len(out)} requests at {rate:g}/s, {bad} not 200")

    server, user, item, parts = serving.setup(run, warm_users, warm)
    b0 = server.stats()["batcher"]
    setup_s = run.elapsed()
    run.say(f"window: {rate:g} requests/s for {run.seconds:g} s, {due.size} requests "
            f"(set-up {setup_s:.2f} s: {({k: round(v, 2) for k, v in parts.items()})})")
    res, sl = serving.drive_window(run, server, spec(server), users, due)
    out, wall = res["out"], res["wall"]
    setup_s += res["startup_s"]  # the generator's own start, before its first request
    good, bad = serving.parse_answers(
        run, [(r[0], r[1], u) for r, u in zip(out, users)], int(item.shape[0]))
    # latencies of the good answers; every other request is missing
    good_codes = {code for code, _ in good}
    lat = sorted(1e3 * r[3] for r, u in zip(out, users)
                 if r[0] == 200 and int(u) in good_codes)
    late = sorted(1e3 * r[2] for r in out)
    p50, p95 = percentile(lat, 50, bad), percentile(lat, 95, bad)
    run.say(f"window: {len(out)} sent in {wall:.2f} s, {bad} failed "
            f"{serving.status_counts(out)}; client p50 {p50:.3f} p95 {p95:.3f} p99 "
            f"{percentile(lat, 99, bad):.3f} ms; generator late p95 "
            f"{percentile(late, 95):.3f} max {late[-1]:.1f} ms; "
            f"{serving.stalls_text(res['stalls'])}")
    not_ok = [float(due[i]) for i, r in enumerate(out) if r[0] != 200]
    if not_ok:
        run.say(f"window: requests not 200 were due between {min(not_ok):.2f} s "
                f"and {max(not_ok):.2f} s")
    facts = {
        "attempted": len(out),
        "end_to_end": {"setup_s": setup_s, "query_p50_ms": p50, "query_p95_ms": p95},
        "client": {"p50_ms": p50, "p95_ms": p95, "late_p95_ms": percentile(late, 95)},
        "setup_parts": parts,
    }
    return serving.finish(run, server, facts, b0, good, bad, user, item, sl)
