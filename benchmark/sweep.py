#!/usr/bin/env python3
"""Find an open-loop cell's knee, once: one server, a ladder of rates.

    python3 benchmark/sweep.py --workload <cell> --seed <n> --rates 160,240,320 \
        [--seconds 10]

Not part of a run: the rate a cell offers is a number in its traffic file,
four fifths of the highest rate this sweep shows the tree sustaining without
a growing backlog. Prints one JSON line per rate: client percentiles timed
from the due instant, failures, the batcher's queue depth when the last
request was sent, and how late the generator ran.
"""

import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, loadgen, serving  # noqa: E402
from benchmark.stats import percentile  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--platforms", default="tpu", help=argparse.SUPPRESS)  # tests
    args = ap.parse_args()
    held = os.environ.get("JAX_PLATFORMS", "")
    os.environ["JAX_PLATFORMS"] = "cpu"  # this process; the server keeps `held`
    manifest = harness.load_json(harness.ROOT, "BENCHMARK.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    cfg = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    run = harness.Run(
        harness.ROOT, cell, harness.load_json(harness.ROOT, cfg["file"]),
        harness.load_json(harness.HERE, "traffic", cell["traffic"] + ".json"),
        args.seed, args.seconds, False, T0, args.platforms, child_jax_platforms=held)
    num, timeout = int(run.traffic["num"]), float(run.traffic["timeout_s"])
    rates = [float(r) for r in args.rates.split(",")]
    n_users = run.config["shape"]["users"]
    total = int(sum(r * args.seconds for r in rates)) + 64
    users = loadgen.distinct_users(n_users, total, args.seed + 2)
    try:
        server, _, _, parts = serving.setup(run, users, lambda s: None)
        run.say(f"set-up parts {parts}")
        at = 1
        for rate in rates:
            due = loadgen.open_loop_schedule(rate, args.seconds, args.seed)
            b0 = server.stats()["batcher"]
            res = loadgen.drive(
                run.workdir, f"rate{int(rate)}",
                {"mode": "open", "port": server.port, "num": num, "timeout_s": timeout},
                users[at:at + due.size], due)
            out, wall = res["out"], res["wall"]
            b1 = server.stats()["batcher"]
            at += due.size
            bad = sum(1 for r in out if r[0] != 200)
            lat = sorted(1e3 * r[3] for r in out if r[0] == 200)
            late = sorted(1e3 * r[2] for r in out)
            # backlog: latency of the last tenth against the first tenth
            tenth = max(1, len(out) // 10)
            first = sorted(1e3 * r[3] for r in out[:tenth] if r[0] == 200)
            last = sorted(1e3 * r[3] for r in out[-tenth:] if r[0] == 200)
            win = serving.batch_window(b0, b1)
            print(json.dumps({
                "rate_per_s": rate, "sent": len(out), "failed": bad,
                "p50_ms": percentile(lat, 50, bad), "p95_ms": percentile(lat, 95, bad),
                "p99_ms": percentile(lat, 99, bad),
                "first_tenth_p50_ms": percentile(first, 50),
                "last_tenth_p50_ms": percentile(last, 50),
                "late_p95_ms": percentile(late, 95), "wall_s": wall,
                "batch_fill": win["batch_fill"], "rejected": win["rejected"],
                "handle_p50_ms": b1["latencyMs"]["handle"]["p50"],
                "queue_depth_after": b1["queueDepth"],
            }), flush=True)
            time.sleep(1.0)  # let a backlog drain before the next rate
        server.stop()
    finally:
        run.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
