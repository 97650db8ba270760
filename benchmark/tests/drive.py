"""Skips the harness's look for a chip and drives the rest of a run on the
CPU, for the tests only: `python drive.py ROOT WORKLOAD SEED SECONDS TRACE
[BROKEN]`. BROKEN names a fault put underneath the timed path:

* ``drop_events``: the store gets nine tenths of the events while the
  reference is handed all of them (a part of the batch left out);
* ``alter_answer``: every answer's best score is nudged by a thousandth as
  it comes back from the load generator (an answer altered).
"""

import json
import os
import sys
import time

T0 = time.monotonic()
root, workload, seed, seconds, trace = sys.argv[1:6]
broken = sys.argv[6] if len(sys.argv) > 6 else ""
sys.path.insert(0, root)

from benchmark import harness, loadgen, roofline  # noqa: E402
from benchmark.kinds import train_job  # noqa: E402

# the table of peaks has no CPU, and must not: a traced run on an unknown
# device is an error. The tests lend it one.
real_peaks = roofline.peaks
roofline.peaks = lambda kind: (
    {"flops_per_s": 1e12, "bytes_per_s": 1e11} if kind == "cpu" else real_peaks(kind))

if broken == "drop_events":
    write = train_job._write_events

    def fewer(run, app, events):
        keep = slice(0, events["rows"].size * 9 // 10)
        write(run, app, {k: v[keep] for k, v in events.items()})

    train_job._write_events = fewer
elif broken == "alter_answer":
    real_drive = loadgen.drive

    def altered(workdir, name, spec, users, due=None):
        result = real_drive(workdir, name, spec, users, due)

        def nudge(body):
            doc = json.loads(body)
            if doc.get("itemScores"):
                doc["itemScores"][0]["score"] *= 1.001
            return json.dumps(doc).encode()

        result["out"] = [(r[0], nudge(r[1]) if r[0] == 200 else r[1], *r[2:])
                         for r in result["out"]]
        return result

    loadgen.drive = altered
elif broken:
    sys.exit(f"unknown fault {broken!r}")

with open(os.path.join(root, "BENCHMARK.json")) as f:
    manifest = json.load(f)
line = harness.run_cell(root, manifest, workload, int(seed), float(seconds),
                        bool(int(trace)), T0, platforms="cpu")
print(json.dumps(line))
