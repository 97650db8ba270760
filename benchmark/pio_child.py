#!/usr/bin/env python3
"""Run one `pio` command as the process that holds the chip, and say what
that process saw.

    python benchmark/pio_child.py --report FILE [--trace-dir DIR]
        [--allow-platform cpu] -- train --engine-json ...

Calls the program's own entry point (``predictionio_tpu.tools.console.main``
with the argv after ``--``) in this process, exactly as ``python -m
predictionio_tpu.tools.console`` does. Around it, and nowhere inside it:

* looks at ``jax.devices()`` first and exits 3 without running anything when
  the platform is not ``tpu`` (only the benchmark's tests pass
  ``--allow-platform``);
* counts persistent-compile-cache hits and misses (``jax.monitoring``);
* with ``--trace-dir`` wraps the call in ``jax.profiler`` (only the process
  that holds the chip can trace it);
* reads the allocator's ``memory_stats()`` of every local device five times
  a second from a thread of its own and keeps, per device, the instant at
  which live buffers and the pool reserved for programs' temporaries were
  largest *together*: a peak really seen, not the sum of two peaks;
* afterwards writes ``--report``: device platform, kind and count, that
  watch, the allocator's last ``memory_stats()``, the cache counters and
  the host-clock seconds inside the entry point (the traced span).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class MemoryWatch(threading.Thread):
    """Per device, the sample at which ``bytes_in_use + bytes_reserved`` was
    largest."""

    def __init__(self, devices, every_s: float = 0.2):
        super().__init__(daemon=True)
        self.devices, self.every_s = devices, every_s
        self.peaks = [{"occupied": 0, "in_use": 0, "reserved": 0} for _ in devices]
        self.samples = 0
        self._done = threading.Event()

    def sample(self) -> None:
        for peak, dev in zip(self.peaks, self.devices):
            stats = dev.memory_stats() or {}
            in_use = int(stats.get("bytes_in_use", 0))
            reserved = int(stats.get("bytes_reserved", 0))
            if in_use + reserved > peak["occupied"]:
                peak.update(occupied=in_use + reserved, in_use=in_use, reserved=reserved)
        self.samples += 1

    def run(self) -> None:
        while not self._done.wait(self.every_s):
            self.sample()

    def finish(self) -> dict:
        self._done.set()
        self.join()
        self.sample()
        return {"samples": self.samples, "every_s": self.every_s, "peaks": self.peaks}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", required=True)
    ap.add_argument("--trace-dir")
    ap.add_argument("--allow-platform", default="tpu")
    ap.add_argument("pio", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    pio_argv = args.pio[1:] if args.pio[:1] == ["--"] else args.pio
    sys.path.insert(0, ROOT)
    from predictionio_tpu.tools import console
    from predictionio_tpu.utils import compile_cache

    report: dict = {"argv": pio_argv, "cache_dir": compile_cache.configure()}

    def write() -> None:  # read by the parent only after this process has ended
        with open(args.report, "w") as f:
            json.dump(report, f)

    import jax

    devices = jax.local_devices()
    report["device"] = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if devices[0].platform not in args.allow_platform.split(","):
        report["refused"] = f"platform {devices[0].platform!r} is not a TPU"
        write()
        print(f"pio_child: {report['refused']}; nothing was run", file=sys.stderr)
        return 3

    counts = {"requests": 0, "hits": 0, "misses": 0}

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1

    jax.monitoring.register_event_listener(on_event)
    if args.trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # a whole train of Python calls is huge
        options.host_tracer_level = 2
        jax.profiler.start_trace(args.trace_dir, profiler_options=options)
    watch = MemoryWatch(devices)
    watch.start()
    t0 = time.monotonic()
    report["main_started"] = time.time()  # against the parent's clock at spawn
    try:
        rc = console.main(pio_argv)
    finally:
        report["main_s"] = time.monotonic() - t0
        if args.trace_dir:  # the traced span is main_s, on the host's clock
            jax.profiler.stop_trace()
        report["compile_cache"] = counts
        report["memory_watch"] = watch.finish()
        report["memory"] = [d.memory_stats() or {} for d in devices]
        write()
    return int(rc or 0)


if __name__ == "__main__":
    sys.exit(main())
