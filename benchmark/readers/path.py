"""A number at a path into the run's facts: the instance's env, the
server's /stats.json, the client's summary or the trace reduction.

args: ``path`` (``a.b.0``), optional ``skip`` (drop the first n of a list),
``stat`` (``median`` | ``sum`` | ``mean`` of a list), ``scale``.
"""

import statistics

from benchmark.harness import dig

_STATS = {"median": statistics.median, "sum": sum, "mean": statistics.fmean}


def read(facts: dict, args: dict):
    value = dig(facts, args["path"])
    if value is None:
        return None
    if isinstance(value, list):
        value = value[int(args.get("skip", 0)):]
        if not value or "stat" not in args:
            return None
        value = _STATS[args["stat"]](value)
    return float(value) * float(args.get("scale", 1.0))
