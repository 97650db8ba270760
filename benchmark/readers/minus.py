"""One number less others: args ``from`` and ``minus`` (a list), each the
args of the ``path`` reader. Nothing if any part is missing."""

from benchmark.readers import path


def read(facts: dict, args: dict):
    total = path.read(facts, args["from"])
    parts = [path.read(facts, a) for a in args["minus"]]
    if total is None or any(p is None for p in parts):
        return None
    return total - sum(parts)
