"""A kernel's share of its roofline, in percent: the least time the chip
could take for the work the algorithm needs (benchmark/roofline.py, from
the cell's shapes) over the time measured.

args: ``work`` (``als_sweep`` | ``topk``), ``seconds`` (args of the ``path``
reader, giving the measured time in seconds), and for ``topk`` ``queries``
(path args: the queries really in a batch).
"""

from benchmark import roofline
from benchmark.readers import path


def read(facts: dict, args: dict):
    seconds = path.read(facts, args["seconds"])
    if seconds is None or seconds <= 0:
        return None
    kind = facts["device"]["kind"]
    if args["work"] == "als_sweep":
        shape, model = facts["config"]["shape"], facts["config"]["model"]
        work = roofline.als_sweep_work(
            shape["users"], shape["items"], shape["ratings"], model["rank"])
    elif args["work"] == "topk":
        queries = path.read(facts, args["queries"])
        if queries is None:
            return None
        s = facts["shape"]
        work = roofline.topk_work(queries, s["items"], s["rank"], s["num"])
    else:
        raise ValueError(f"unknown work {args['work']!r}")
    least, _ = roofline.least_seconds(work, kind)
    return 100.0 * least / seconds
