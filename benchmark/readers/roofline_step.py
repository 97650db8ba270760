"""The two-tower step's share of its roofline, in percent: the least time
the chip could take for one step's *needed* work over the time a step took.

args: ``ms`` (args of the ``path`` reader, giving a step's milliseconds).
The batch and the dimension are the configuration's.
"""

from benchmark import roofline
from benchmark.readers import path


def step_work(batch: int, dim: int) -> dict:
    """One step of batch ``B`` at dimension ``D``. FLOPs: the forward
    logits GEMM and the two gradient GEMMs, ``2 B^2 D`` each (the backward's
    recomputation of the logits buys HBM traffic and is not needed work:
    ``ops/fused_ce.py``). Bytes: each of the ``2 B`` gathered rows of ``p``
    read for the forward, then ``p``, ``m`` and ``v`` of it read and written
    once by the update (six passes of ``4 D`` bytes a row: duplicates in a
    batch would need fewer), and the batch's ``2 B`` int32 ids."""
    flops = 6.0 * batch * batch * dim
    nbytes = 2.0 * batch * dim * 4 * 6 + 8.0 * batch
    return {"flops": flops, "bytes": nbytes}


def read(facts: dict, args: dict):
    ms = path.read(facts, args["ms"])
    if ms is None or ms <= 0:
        return None
    model = facts["config"]["model"]
    least, _ = roofline.least_seconds(
        step_work(model["batch"], model["dim"]), facts["device"]["kind"])
    return 100.0 * least / (ms / 1e3)
