import pytest

from benchmark import roofline


def test_als_sweep_work_by_hand():
    # 3 users, 2 items, 5 ratings, rank 4. Per side and rating: 2*16 + 2*4
    # = 40 FLOPs, so 2 * 5 * 40 = 400; per solved row 64/3 + 32, times 5
    # rows = 266.67. Bytes: 2 * 5 * (8 + 16) = 240, plus 5 rows * 16 = 80.
    w = roofline.als_sweep_work(users=3, items=2, ratings=5, rank=4)
    assert w["flops"] == pytest.approx(400 + 5 * (64 / 3 + 32))
    assert w["bytes"] == 240 + 80


def test_topk_work_by_hand():
    # 3 queries, 10 items, rank 4, num 2: 2*3*4*10 = 240 FLOPs; bytes: table
    # 10*4*4 = 160, user rows 3*4*4 = 48, answers 3*2*8 = 48
    w = roofline.topk_work(queries=3, items=10, rank=4, num=2)
    assert w["flops"] == 240 and w["bytes"] == 160 + 48 + 48


def test_least_seconds_says_which_bound_binds():
    t, bound = roofline.least_seconds({"flops": 197e12, "bytes": 1.0}, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "flops"
    t, bound = roofline.least_seconds({"flops": 1.0, "bytes": 819e9 * 2}, "TPU v5 lite")
    assert t == pytest.approx(2.0) and bound == "bytes"


def test_the_peaks_table_has_its_source_and_refuses_unknown_devices():
    pk = roofline.peaks("TPU v5 lite")
    assert pk["flops_per_s"] == 197e12 and pk["bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind 'TPU v9'"):
        roofline.peaks("TPU v9")
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
    with pytest.raises(KeyError):
        roofline.peaks("_source")
