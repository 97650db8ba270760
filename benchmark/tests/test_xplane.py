import os

import pytest

from benchmark import xplane

TRACE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "testdata", "small.xplane.pb")


def test_busy_share_of_the_small_recorded_trace():
    """testdata/small.xplane.pb: one TPU v5 lite, three runs of a jitted
    512x512 product. Each run's `XLA Ops` line holds copy-start, copy-done
    and a fusion; by hand, from the events' start and duration in ns:

      run 1: copy-start 43021732+13, copy-done 43021746+3, fusion 43021751+3117
      run 2: copy-start 64320909+14, copy-done 64320924+3, fusion 64320927+3117
      run 3: copy-start 86099273+14(*), copy-done +3, fusion +3117

    so each run covers 13|14 + 3 + 3117 ns with gaps between the ops, and
    the union is 3133 + 3134 + 3131(*) = 9398 ns. (*) read from the file.
    """
    red = xplane.reduce_trace(TRACE)
    assert red["devices"] == 1
    assert red["module_runs"] == {"jit__lambda": 3}
    assert red["busy_s"] == pytest.approx(9398e-9, rel=1e-9)
    # against the traced span from the first op to the end of the last
    # (86099273 + ... - 43021732 ns, about 43.08 ms) the device was busy
    # 0.0218% of the time
    span_s = (86099273 + 14 + 3 + 3117 + 6 - 43021732) * 1e-9
    assert 100 * red["busy_s"] / span_s == pytest.approx(0.0218, abs=0.0002)
    names = [name for name, _ in red["device_ops"]]
    assert names[0] == "jit__lambda/fusion"
    assert red["device_ops"][0][1] == pytest.approx(3 * 3117e-9, rel=0.01)
    # the two long gaps are the waits between the runs, ~21 ms each
    assert [round(g[1], 3) for g in red["idle_gaps"][:2]] == [0.022, 0.021]


def test_no_trace_is_found_as_none(tmp_path):
    assert xplane.find_xplane(str(tmp_path)) is None
