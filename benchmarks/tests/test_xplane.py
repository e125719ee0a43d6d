"""The trace reduction on a small recorded device trace.

`data/small.xplane.pb` was recorded on a TPU v5 lite by
`record_trace.py` (PR 24): three rounds of four runs of one jitted conv
program inside a `train` annotation, each followed by a 20 ms host sleep
inside a `journal` annotation.
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import xplane  # noqa: E402

TRACE = os.path.join(HERE, "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return xplane.reduce_trace(TRACE, {"train", "journal"})


def test_union_merges_nested_and_overlapping():
    assert xplane._union([(0, 10), (2, 3), (9, 12), (20, 21)]) == [[0, 12], [20, 21]]


def test_self_time_excludes_children():
    # a 100 ns while holding two 30 ns fusions: 40 ns of its own
    evs = [(0.0, 100.0, "while"), (10.0, 40.0, "fusion.1"), (50.0, 80.0, "fusion.1")]
    t = xplane._self_times(evs)
    assert t["while"] == pytest.approx(40e-9)
    assert t["fusion.1"] == pytest.approx(60e-9)


def test_busy_union_and_idle_share(reduced):
    assert reduced["chips"] == 1
    # 12 runs of a 0.114 ms program (XLA Modules line, read by hand)
    assert reduced["busy_s"] == pytest.approx(12 * 114.05e-6, rel=0.02)
    # three rounds, each with a 20 ms sleep: the window is mostly idle
    assert 0.06 < reduced["window_s"] < 0.2
    idle = 1.0 - reduced["busy_s"] / reduced["window_s"]
    assert 0.9 < idle < 1.0


def test_top_operations(reduced):
    ops = dict(reduced["device_ops"])
    assert len(reduced["device_ops"]) <= 10
    # the conv fusion is where the device time goes (66.6 us of each 114 us run)
    name, seconds = reduced["device_ops"][0]
    assert name.startswith("fusion")
    assert seconds == pytest.approx(12 * 66.6e-6, rel=0.05)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"], rel=0.01)


def test_idle_gaps_are_named_after_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    # the sleeps sit inside the `journal` annotation
    assert gaps["journal"] == pytest.approx(3 * 0.02, rel=0.25)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=0.01
    )


def test_a_trace_with_no_device_plane_reduces_to_nothing(tmp_path):
    import jax

    d = str(tmp_path / "p")
    jax.profiler.start_trace(d)
    jax.numpy.ones((8,)).block_until_ready()
    jax.profiler.stop_trace()
    pb = xplane.find_xplane(d)
    assert pb is not None
    assert xplane.reduce_trace(pb, {"train"}) == {}
