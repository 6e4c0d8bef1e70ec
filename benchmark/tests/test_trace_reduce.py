"""The trace reducer against a small trace recorded on a TPU v5e
(``data/v5e_probe.xplane.pb``, made by ``record_trace.py`` on one chip:
three steps of a 512x512 matmul and a 262144-element top-k inside
``bench.step`` spans)."""

import os

import jax
import pytest

from benchmark import readers, trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "v5e_probe.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    pd = jax.profiler.ProfileData.from_file(TRACE)
    return trace_reduce.reduce(pd, chips=1, rounds=3)


def test_window_is_the_harness_spans(reduced):
    # three bench.step spans, each a step of ~0.3 ms and a 10 ms sleep
    assert 0.02 < reduced["window_s"] < 0.05
    assert reduced["chips_with_ops"] == [0]


def test_busy_and_idle_share(reduced):
    # each step runs ~0.257 ms of operations (XLA Modules line): 3 steps
    assert reduced["busy_s"] == pytest.approx(3 * 0.000257, rel=0.05)
    ctx = readers.Context(cell="x", chips=1, peaks=None, rounds=3, timed=[],
                          snaps=[], trace=reduced, tokens_traced=0,
                          reference=None, cfg={}, seq_len=0)
    idle = readers.trace_idle_share(ctx, {})
    assert 95.0 < idle < 99.5


def test_top_operation_is_the_sort(reduced):
    name, seconds = reduced["device_ops"][0]
    assert name == "sort"
    assert seconds == pytest.approx(3 * 252e-6, rel=0.02)
    assert len(reduced["device_ops"]) <= 10


def test_idle_gaps_are_labelled(reduced):
    assert 1 <= len(reduced["idle_gaps"]) <= 10
    label, seconds = reduced["idle_gaps"][0]
    assert seconds > 0.005
    assert label == "between rounds" or label.startswith("inside step of")


def test_all_reduce_reader_finds_nothing_on_one_chip(reduced):
    ctx = readers.Context(cell="x", chips=1, peaks=None, rounds=3, timed=[],
                          snaps=[], trace=reduced, tokens_traced=0,
                          reference=None, cfg={}, seq_len=0)
    assert readers.trace_op_ms_per_round(
        ctx, {"prefix": ["all-reduce"]}) is None
    sort_ms = readers.trace_op_ms_per_round(ctx, {"prefix": ["sort"]})
    assert sort_ms == pytest.approx(0.252, rel=0.02)


def test_op_name():
    assert trace_reduce.op_name(
        "%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x), "
        "replica_groups=[1,2]<=[2]") == "all-reduce.7"


def test_union_and_gaps():
    iv = [(0, 10), (5, 20), (30, 40)]
    assert trace_reduce.union_length(iv, 0, 50) == 30
    assert trace_reduce.union_length(iv, 8, 35) == 17
    assert trace_reduce.gaps(iv, 0, 50) == [(20, 30), (40, 50)]


def test_no_device_plane_is_an_error():
    class Empty:
        planes = []

    with pytest.raises(trace_reduce.NoDeviceOps):
        trace_reduce.reduce(Empty(), chips=1, rounds=1)
