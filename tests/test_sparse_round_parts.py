"""The sparse round's parts on the worker (``KVStoreDist.
push_pull_bsc_batch_async``): what a key's result is made of.

A key that comes back in one part from offset 0 completes with that
part's own arrays (views of the response's frame, the wire's int32
indices); a key in two shards is joined, the shard further in widened
to int64 and offset. Whatever the form, (values, indices) say what the
parent's always-int64, always-concatenated result said. The two
counters book every byte: ``van.payload_bytes_borrowed`` the parts that
travelled as the buffers they are, ``van.payload_bytes_copied`` the
masks, the widening and the join.
"""

import numpy as np
import pytest

from geomx_tpu import telemetry
from tests.harness import SingleTier, _parallel, count_sent_payload

N = 40          # elements of the key
BOUND = 16      # bigarray bound of the sharded tier: shards [0,20) [20,40)

# case: (servers, worker 0's (indices, values), worker 1's)
CASES = {
    "one_part": (1, ([0, 5, 17, 33], [1.0, 2.0, 3.0, 4.0]),
                 ([5, 20, 39], [10.0, 20.0, 30.0])),
    "two_shards": (2, ([0, 5, 17, 33], [1.0, 2.0, 3.0, 4.0]),
                   ([5, 20, 39], [10.0, 20.0, 30.0])),
    # every selected position lies in the shard whose offset is 20
    "offset_shard_only": (2, ([21, 25, 39], [1.0, 2.0, 3.0]),
                          ([25, 30], [10.0, 20.0])),
}


def _counter(name):
    snap = telemetry.snapshot()["counters"]
    return sum(v for k, v in snap.items() if k.startswith(name))


@pytest.mark.parametrize("index_dtype", [np.int32, np.int64])
@pytest.mark.parametrize("case", list(CASES))
def test_round_result_and_the_bytes_booked(case, index_dtype, monkeypatch):
    servers, sel0, sel1 = CASES[case]
    sent = count_sent_payload(monkeypatch)
    extra = {"bigarray_bound": BOUND} if servers > 1 else {}
    got = {}
    with SingleTier(extra=extra, num_servers=servers,
                    num_workers=2) as topo:
        def init(kv):
            kv.init(7, np.zeros(N, np.float32))
            np.testing.assert_array_equal(kv.pull(7), np.zeros(N))

        _parallel([lambda kv=kv: init(kv) for kv in topo.workers])
        telemetry.reset()
        telemetry.enable(True)
        del sent[:]

        def train(kv):
            idx, vals = (sel0, sel1)[topo.workers.index(kv)]
            fut = kv.push_pull_bsc_batch_async(
                [7], [np.asarray(vals, np.float32)],
                [np.asarray(idx, index_dtype)], slice_bytes=0)
            got[topo.workers.index(kv)] = fut.results(30)[7]

        try:
            _parallel([lambda kv=kv: train(kv) for kv in topo.workers])
            borrowed = _counter("van.payload_bytes_borrowed")
            copied = _counter("van.payload_bytes_copied")
            payload = sum(sent)
        finally:
            telemetry.reset()
            telemetry.enable(False)

    # the parent's semantics: the dense sum's exact nonzero set
    expect = np.zeros(N, np.float32)
    for idx, vals in (sel0, sel1):
        expect[idx] += np.asarray(vals, np.float32)
    nz = np.flatnonzero(expect)
    for w in (0, 1):
        vals, idx = got[w]
        assert vals.dtype == np.float32 and idx.dtype.kind == "i"
        order = np.argsort(idx, kind="stable")
        np.testing.assert_array_equal(idx[order], nz)
        np.testing.assert_array_equal(vals[order], expect[nz])
        if case == "one_part":
            # the response's own part: the wire's int32, views of the
            # frame (read-only), nothing joined, nothing widened
            assert idx.dtype == np.int32
            assert not idx.flags.writeable and not vals.flags.writeable
            np.testing.assert_array_equal(idx, nz)  # the server's order
        else:
            assert idx.dtype == np.int64

    # every part was written from its owner's memory and read as a view
    # of the frame: once a side
    assert borrowed == 2 * payload
    isz = np.dtype(index_dtype).itemsize
    if case == "one_part":
        # only a caller's int64 positions are narrowed to the wire's
        # width; that is a conversion, not a copy of a part
        assert copied == 0
    else:
        pushed = len(sel0[0]) + len(sel1[0])
        upper = sum(1 for i in nz if i >= 20)
        # the masks (values + positions, per shard, each pushed entry
        # once), the upper shard's positions widened, the two parts
        # joined (values 4, positions 4 from offset 0 and 8 from 20)
        mask = pushed * (4 + isz)
        widen = 2 * upper * 4
        lower = len(nz) - upper
        join = 2 * (len(nz) * 4 + lower * 4 + upper * 8)
        assert copied == mask + widen + join
