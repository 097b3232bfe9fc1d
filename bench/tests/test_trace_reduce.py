"""The trace reduction: busy/idle union arithmetic, per-op sums and gap
ranking, on hand-made events and on a small trace recorded on a TPU v5e
(``data/tiny.xplane.pb``: a paged-attention kernel call and a matmul
chain, three rounds inside the benchmark's window mark)."""
from pathlib import Path

from bench import trace
from bench.trace import Event

DATA = Path(__file__).parent / "data" / "tiny.xplane.pb"


def ev(a, b, name="op"):
    return Event(a, b, name)


def test_busy_is_the_union_of_overlapping_ops():
    evs = [ev(0, 10), ev(5, 15), ev(20, 30), ev(22, 25), ev(40, 60)]
    assert trace.merge([(e.start, e.end) for e in evs]) == \
        [(0, 15), (20, 30), (40, 60)]
    assert trace.busy_ns(evs, 0, 100) == 15 + 10 + 20
    # clipped to the window
    assert trace.busy_ns(evs, 8, 45) == 7 + 10 + 5


def test_touching_intervals_merge():
    assert trace.merge([(0, 5), (5, 9)]) == [(0, 9)]


def test_per_op_sums_clip_to_the_window():
    evs = [ev(0, 10, "a"), ev(5, 15, "b"), ev(12, 20, "a")]
    assert trace.per_op_ns(evs, 0, 100) == {"a": 18, "b": 10}
    assert trace.per_op_ns(evs, 6, 14) == {"a": 6, "b": 8}


def test_idle_gaps_longest_first():
    evs = [ev(10, 20), ev(25, 30), ev(60, 70)]
    assert trace.idle_gaps(evs, 0, 100) == \
        [(30, 60), (70, 100), (0, 10), (20, 25)]
    assert trace.idle_gaps([], 0, 5) == [(0, 5)]


def test_gap_label_is_the_innermost_host_event():
    host = [Event(0, 100, "bench.window"), Event(40, 60, "PjitFunction")]
    assert trace.label_at(host, 50) == "PjitFunction"
    assert trace.label_at(host, 10) == "bench.window"
    assert trace.label_at(host, 200) == "no host event"



def test_parent_ops_are_left_out_of_per_op_sums():
    evs = [ev(0, 100, "while.3"), ev(10, 40, "fusion.1"),
           ev(40, 90, "kernel.2"), ev(120, 130, "copy.1")]
    assert [e.name for e in trace.leaves(evs)] == \
        ["fusion.1", "kernel.2", "copy.1"]
    assert trace.per_op_ns(evs, 0, 200) == {"fusion.1": 30, "kernel.2": 50,
                                            "copy.1": 10}
    assert trace.busy_ns(evs, 0, 200) == 110
    assert trace.op_name("%fusion.12 = bf16[2]{0} fusion(%a), kind=kLoop") \
        == "fusion.12"


def brute_busy(evs, w0, w1):
    busy = bytearray(w1 - w0)
    for e in evs:
        for t in range(max(e.start, w0), min(e.end, w1)):
            busy[t - w0] = 1
    return sum(busy)


def test_recorded_tpu_trace():
    tr = trace.load(str(DATA))
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    mark = tr.host_named("bench.traced_window")
    assert len(mark) == 1
    w0, w1 = mark[0].start, mark[0].end
    assert w1 - w0 == 12994560
    assert len(ops) == 27 and all(w0 <= e.start and e.end <= w1 for e in ops)
    busy = trace.busy_ns(ops, w0, w1)
    assert busy == brute_busy(ops, w0, w1) == 25294
    per_op = trace.per_op_ns(ops, w0, w1)
    assert per_op["paged_decode_attention.1"] == 6447 + 6379 + 6382
    assert per_op["fusion"] == 1023 + 1026 + 1023
    assert sum(per_op.values()) == sum(e.end - e.start for e in ops)
    gaps = trace.idle_gaps(ops, w0, w1)
    assert sum(b - a for a, b in gaps) == (w1 - w0) - busy
    lengths = [b - a for a, b in gaps]
    assert lengths == sorted(lengths, reverse=True)
    # The longest idle stretches lie between rounds (the host sleeps):
    # from the end of a round's matmul to the start of the next round.
    assert gaps[:2] == [(54862852 + 1026, 58680051),
                        (51129646 + 1023, 54280853)]
    assert gaps[2] == (59460741 + 1023, w1)        # after the last op
