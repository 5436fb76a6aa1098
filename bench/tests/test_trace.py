"""The reduction of a profiler trace, on a small trace recorded on a TPU
v5e (``data/rank_counts.xplane.pb``: two ``bench.eval.pass`` spans, each
one ``rank_counts`` kernel call over 64 x 2,048 x 400 and one elementwise
op, then a 2 ms host sleep in ``bench.host``), and on intervals made up
here."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace

SAMPLE = Path(__file__).parent / "data" / "rank_counts.xplane.pb"
KERNEL = r'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def summary():
    return trace.summarize(str(SAMPLE), chips=1)


def test_window_and_busy_union(summary):
    assert summary.chips == 1
    assert 0.0 < summary.busy_s < summary.window_s
    ops_total = sum(s for s, _ in summary.ops.values())
    assert ops_total == pytest.approx(summary.busy_s, rel=1e-6)


def test_kernel_time_found_by_its_custom_call(summary):
    secs = summary.op_seconds(KERNEL)
    assert secs is not None and 0.0 < secs <= summary.busy_s
    assert secs > 0.5 * summary.busy_s          # the kernel dominates
    assert summary.op_seconds("no_such_op") is None


def test_no_collective_on_one_chip(summary):
    assert summary.collective_s == 0.0


def test_idle_gaps_named_by_host_span(summary):
    gaps = summary.breakdown()["idle_gaps"]
    names = [n for n, _ in gaps]
    assert names[0] == "bench.host"            # the sleep is the longest
    assert set(names) <= {"bench.host", "bench.eval.pass", "none"}
    idle = sum(s for _, s in gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s,
                                 rel=1e-6)
    assert [n for n, _, _ in summary.spans].count("bench.eval.pass") == 2


def test_breakdown_names_are_short(summary):
    ops = summary.breakdown()["device_ops"]
    assert 0 < len(ops) <= 10
    assert ops[0][0] == "_lambda_.1 custom-call tpu_custom_call"
    assert all(len(n) < 80 for n, _ in ops)


def test_hlo_names():
    text = ("%all-reduce.3 = f32[8]{0:T(256)} all-reduce(f32[8]{0} "
            "%fusion.1), replica_groups={{0,1}}")
    assert trace.is_collective(text)
    assert trace.short_name(text) == "all-reduce.3 all-reduce"
    consumer = ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), "
                "kind=kLoop")
    assert not trace.is_collective(consumer)
    start = ("%all-gather-start = (f32[4]{0}, f32[16]{0}) "
             "all-gather-start(f32[4]{0} %p), dimensions={0}")
    assert trace.is_collective(start)
    loop = ("%while.26 = (s32[]{:T(128)}, f32[4,8]{1,0:T(4,128)}) "
            "while((s32[]{:T(128)}, f32[4,8]{1,0}) %tuple.2), body=%b")
    assert trace.short_name(loop) == "while.26 while"


def test_intervals():
    assert trace.union([(3, 4), (0, 1), (0.5, 2)]) == [[0, 2], [3, 4]]
    assert trace.clip([(0, 2), (3, 5), (6, 7)], 1, 4) == [(1, 2), (3, 4)]
    assert trace.covered([(0, 1), (0.5, 1.5), (2, 3)]) == 2.5
    loop = ("while", 0.0, 10.0)
    body = [("a", 1.0, 2.0), ("b", 2.0, 4.0)]
    assert trace.leaves([loop, *body]) == body
    assert trace.leaves([("x", 0.0, 1.0), ("y", 1.0, 2.0)]) == [
        ("x", 0.0, 1.0), ("y", 1.0, 2.0)]
