"""The benchmark's reduction of a trace by program scope and span
(``bench/scopes.py``), on two traces recorded on a TPU v5e and on data
made up here; and the readings ``bench/trace.py`` and the accepted
per-layer metrics give the committed ``rank_counts.xplane.pb`` trace,
pinned.

``data/scopes.xplane.pb`` (``bench/tests/make_scopes_trace.py``): one
jitted scan under ``repro.map`` and ``repro.reduce`` scopes inside a
``repro.fit.block`` span, then a 5 ms host sleep in ``repro.fit.boundary``.
"""
from pathlib import Path

import pytest

from bench import flops, harness, scopes
from bench import trace as trace_lib

DATA = Path(harness.__file__).resolve().parent / "tests" / "data"
RANK_COUNTS = DATA / "rank_counts.xplane.pb"
SCOPES = DATA / "scopes.xplane.pb"


# -- the accepted readings of rank_counts.xplane.pb, unchanged ----------------

def test_rank_counts_summary_unchanged():
    s = trace_lib.summarize(str(RANK_COUNTS), chips=1)
    assert (s.window_s, s.busy_s, s.collective_s) == (
        0.008819258999999996, 0.0002302470000000098, 0.0)
    assert (len(s.ops), len(s.spans), len(s.gaps)) == (17, 4, 18)
    b = s.breakdown()
    assert b["device_ops"][:3] == [
        ["_lambda_.1 custom-call tpu_custom_call", 0.00016935199999999762],
        ["sine_multiply_fusion fusion", 4.373600000000255e-05],
        ["dynamic-update-slice.6 dynamic-update-slice",
         7.5530000000004205e-06]]
    assert len(b["device_ops"]) == 10
    assert b["idle_gaps"] == [["bench.host", 0.005324881999999989],
                              ["bench.eval.pass", 0.003264129999999997]]


def test_rank_counts_per_layer_unchanged():
    s = trace_lib.summarize(str(RANK_COUNTS), chips=1)
    cell = harness.Cell(
        "transe-fb15k.eval",
        harness.load_json(harness.find("configs", "transe-fb15k")),
        harness.load_json(harness.find("traffic", "eval")), 1, 0, None)
    cell.work = {"passes": 2, "test_triples": 128, "seconds": 1.0}
    ctx = {"cell": cell, "summary": s, "flops": flops,
           "peak": harness.device_peaks("TPU v5 lite")}
    read = {m: harness.load_module(harness.find("metrics", m)).read(ctx)
            for m in ("device_idle.eval", "rank_topk_roofline", "eval_mfu")}
    assert read == {"device_idle.eval": 97.38927045911669,
                    "rank_topk_roofline": 17.543888184920128,
                    "eval_mfu": 0.2762928522764406}


def test_rank_counts_has_no_program_scope():
    """A trace of code without scopes: every op unscoped, the same busy
    time and the same gaps as ``bench/trace.py`` finds."""
    s = trace_lib.summarize(str(RANK_COUNTS), chips=1)
    r = scopes.read(str(RANK_COUNTS), chips=1)
    assert set(r.scopes) == {scopes.UNSCOPED}
    assert r.scopes[scopes.UNSCOPED] == pytest.approx(s.busy_s, rel=1e-9)
    assert (r.window_s, r.busy_s) == (s.window_s, s.busy_s)
    assert r.gaps == s.gaps
    assert r.scoped_share() == 0.0 and r.own_s == 0.0
    names = scopes.hlo_scopes(str(RANK_COUNTS))
    assert len(names) == 2 and all(names.values())


# -- the v5e trace with the program's scopes and spans ------------------------

@pytest.fixture(scope="module")
def scoped():
    return scopes.read(str(SCOPES), chips=1)


def test_scopes_attribute_the_busy_time(scoped):
    # the leaf ops cover the busy time but for the scan's own loop control
    total = sum(scoped.scopes.values())
    assert 0.999 * scoped.busy_s <= total <= scoped.busy_s
    assert scoped.scoped_share() >= 95.0 and scoped.own_share() >= 90.0
    assert set(scoped.scopes) <= {"repro.map", "repro.reduce",
                                  scopes.UNSCOPED}
    assert scoped.scopes["repro.map"] > 0 and scoped.scopes["repro.reduce"] > 0


def test_sleep_gap_named_by_its_program_span(scoped):
    longest = max(scoped.gaps)
    assert longest[1] == "repro.fit.boundary"
    assert longest[0] >= 0.004                  # the 5 ms sleep
    names = {n for n, _, _ in scoped.spans}
    assert {"repro.fit.block", "repro.fit.boundary"} <= names
    assert scoped.idle_under("repro.fit.") >= longest[0]
    b = scoped.breakdown()
    assert b["idle_gaps"][0][0] == "repro.fit.boundary"
    assert [n for n, _ in b["scopes"]][0] in ("repro.map", "repro.reduce")


# -- made-up data ------------------------------------------------------------

@pytest.mark.parametrize("op_name, scope", [
    ("jit(f)/repro.map/mul", "repro.map"),
    ("jit(f)/repro.reduce/repro.reduce.exchange/all-gather",
     "repro.reduce.exchange"),
    ("jit(f)/repro.map/transpose(jvp(repro.negatives))/add",
     "repro.negatives"),
    ("jit(f)/while/body/add", None),
    ("", None),
])
def test_innermost(op_name, scope):
    assert scopes.innermost(op_name) == scope


def test_gaps_named_by_innermost_program_span():
    busy = [[0.0, 1.0], [3.0, 4.0], [6.0, 7.0]]
    spans = [("bench.eval.pass", 0.0, 7.0), ("repro.eval.layout", 1.0, 2.5),
             ("repro.eval.classify_host", 4.5, 5.9)]
    gaps = trace_lib._gaps(busy, 0.0, 7.0, spans)
    assert gaps == [(2.0, "repro.eval.layout"),
                    (2.0, "repro.eval.classify_host")]
    r = scopes.Scoped(window_s=7.0, busy_s=3.0, chips=1,
                      scopes={"repro.eval.scan": 2.0,
                              "repro.eval.filter": 0.5,
                              scopes.UNSCOPED: 0.5},
                      own_s=1.5, spans=spans, gaps=gaps)
    assert r.idle_under("repro.eval.") == 4.0
    assert r.idle_under("bench.") == 0.0
    assert r.scoped_share() == pytest.approx(100.0 * 2.5 / 3.0)
    assert r.own_share() == pytest.approx(50.0)
    assert r.breakdown()["scopes"] == [["repro.eval.scan", 2.0],
                                       ["repro.eval.filter", 0.5],
                                       [scopes.UNSCOPED, 0.5]]


def _varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _msg(*fields) -> bytes:
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _inst(iid, name, op_name="", operands=(), called=()):
    fields = [(1, name), (35, iid)]
    if op_name:
        fields.append((7, _msg((2, op_name))))
    if operands:
        fields.append((36, b"".join(_varint(o) for o in operands)))
    if called:
        fields.append((38, b"".join(_varint(c) for c in called)))
    return _msg(*fields)


def test_hlo_scopes_from_the_metadata_plane(tmp_path):
    """Own op_names first; an op without one takes its fused
    computation's, its nearest operand's, else its nearest user's scope.
    """
    fused = _msg((1, "fused"), (5, 2), (6, 11),
                 (2, _inst(10, "p0")),
                 (2, _inst(11, "mul.1", "jit(f)/repro.map/mul", (10,))))
    entry = _msg((1, "main"), (5, 1), (6, 5),
                 (2, _inst(1, "param.1")),
                 (2, _inst(2, "copy.1", operands=(1,))),
                 (2, _inst(3, "fusion.3", operands=(2,), called=(2,))),
                 (2, _inst(4, "copy.2", operands=(3,))),
                 (2, _inst(5, "sub.1", "jit(f)/repro.reduce/sub", (4,))),
                 (2, _inst(6, "constant.1")))
    hlo = _msg((1, _msg((1, "jit_f"), (3, entry), (3, fused))))
    meta = _msg((1, 7), (2, "jit_f(42)"), (5, _msg((1, 1), (6, hlo))))
    plane = _msg((1, 3), (2, "/host:metadata"), (4, _msg((1, 7), (2, meta))))
    other = _msg((1, 1), (2, "/device:TPU:0"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, other), (1, plane)))
    assert scopes.hlo_scopes(str(path)) == {"jit_f(42)": {
        "param.1": ("repro.map", False),      # its user's user's fusion
        "copy.1": ("repro.map", False),       # its user, the fusion
        "fusion.3": ("repro.map", False),     # its fused computation
        "copy.2": ("repro.map", False),       # its operand, the fusion
        "sub.1": ("repro.reduce", True),
        "constant.1": (scopes.UNSCOPED, False),
        "p0": ("repro.map", False),           # its user
        "mul.1": ("repro.map", True)}}
