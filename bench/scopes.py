"""Device seconds per program scope, and idle gaps named by program spans,
from a profiler trace.

The program names its layers through ``repro.obs``: ``jax.named_scope``
puts ``repro.<layer>`` into the ``op_name`` metadata of every op traced
inside it, and ``TraceAnnotation`` spans named ``repro.<what>`` mark its
host work.  A trace written by ``jax.profiler.trace`` carries both: the
spans on the host planes, and the HLO of every program it ran, metadata
included, in the ``/host:metadata`` plane (which ``ProfileData`` does not
expose; a small protobuf decoder reads it here).  Each device op is
matched to its program by the ``XLA Modules`` event that holds it, and
the seconds of every leaf op in the ``bench.window`` span (counted as
``bench/trace.py`` counts them) go to the innermost ``repro.*`` scope of
its ``op_name``.  An op XLA made itself (a layout copy, a fill, a fusion)
often has no ``op_name``; it takes the scope of the tensors it computes
from, else of those it feeds (``_instruction_scopes``), and the share of
time whose own ``op_name`` names the scope is reported beside.  Ops with
no scope either way go to ``unscoped``.  Idle gaps are named by the
innermost ``bench.*`` or ``repro.*`` span that covers their middle.

As a script it runs one cell as ``bench/run.py --trace 1`` does, keeps
the trace (``--out``, for TensorBoard or xprof) and prints this reduction
with the per-layer readings it gives, as one JSON line:

    python bench/scopes.py --workload transe-fb15k.eval --seed 7 [--out DIR]

``--profile-window S`` instead runs the cell's ``--trace 0`` window of S
seconds under the profiler and prints its end-to-end metrics: what
tracing costs the program when it is on.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import sys
from pathlib import Path

if __package__ in (None, ""):
    ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace as trace_lib    # noqa: E402

PREFIXES = ("bench.", "repro.")
SCOPE = re.compile(r"repro\.[A-Za-z0-9_.]*[A-Za-z0-9_]")
UNSCOPED = "unscoped"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"


# -- protobuf wire format: just enough of XSpace and HloProto ---------------

def _varint(buf: bytes, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of every field of one message; a
    length-delimited value is its bytes, a varint its integer."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _first(buf: bytes, number: int, default=b""):
    for f, value in _fields(buf):
        if f == number:
            return value
    return default


def _ints(values) -> list:
    """A repeated integer field, packed (bytes) or not (ints)."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            n, i = _varint(v, i)
            out.append(n)
    return out


def innermost(op_name: str) -> str | None:
    """The innermost ``repro.*`` scope named in an ``op_name`` (the last
    one: JAX writes scopes outermost first), else None."""
    found = SCOPE.findall(op_name)
    return found[-1] if found else None


def _instruction_scopes(hlo_proto: bytes) -> dict:
    """``{instruction name: (scope, own)}`` of one serialized ``HloProto``.

    An instruction's scope is the innermost ``repro.*`` scope of its own
    ``op_name`` (``own`` true).  XLA's own instructions (layout copies,
    fills, the fusions it forms) often carry none; such an instruction
    takes the scope of what it computes (``own`` false): a fusion that of
    its fused computation, root first; anything else that of its nearest
    operand with a scope, else of its nearest user.  ``unscoped`` where
    none is found.

    Fields read: hlo_module = 1; computations = 3, each with instructions
    = 2, id = 5 and root_id = 6; an instruction's name = 1, metadata = 7
    (op_name = 2), id = 35, operand_ids = 36, called_computation_ids =
    38."""
    inst, comps = {}, {}
    for f, comp in _fields(_first(hlo_proto, 1)):
        if f != 3:
            continue
        ids, cid, root = [], None, None
        for g, v in _fields(comp):
            if g == 2:
                d: dict = {}
                for h, w in _fields(v):
                    d.setdefault(h, []).append(w)
                iid = d.get(35, [None])[0]
                op_name = _first(d.get(7, [b""])[0], 2).decode()
                inst[iid] = (d.get(1, [b""])[0].decode(), innermost(op_name),
                             _ints(d.get(36, [])), _ints(d.get(38, [])))
                ids.append(iid)
            elif g == 5:
                cid = v
            elif g == 6:
                root = v
        comps[cid] = [root] + ids[::-1]
    direct = {}
    for iid, (_, own, _, called) in inst.items():
        inner = (inst[j][1] for c in called for j in comps.get(c, ())
                 if j in inst and inst[j][1])
        direct[iid] = own or next(inner, None)
    users: dict = {}
    for iid, (_, _, operands, _) in inst.items():
        for o in operands:
            users.setdefault(o, []).append(iid)

    def nearest(iid, step):
        queue, seen = collections.deque(step(iid)), {iid}
        while queue:
            j = queue.popleft()
            if j in seen or j not in inst:
                continue
            seen.add(j)
            if direct[j]:
                return direct[j]
            queue.extend(step(j))
        return None

    out = {}
    for iid, (name, own, _, _) in inst.items():
        scope = (direct[iid] or nearest(iid, lambda j: inst[j][2])
                 or nearest(iid, lambda j: users.get(j, ())))
        out[name] = (scope or UNSCOPED, own is not None)
    return out


def hlo_scopes(path: str) -> dict:
    """``{program name: {instruction name: (scope, own)}}`` from the HLO
    protos in a trace's ``/host:metadata`` plane (XSpace planes = 1; a
    plane's name = 2 and event metadata = 4, each entry's value = 2
    holding its name = 2 and stats = 5, the HLO proto as a stat's bytes
    value = 6)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for f, plane in _fields(space):
        if f != 1 or _first(plane, 2).decode() != METADATA_PLANE:
            continue
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _first(entry, 2)
            found = {}
            for h, stat in _fields(meta):
                if h == 5:
                    found.update(_instruction_scopes(_first(stat, 6)))
            out[_first(meta, 2).decode()] = found
    return out


# -- the reduction ------------------------------------------------------------

@dataclasses.dataclass
class Scoped:
    """Seconds, on one clock, of what the traced window held, by scope."""

    window_s: float
    busy_s: float               # device-busy union, mean over chips
    chips: int
    scopes: dict                # scope -> device seconds per chip (leaves)
    own_s: float                # of which in ops whose own op_name names it
    spans: list                 # (name, start_s, end_s) bench.* / repro.*
    gaps: list                  # (seconds, innermost span) idle gaps

    def scoped_share(self) -> float:
        """Percent of the leaf ops' device time given a ``repro.*``
        scope."""
        total = sum(self.scopes.values())
        return 100.0 * (1.0 - self.scopes.get(UNSCOPED, 0.0) / total)

    def own_share(self) -> float:
        """Percent of the leaf ops' device time in ops whose own
        ``op_name`` names a ``repro.*`` scope."""
        return 100.0 * self.own_s / sum(self.scopes.values())

    def idle_under(self, prefix: str) -> float:
        """Idle seconds whose innermost span starts with ``prefix``."""
        return sum(s for s, name in self.gaps if name.startswith(prefix))

    def breakdown(self) -> dict:
        """Device seconds per scope and idle seconds per innermost span,
        largest first."""
        idle: dict = {}
        for secs, name in self.gaps:
            idle[name] = idle.get(name, 0.0) + secs
        return {
            "scopes": [[n, s] for n, s in sorted(
                self.scopes.items(), key=lambda kv: -kv[1])],
            "idle_gaps": [[n, s] for n, s in sorted(
                idle.items(), key=lambda kv: -kv[1])[:12]]}


def _scope_of(text: str, module: str | None, names: dict) -> tuple:
    m = trace_lib.HLO.match(text)
    if module is None or not m:
        return UNSCOPED, False
    return names.get(module, {}).get(m["id"], (UNSCOPED, False))


def read(path: str, chips: int) -> Scoped:
    """Reduce the trace at ``path`` over its ``bench.window`` span."""
    from jax.profiler import ProfileData

    names = hlo_scopes(path)
    data = ProfileData.from_file(path)
    spans, devices = [], []
    for plane in data.planes:
        if trace_lib.DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend(ev for ev in trace_lib._events(line)
                             if ev[0].startswith(PREFIXES))
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    if not windows:
        raise ValueError(f"{path}: no bench.window span")
    lo, hi = windows[0]
    devices = sorted(devices, key=lambda p: int(p.name.rsplit(":", 1)[1]))
    devices = devices[:chips]
    if not devices:
        raise ValueError(f"{path}: no device plane")
    scopes: dict = {}
    own_s = 0.0
    busy = []
    for plane in devices:
        lines = {line.name: list(trace_lib._events(line))
                 for line in plane.lines}
        ops = lines.get(trace_lib.OPS_LINE, [])
        modules = sorted(lines.get(MODULES_LINE, []), key=lambda m: m[1])
        starts = [s for _, s, _ in modules]
        busy.append(trace_lib.union(
            trace_lib.clip([(s, e) for _, s, e in ops], lo, hi)))
        for text, s, e in trace_lib.leaves(ops):
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            k = bisect.bisect_right(starts, s) - 1
            module = modules[k][0] if k >= 0 and s < modules[k][2] else None
            scope, own = _scope_of(text, module, names)
            scopes[scope] = scopes.get(scope, 0.0) + d / len(devices)
            own_s += own * d / len(devices)
    spans = [(n, s - lo, e - lo) for n, s, e in spans if n != "bench.window"]
    return Scoped(
        window_s=hi - lo,
        busy_s=sum(trace_lib.covered(b) for b in busy) / len(busy),
        chips=len(devices), scopes=scopes, own_s=own_s, spans=spans,
        gaps=trace_lib._gaps(busy[0], lo, hi, spans))


def readings(cell, scoped: Scoped) -> dict:
    """The per-layer numbers the reduction gives a cell: device seconds of
    the Map, the negatives and the Reduce per traced epoch (one epoch per
    block in the ``fit`` mixes), of the filter correction and of idle time
    under ``repro.eval.*`` spans per traced pass, and the share of the
    filter's padded cells that hold a known candidate (the program's
    counters)."""
    s, work = scoped.scopes, cell.work
    out = {"scoped_share": scoped.scoped_share(),
           "own_scope_share": scoped.own_share()}
    if work.get("blocks"):
        n = work["blocks"]
        for scope, name in (("repro.map", "map_s_per_epoch"),
                            ("repro.negatives", "negatives_s_per_epoch"),
                            ("repro.reduce", "reduce_s_per_epoch")):
            out[name] = s.get(scope, 0.0) / n
    if work.get("passes"):
        n = work["passes"]
        out["filter_s_per_pass"] = s.get("repro.eval.filter", 0.0) / n
        out["eval_host_idle_s_per_pass"] = scoped.idle_under(
            "repro.eval.") / n
        from repro import obs

        c = obs.counters()
        if c.get("eval.filter_cells"):
            out["filter_useful_share"] = (
                100.0 * c["eval.filter_known"] / c["eval.filter_cells"])
    return out


# -- the script ---------------------------------------------------------------

def main(argv) -> int:
    import argparse
    import json
    import shutil
    import tempfile

    from bench import harness

    ap = argparse.ArgumentParser(prog="bench/scopes.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", help="keep the trace in this directory")
    ap.add_argument("--profile-window", type=float, metavar="S",
                    help="run the --trace 0 window of S seconds under the "
                         "profiler and print its end-to-end metrics")
    args = ap.parse_args(argv)

    import jax

    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell_spec = {c["name"]: c for c in spec["workloads"]}[args.workload]
    config = harness.load_json(harness.find("configs", cell_spec["config"]))
    mix = harness.load_json(harness.find("traffic", cell_spec["traffic"]))
    try:
        devices = harness.check_devices(cell_spec["chips"])
    except harness.NoChip as exc:
        print(f"bench/scopes.py: {exc} - nothing was run", file=sys.stderr)
        return 3
    harness.enable_cache()
    if config.get("matmul_precision"):
        jax.config.update("jax_default_matmul_precision",
                          config["matmul_precision"])
    cell = harness.Cell(args.workload, config, mix, cell_spec["chips"],
                        args.seed, devices)
    entry = harness.load_module(
        harness.find("entries", mix["entry"])).Entry(cell)
    entry.setup()
    out = args.out or tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        if args.profile_window:
            with jax.profiler.trace(out):
                window = entry.window(args.profile_window)
            result = {"metrics": window["metrics"], "log": window["log"]}
        else:
            harness.traced(entry.trace_window, out)
            path = trace_lib.find_xplane(out)
            scoped = read(path, cell.chips)
            summary = trace_lib.summarize(path, chips=cell.chips)
            result = {"work": cell.work, "busy_s": scoped.busy_s,
                      "window_s": scoped.window_s,
                      "readings": readings(cell, scoped),
                      **scoped.breakdown(),
                      "device_ops": summary.breakdown()["device_ops"]}
    finally:
        if not args.out:
            shutil.rmtree(out, ignore_errors=True)
    d0 = devices[0]
    result["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                        "count": len(devices)}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
