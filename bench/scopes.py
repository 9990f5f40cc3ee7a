"""Device time by named scope and by kernel, from the raw profiler trace.

On a TPU the trace keeps each op's name stack (``jax.named_scope``s and
the transformations around them, e.g. ``jit(train_step)/transpose(jvp())
/while/body/closed_call/checkpoint/rematted_computation/attention/...``)
as the ``tf_op`` stat of the op's *event metadata* on the device plane
``/device:TPU:<n>``, line ``XLA Ops``; the event itself carries only its
time. ``jax.profiler.ProfileData`` shows an event's own stats alone, so
this module reads the device planes from the ``.xplane.pb`` protobuf
itself, field by number, and only the fields it needs. The traced window
is the host span ``bench.window``, as in ``trace_reduce``.

Each op's self time (``trace_reduce.self_times``) goes to the innermost
component of its name stack that is one of ``SCOPES``, with wrappers
such as ``jvp(...)``, ``transpose(...)`` and remat's
``checkpoint/rematted_computation`` seen through, or to ``NO_SCOPE``.
Only ops inside whole runs of the step's own module in the window count,
and totals are per run of that module.

    python3 -m bench.scopes [<trace dir or .xplane.pb>] [--module train_step]

prints each scope's milliseconds per step, the ops under no scope, and
what no op's self time covers.
"""
from __future__ import annotations

import argparse
import bisect
import os
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

if __package__ in (None, ""):          # run as a script
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import trace_reduce  # noqa: E402

SCOPES = ("attention", "mlp", "unembed", "loss", "optimizer", "sampler")
NO_SCOPE = "(no scope)"
_WRAPPED = re.compile(r"^[\w.-]+\((.*)\)$")


# ---------------------------------------------------------------------------
# The protobuf, by field number (tsl/profiler/protobuf/xplane.proto):
# XSpace.planes 1; XPlane name 2, lines 3, event_metadata 4 (map),
# stat_metadata 5 (map); XLine name 2, timestamp_ns 3, events 4;
# XEvent metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata id 1,
# name 2, display_name 4, stats 5; XStatMetadata id 1, name 2;
# XStat metadata_id 1, str_value 5, ref_value 7.
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message: an int for a varint, a
    slice of ``buf`` for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entry(v) -> Tuple[int, object]:
    key, value = 0, b""
    for f, x in _fields(v):
        if f == 1:
            key = x
        elif f == 2:
            value = x
    return key, value


def _device_plane(buf) -> Optional[Dict]:
    """A device plane's ``XLA Modules`` and ``XLA Ops`` events as
    (name, start_ns, duration_ns, tf_op), or None for another plane."""
    lines, metas, stat_names = [], {}, {}
    for f, v in _fields(buf):
        if f == 2 and not trace_reduce.DEVICE_PLANE.match(_text(v)):
            return None
        if f == 3:
            lines.append(v)
        elif f == 4:
            k, md = _map_entry(v)
            metas[k] = md
        elif f == 5:
            k, sm = _map_entry(v)
            stat_names[k] = next((_text(x) for g, x in _fields(sm)
                                  if g == 2), "")
    tf_op_ids = {k for k, n in stat_names.items() if n == "tf_op"}
    names: Dict[int, Tuple[str, str]] = {}
    for k, md in metas.items():
        name = tf_op = ""
        for f, v in _fields(md):
            if f == 2:
                name = _text(v)
            elif f == 5:
                sid, val = 0, ""
                for g, x in _fields(v):
                    if g == 1:
                        sid = x
                    elif g == 5:
                        val = _text(x)
                    elif g == 7:
                        val = stat_names.get(x, "")
                if sid in tf_op_ids:
                    tf_op = val
        names[k] = (name, tf_op)
    out: Dict[str, List] = {}
    for ln in lines:
        lname, ts, evs = "", 0, []
        for f, v in _fields(ln):
            if f == 2:
                lname = _text(v)
            elif f == 3:
                ts = v
            elif f == 4:
                evs.append(v)
        if lname not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
            continue
        rows = out.setdefault(lname, [])
        for ev in evs:
            mid = off = dur = 0
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = v
                elif f == 3:
                    dur = v
            name, tf_op = names.get(mid, ("", ""))
            rows.append((name, ts + off / 1e3, dur / 1e3, tf_op))
    return out


def device_events(path: str) -> List[Dict]:
    """Per device plane: ``modules`` and ``ops`` events as
    (name, start_ns, duration_ns, tf_op)."""
    with open(path, "rb") as fh:
        buf = memoryview(fh.read())
    planes = []
    for f, v in _fields(buf):
        if f == 1:
            p = _device_plane(v)
            if p is not None:
                planes.append({"modules": p.get(trace_reduce.MODULES_LINE, []),
                               "ops": p.get(trace_reduce.OPS_LINE, [])})
    return planes


def window(path: str) -> Optional[Tuple[float, float]]:
    """Start and end of the host span ``bench.window``, on the device
    events' clock (both count from the profile's start)."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_reduce.WINDOW_SPAN:
                    return float(e.start_ns), float(e.end_ns)
    return None


# ---------------------------------------------------------------------------
# Attribution
# ---------------------------------------------------------------------------

def scope_of(tf_op: str, scopes=SCOPES) -> str:
    """The innermost component of a name stack that is one of
    ``scopes``, seen through wrappers: ``transpose(jvp(mlp))`` is
    ``mlp``."""
    found = NO_SCOPE
    for part in tf_op.split("/"):
        m = _WRAPPED.match(part)
        while m is not None:
            part = m.group(1)
            m = _WRAPPED.match(part)
        if part in scopes:
            found = part
    return found


class StepOps:
    """The ops of whole runs of one module in a traced window, each with
    its self time (seconds) and name stack."""

    def __init__(self, planes: List[Dict], module: str,
                 win: Optional[Tuple[float, float]]) -> None:
        rx = re.compile(module)
        self.runs = 0
        self.module_s = 0.0
        self.ops: List[Tuple[str, float, str]] = []     # op, self s, tf_op
        for p in planes:
            runs = sorted((s, s + d) for name, s, d, _ in p["modules"]
                          if rx.search(trace_reduce.module_name(name)))
            if win is not None:
                runs = [(s, e) for s, e in runs if s >= win[0] and e <= win[1]]
            self.runs += len(runs)
            self.module_s += sum(e - s for s, e in runs) / 1e9
            starts = [s for s, _ in runs]
            inside = []
            for name, s, d, tf_op in p["ops"]:
                k = bisect.bisect_right(starts, s) - 1
                if k >= 0 and s <= runs[k][1]:
                    inside.append((name, s, d, tf_op))
            selfs = trace_reduce.self_times(
                [("", trace_reduce.op_name(n), s, d) for n, s, d, _ in inside])
            self.ops += [(op, secs, ev[3])
                         for (_, op, secs), ev in zip(selfs, inside)]

    def per_run_ms(self, seconds: float) -> Optional[float]:
        return 1e3 * seconds / self.runs if self.runs else None

    def by_scope(self, scopes=SCOPES) -> Dict[str, float]:
        """Self seconds per scope (and ``NO_SCOPE``), over all runs."""
        out = dict.fromkeys(tuple(scopes) + (NO_SCOPE,), 0.0)
        for _, secs, tf_op in self.ops:
            out[scope_of(tf_op, scopes)] += secs
        return out

    def kernel_s(self, kernel: str) -> float:
        """Self seconds of the ops named ``<kernel>`` or ``<kernel>.<n>``
        (a ``pallas_call``'s ``name=``), over all runs."""
        rx = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
        return sum(secs for op, secs, _ in self.ops if rx.match(op))


def step_ops(path: str, module: str) -> StepOps:
    return StepOps(device_events(path), module, window(path))


def traced_step(drv, module: str) -> Optional[StepOps]:
    """The run's traced window, for the per-layer readers: None in an
    untraced run or where the module never ran whole in the window."""
    if getattr(drv, "trace", None) is None:
        return None
    from bench import harness
    path = trace_reduce.newest_xplane(str(harness.OUT / "trace"))
    if path is None:
        return None
    st = step_ops(path, module)
    return st if st.runs else None


def scope_ms(drv, module: str, scope: str) -> Optional[float]:
    """Device ms per run of ``module`` under ``scope``; None where no op
    of the module carries that scope (a program without the scope)."""
    st = traced_step(drv, module)
    if st is None:
        return None
    secs = st.by_scope()[scope]
    return st.per_run_ms(secs) if secs > 0 else None


def kernel_ms(drv, module: str, kernel: str) -> Optional[float]:
    st = traced_step(drv, module)
    if st is None:
        return None
    secs = st.kernel_s(kernel)
    return st.per_run_ms(secs) if secs > 0 else None


# ---------------------------------------------------------------------------

def report(st: StepOps, top: int = 0) -> List[str]:
    """Each scope's ms per run and share of the module's time, what no
    op's self time covers, and the ``top`` ops with most self time."""
    step_ms = st.per_run_ms(st.module_s)
    lines = [f"runs {st.runs}, module {step_ms:.3f} ms per run"]
    by = st.by_scope()
    for name, secs in sorted(by.items(), key=lambda kv: -kv[1]):
        ms = st.per_run_ms(secs)
        lines.append(f"{name:>12} {ms:10.3f} ms  {100 * ms / step_ms:6.2f} %")
    left = st.per_run_ms(st.module_s - sum(by.values()))
    lines.append(f"{'(no op)':>12} {left:10.3f} ms  "
                 f"{100 * left / step_ms:6.2f} %")
    per_op: Dict[str, List] = {}
    for op, secs, tf_op in st.ops:
        per_op.setdefault(op, [0.0, tf_op])[0] += secs
    for op, (secs, tf_op) in sorted(per_op.items(),
                                    key=lambda kv: -kv[1][0])[:top]:
        lines.append(f"{st.per_run_ms(secs):10.3f} ms  {op}  "
                     f"[{scope_of(tf_op)}] {tf_op[:120]}")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".bench_out", "trace"))
    ap.add_argument("--module", default=r"train_step|serve_step")
    ap.add_argument("--top", type=int, default=0,
                    help="also list the ops with most self time")
    args = ap.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = trace_reduce.newest_xplane(path)
    if path is None:
        print("no .xplane.pb found", file=sys.stderr)
        return 1
    st = step_ops(path, args.module)
    if not st.runs:
        print(f"no whole run of {args.module!r} in the window",
              file=sys.stderr)
        return 1
    print("\n".join(report(st, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
