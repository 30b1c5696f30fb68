"""From a profiler trace to device busy/idle, kernel time, collective
exposure and the breakdown — with `jax.profiler.ProfileData`, plus a
forty-line reader of the file's wire format for the one thing
ProfileData does not expose (`event_metadata_text`: the HLO op's
`op_name`, where the jax.named_scope path is).

A trace is read into plain tuples once (`load`), so that the reductions
are ordinary Python over
    Trace.planes = [Plane(name, lines=[Line(name, events=[Event])])]
    Event = (name, start_ns, duration_ns, text)
where `text` joins the event's string-valued stats and its metadata's
(on a TPU plane the HLO op's `op_name`, the `jax.named_scope` path). The small
recorded trace under benchmark/fixtures/ is a real `.xplane.pb`.

Device planes are the planes whose name starts with "/device:TPU:".
Their "XLA Ops" line holds one event per executed HLO op; busy time is
the union of those intervals, the window is the span from the first op
start to the last op end over all device planes (or what the caller
gives), and a gap is a maximal interval inside the window in which no
op ran on that device.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

Event = collections.namedtuple("Event", "name start dur text")
Line = collections.namedtuple("Line", "name events")
Plane = collections.namedtuple("Plane", "name lines")
Trace = collections.namedtuple("Trace", "planes")

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the program's stable jax.named_scope names, innermost wins
SCOPES = ("flash_fwd", "flash_bwd_merged", "flash_bwd_dq", "flash_bwd_dkv",
          "lora_epilogue", "fused_ce_fwd", "fused_ce_bwd_dh",
          "fused_ce_bwd_dw", "attention", "mlp", "embed", "loss",
          "optimizer")
_SCOPE_RE = re.compile(r"(?:^|[/(\s])(" + "|".join(SCOPES) + r")(?=[/)\s]|$)")
COLLECTIVE_RE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
    r"|allgather|allreduce|reducescatter|alltoall", re.I)
GAP_FLOOR_NS = 50_000       # shorter gaps are the space between ops


def find_xplane(trace_dir: str):
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def _fields(buf):
    """(field number, wire type, value) of one protobuf message; a
    length-delimited value is a memoryview slice."""
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        fno, wt = key >> 3, key & 7
        if wt == 0:
            val = shift = 0
            while True:
                b = buf[i]
                i += 1
                val |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
        elif wt == 2:
            ln = shift = 0
            while True:
                b = buf[i]
                i += 1
                ln |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            val = buf[i:i + ln]
            i += ln
        elif wt == 1:
            val, i = buf[i:i + 8], i + 8
        elif wt == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wt}")
        yield fno, wt, val


def event_metadata_text(path: str) -> dict:
    """{plane name: {event name: its metadata's strings}}.

    `ProfileData` gives an event's own stats, not the stats of its
    XEventMetadata — and on a TPU plane the HLO op's `op_name` (the
    jax.named_scope path, e.g. "jit(step)/attention/flash_fwd/...")
    lives there. This reads just that from the file's wire format
    (tsl/profiler/protobuf/xplane.proto: XSpace.planes=1; XPlane.name=2,
    event_metadata=4 (map id -> XEventMetadata), stat_metadata=5;
    XEventMetadata.name=2, display_name=4, stats=5; XStat.str_value=5,
    ref_value=7 (an id of stat_metadata, whose name=2 is the string))."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for fno, wt, plane in _fields(space):
        if fno != 1 or wt != 2:
            continue
        name, metas, stat_names = "", [], {}
        for pf, pw, val in _fields(plane):
            if pf == 2 and pw == 2:
                name = bytes(val).decode(errors="replace")
            elif pf == 4 and pw == 2:
                metas.append(val)
            elif pf == 5 and pw == 2:
                for ef, _, ev in _fields(val):
                    if ef == 2:
                        sid = sname = None
                        for sf, _, sv in _fields(ev):
                            if sf == 1:
                                sid = sv
                            elif sf == 2:
                                sname = bytes(sv).decode(errors="replace")
                        stat_names[sid] = sname or ""
        if not name.startswith(DEVICE_PREFIX):
            continue
        table = {}
        for entry in metas:
            for ef, _, ev in _fields(entry):
                if ef != 2:
                    continue
                ename, texts = "", []
                for mf, mw, mv in _fields(ev):
                    if mf == 2 and mw == 2:
                        ename = bytes(mv).decode(errors="replace")
                    elif mf == 5 and mw == 2:
                        for sf, sw, sv in _fields(mv):
                            if sf == 5 and sw == 2:
                                texts.append(bytes(sv).decode(
                                    errors="replace"))
                            elif sf == 7 and sw == 0:
                                texts.append(stat_names.get(sv, ""))
                table[ename] = " ".join(t for t in texts if t)
        out[name] = table
    return out


def load(path: str) -> Trace:
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    meta = event_metadata_text(path)
    planes = []
    for p in data.planes:
        lines = []
        table = meta.get(p.name, {})
        for ln in p.lines:
            evs = []
            for e in ln.events:
                text = " ".join(str(v) for _, v in e.stats
                                if isinstance(v, str))
                more = table.get(e.name)
                if more:
                    text = f"{text} {more}" if text else more
                evs.append(Event(e.name, int(e.start_ns),
                                 int(e.duration_ns), text))
            lines.append(Line(ln.name, evs))
        planes.append(Plane(p.name, lines))
    return Trace(planes)


def describe(trace: Trace, n: int = 3) -> list:
    """Planes, lines, event counts and a few events — what to look at by
    hand before trusting a reduction."""
    out = []
    for p in trace.planes:
        for l in p.lines:
            out.append({"plane": p.name, "line": l.name,
                        "events": len(l.events),
                        "first": [list(e) for e in l.events[:n]]})
    return out


# --------------------------- intervals --------------------------------------

def union(intervals):
    """Merge [(start, end)] into disjoint ascending intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(merged) -> int:
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """Parts of merged intervals `a` not covered by merged `b`."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def device_planes(trace: Trace):
    return [p for p in trace.planes if p.name.startswith(DEVICE_PREFIX)]


def op_name(ev: Event) -> str:
    """The HLO instruction's name: a TPU op event is named by its whole
    HLO text ("%fusion.5 = (...) fusion(...), kind=..."), elsewhere by
    the op's name."""
    m = re.match(r"%([^\s=]+)\s*=", ev.name)
    return m.group(1) if m else ev.name


CONTAINER_RE = re.compile(r"^(while|conditional|call)([.\d]*)$")


def op_events(plane: Plane, leaves_only: bool = False):
    """The executed-op events of a device plane ("XLA Ops"; a plane
    without that line gives its module executions instead).
    leaves_only drops the container ops (`while`, `conditional`, `call`),
    whose events span the ops of their bodies: busy time is a union and
    does not care, a sum by group would count the bodies twice."""
    for want in (OPS_LINE, MODULES_LINE):
        evs = [e for l in plane.lines if l.name == want for e in l.events]
        if evs:
            return [e for e in evs if not CONTAINER_RE.match(op_name(e))] \
                if leaves_only else evs
    return []


def scope_of(ev: Event):
    """Innermost known named_scope in the op's metadata, else None."""
    found = _SCOPE_RE.findall(ev.text) or _SCOPE_RE.findall(op_name(ev))
    return found[-1] if found else None


def group_of(ev: Event) -> str:
    sc = scope_of(ev)
    if sc:
        return sc
    name = op_name(ev)
    if COLLECTIVE_RE.search(name):
        return COLLECTIVE_RE.search(name).group(0).lower()
    return re.sub(r"[.\d]+$", "", name) or name


# --------------------------- reductions -------------------------------------

def busy(trace: Trace, window=None) -> dict:
    """Busy seconds per device, the window, the idle share of the worst
    device and the average busy time (the contract's `busy_s`)."""
    per = {}
    lo = hi = None
    for p in device_planes(trace):
        iv = [(e.start, e.start + e.dur) for e in op_events(p) if e.dur > 0]
        if not iv:
            continue
        per[p.name] = union(iv)
        lo = per[p.name][0][0] if lo is None else min(lo, per[p.name][0][0])
        hi = per[p.name][-1][1] if hi is None else max(hi,
                                                       per[p.name][-1][1])
    if not per:
        return {}
    if window is not None:
        lo, hi = window
    span = [[lo, hi]]
    busy_ns = {n: total(subtract(span, subtract(span, m)))
               for n, m in per.items()}
    win = max(hi - lo, 1)
    return {"window_s": win / 1e9, "window_ns": (lo, hi),
            "busy_s_by_device": {n: b / 1e9 for n, b in busy_ns.items()},
            "busy_s": sum(busy_ns.values()) / len(busy_ns) / 1e9,
            "idle_share_worst": 1.0 - min(busy_ns.values()) / win,
            "merged": per}


def op_groups(trace: Trace, top: int = 10):
    """[[group, seconds], ...]: device time by named scope where one
    exists, else by HLO op family — averaged over the chips."""
    planes = device_planes(trace)
    acc = collections.Counter()
    for p in planes:
        for e in op_events(p, leaves_only=True):
            acc[group_of(e)] += e.dur
    n = max(len(planes), 1)
    return [[g, ns / n / 1e9] for g, ns in acc.most_common(top)]


def op_time(trace: Trace, pattern: str):
    """(seconds averaged over chips, calls per chip) of the device ops
    whose name, scope or metadata matches the regex."""
    rx = re.compile(pattern)
    planes = device_planes(trace)
    ns = calls = 0
    for p in planes:
        for e in op_events(p, leaves_only=True):
            if rx.search(op_name(e)) or rx.search(e.text):
                ns += e.dur
                calls += 1
    n = max(len(planes), 1)
    return ns / n / 1e9, calls / n


def collective_exposed(trace: Trace) -> dict:
    """Per device: time a collective op runs while no compute op does.
    Returns the worst device's exposed seconds and the window."""
    b = busy(trace)
    if not b:
        return {}
    worst = 0.0
    coll_total = 0.0
    for p in device_planes(trace):
        evs = op_events(p)
        coll = union([(e.start, e.start + e.dur) for e in evs
                      if COLLECTIVE_RE.search(op_name(e)) and e.dur > 0])
        comp = union([(e.start, e.start + e.dur) for e in evs
                      if not COLLECTIVE_RE.search(op_name(e)) and e.dur > 0])
        worst = max(worst, total(subtract(coll, comp)) / 1e9)
        coll_total = max(coll_total, total(coll) / 1e9)
    return {"exposed_s": worst, "collective_s": coll_total,
            "window_s": b["window_s"]}


def host_events(trace: Trace):
    """Host-side spans (TraceAnnotation / TraceMe), sorted by start;
    the Python tracer's `$file:line fn` frames are left out."""
    evs = [e for p in trace.planes if p.name.startswith("/host:")
           for l in p.lines for e in l.events
           if e.dur > 0 and not e.name.startswith("$")]
    return sorted(evs, key=lambda e: e.start)


def idle_gaps(trace: Trace, top: int = 10):
    """[[what the host was doing, seconds], ...] over the idle gaps of
    the FIRST device longer than GAP_FLOOR_NS, summed by attribution;
    the shorter ones are summed under one name. A gap is attributed to
    the shortest host span that covers at least half of it (the most
    specific thing the host was doing), preferring the benchmark's own
    `bench.*` annotations; else to the span overlapping it most; else
    to "(no host span)"."""
    b = busy(trace)
    if not b:
        return []
    name0 = sorted(b["merged"])[0]
    lo, hi = b["window_ns"]
    gaps = subtract([[lo, hi]], b["merged"][name0])
    hosts = host_events(trace)
    starts = [e.start for e in hosts]
    acc = collections.Counter()
    for s, e in gaps:
        if e - s < GAP_FLOOR_NS:
            acc["(gaps under 50 us, between ops)"] += e - s
            continue
        j = bisect.bisect_right(starts, e)
        best, cover = None, None
        for h in hosts[max(0, j - 400):j]:
            ov = min(e, h.start + h.dur) - max(s, h.start)
            if ov <= 0:
                continue
            if ov >= 0.5 * (e - s):
                key = (not h.name.startswith("bench."), h.dur)
                if cover is None or key < cover[0]:
                    cover = (key, h.name)
            if best is None or ov > best[0]:
                best = (ov, h.name)
        acc[cover[1] if cover else best[1] if best
            else "(no host span)"] += e - s
    return [[n, ns / 1e9] for n, ns in acc.most_common(top)]


def breakdown(trace: Trace) -> dict:
    return {"device_ops": op_groups(trace), "idle_gaps": idle_gaps(trace)}
