"""From a profiler trace to: the traced window, busy union, idle share,
device time by operation name. Nothing else.

``recording`` takes a trace; ``load_xplane`` reads the ``.xplane.pb``
that ``jax.profiler`` writes (with nothing but JAX) into plain lists;
``reduce`` does the arithmetic
on those lists, so the test can feed it the recorded, cut-down trace under
``benchmark/testdata/``. The arithmetic is sums over the device's "XLA
Ops" lane (the program's own roll-up of a trace went with PR 30; this is
the one copy), with the busy *union* added: events on one lane can nest (a
``while`` holds its body's operations), so a plain sum counts time twice.
"""
import contextlib
import glob
import gzip
import json
import os
import re
import shutil
import tempfile

#: the lane of a device plane that holds one event per executed HLO
OPS_LINE = "XLA Ops"
#: host annotations written by the benchmark's drivers start with this
HOST_PREFIX = "bench."
#: only the longest idle gaps of a plane are matched with host annotations
ATTRIBUTED_GAPS = 200


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_INSTANCE = re.compile(r"(\.(remat\d*|clone|\d+))+$")


def short_name(hlo_text):
    """``fusion.112`` of ``%fusion.112 = (f32[64]...) fusion(...)``: the
    trace names a device event by the whole HLO instruction."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def family(name):
    """``_ragged_flat_pallas`` of ``_ragged_flat_pallas.25``: XLA numbers
    the instances of one operation (one a layer, say), and ten instances
    of one kernel say less than the ten families that took most time."""
    return _INSTANCE.sub("", name) or name


def categorize(hlo_text):
    """``fusion.Output`` (on a TPU the fusions around a convolution or a
    dot), ``fusion.Loop``, ``fusion.Input``, ``custom-call.<target>``
    (``tpu_custom_call`` is a Mosaic kernel), else the opcode
    (``copy-done``, ``select-and-scatter`` ...). Result
    types hold brackets and upper-case ``T(..)`` tilings only, so the
    first lower-case word before a ``(`` is the opcode."""
    if ", kind=k" in hlo_text:
        return "fusion." + hlo_text.split(", kind=k", 1)[1].split(",")[0]
    if 'custom_call_target="' in hlo_text:      # a Pallas kernel is one
        return "custom-call." + hlo_text.split(
            'custom_call_target="', 1)[1].split('"')[0]
    m = _OPCODE.search(hlo_text)
    return m.group(1) if m else short_name(hlo_text).split(".")[0]


@contextlib.contextmanager
def recording():
    """Profile what runs inside (the Python tracer off: it makes traces
    of hundreds of megabytes and slows the host). Yields a dict that holds
    the loaded trace under ``"trace"`` once the block has ended; the files
    go to a temporary directory and are removed."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    held = {}
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            yield held
        finally:
            jax.profiler.stop_trace()
        held["trace"] = load_xplane(find_xplane(trace_dir))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_xplane(path):
    """``{"devices": {plane: [[name, start_ns, dur_ns, category]]},
    "host": [[name, start_ns, dur_ns]]}`` from an xplane file. ``name`` is
    the instruction's short name, ``category`` what :func:`categorize`
    makes of its text."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                rows = devices.setdefault(plane.name, [])
                for ev in line.events:
                    rows.append([short_name(ev.name), int(ev.start_ns),
                                 int(ev.duration_ns), categorize(ev.name)])
        else:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    return {"devices": devices, "host": host}


def load_recorded(path):
    """The same structure from a ``.json`` or ``.json.gz`` recording."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        return json.load(fh)


def union_ns(intervals):
    """Total length covered by ``[(start, end)]``, overlaps counted once."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, start, end):
    """``[(gap_start, gap_end)]`` inside ``[start, end]`` that no interval
    covers."""
    gaps, edge = [], start
    for s, e in sorted(intervals):
        if s > edge:
            gaps.append((edge, min(s, end)))
        edge = max(edge, e)
        if edge >= end:
            break
    if edge < end:
        gaps.append((edge, end))
    return [(a, b) for a, b in gaps if b > a]


def _self_times(rows):
    """Device time by name with nested events' time taken out of their
    parents, so that the names add up to the busy union of the lane."""
    by_name, stack = {}, []          # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            by_name[name] = by_name.get(name, 0) + self_ns

    for name, start, dur, _ in sorted(rows, key=lambda r: (r[1], -r[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return by_name


def window_ns(trace):
    """``(start, end)`` of the traced window on the trace's own clock:
    from the first of the benchmark's host annotations (``bench.*``) to
    the end of the last, since the drivers put every traced step under
    one; where a trace holds none, from the first device event to the end
    of the last."""
    host = trace.get("host", [])
    if host:
        return (min(h[1] for h in host), max(h[1] + h[2] for h in host))
    rows = [r for plane in trace["devices"].values() for r in plane]
    return (min(r[1] for r in rows), max(r[1] + r[2] for r in rows))


def clipped(rows, start, end):
    """The rows of a device plane cut to ``[start, end]``: the profiler
    runs from before the traced steps until after them, and what the
    device did then is not the window's."""
    out = []
    for name, s, d, cat in rows:
        a, b = max(s, start), min(s + d, end)
        if b > a:
            out.append([name, a, b - a, cat])
    return out


def reduce(trace):
    """The numbers the harness and the per-layer readers use, all on the
    trace's own clock.

    ``window_s`` is the traced window (:func:`window_ns`), ``busy_s`` the
    busy union of the device events inside it, averaged over the device
    planes, ``by_name``, ``by_family`` and ``by_category`` device seconds
    by operation name, by :func:`family` and by :func:`categorize`,
    averaged over the planes, ``idle_gaps`` the idle time by the
    benchmark's host annotation it fell under (``unattributed``: under
    none)."""
    if not any(trace["devices"].values()):
        return None
    first, last = window_ns(trace)
    planes = [rows for rows in (clipped(rows, first, last) for rows in
                                trace["devices"].values()) if rows]
    if not planes:
        return None
    n = len(planes)
    busy = 0
    by_name, by_family, by_cat, gap_by = {}, {}, {}, {}
    host = trace.get("host", [])
    for rows in planes:
        spans = [(r[1], r[1] + r[2]) for r in rows]
        busy += union_ns(spans)
        cats = {r[0]: r[3] for r in rows}
        for name, ns in _self_times(rows).items():
            by_name[name] = by_name.get(name, 0) + ns
            cat = cats.get(name) or "uncategorized"
            # a fusion that XLA left unnamed goes by its kind
            fam = cat if family(name) == "fusion" else family(name)
            by_family[fam] = by_family.get(fam, 0) + ns
            by_cat[cat] = by_cat.get(cat, 0) + ns
        gaps = sorted(gaps_ns(spans, first, last), key=lambda g: g[0] - g[1])
        for a, b in gaps[:ATTRIBUTED_GAPS]:
            left = b - a
            for hname, hs, hd in host:
                ov = min(b, hs + hd) - max(a, hs)
                if ov > 0:
                    gap_by[hname] = gap_by.get(hname, 0) + ov
                    left -= ov
            if left > 0:        # under none of the benchmark's annotations
                gap_by["unattributed"] = gap_by.get("unattributed", 0) + left
        rest = sum(b - a for a, b in gaps[ATTRIBUTED_GAPS:])
        if rest:
            gap_by["shorter_gaps"] = gap_by.get("shorter_gaps", 0) + rest
    window_s = (last - first) / 1e9

    def top(d):
        return sorted(([k, v / n / 1e9] for k, v in d.items()),
                      key=lambda kv: -kv[1])

    return {"busy_s": busy / n / 1e9, "window_s": window_s,
            "idle_share": 1.0 - busy / n / 1e9 / window_s,
            "by_name": dict(top(by_name)), "by_family": dict(top(by_family)),
            "by_category": dict(top(by_cat)),
            "idle_gaps": top(gap_by), "planes": n}
