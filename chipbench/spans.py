"""The program's own spans and scopes in a profiler trace: the stages of
the search program on the device, and the serving tier's steps on the
host, on the device trace's clock.

The program names them:

- device stages: ``jax.named_scope`` in ``_ivf_search`` (``ivf.coarse``,
  ``ivf.scan``, ``ivf.cut``, ``ivf.rerank``), which every op carries in
  its ``op_name`` path.  The device trace keeps that path in the stat
  :data:`SCOPE_STAT` of the op's event metadata, which
  ``jax.profiler.ProfileData`` does not expose, so :func:`op_scopes`
  reads it from the ``.xplane.pb`` itself.  An op whose path holds no
  ``ivf.*`` scope is :data:`UNSCOPED`.
- host steps: ``jax.profiler.TraceAnnotation`` spans of the tier, one
  ``serve.batch`` (args ``seq``, ``rows``) per batch over its children
  ``serve.form``, ``serve.dispatch``, ``serve.wait``, ``serve.d2h`` and
  ``serve.deliver``.

:func:`events` keeps what :func:`chipbench.traces.events` drops (each
host span's args, each device op's scope); :func:`reduce` works on those
records alone, so a small recorded trace can be checked without a chip.
A trace of a program without the spans or scopes reduces to nothing
for them, and the readers return ``None``.
"""
from __future__ import annotations

import bisect
import glob
import os
import statistics

from chipbench import harness, readers, traces

#: the stat of an op's event metadata that holds its ``op_name`` path,
#: e.g. ``jit(_ivf_search)/ivf.cut/jit(take_along_axis)/select_n:``
#: (read by hand on a v5e trace)
SCOPE_STAT = "tf_op"
SCOPE_PREFIX = "ivf."
SCOPES = ("ivf.coarse", "ivf.scan", "ivf.cut", "ivf.rerank")
UNSCOPED = "unscoped"
BATCH_SPAN = "serve.batch"
WAIT_SPAN = "serve.wait"
CHILD_SPANS = ("serve.form", "serve.dispatch", WAIT_SPAN, "serve.d2h",
               "serve.deliver")
#: the idle gaps the earlier lines name
N_GAPS = 5


# -- the .xplane.pb, read past what ProfileData exposes -------------------

def _varint(buf, i: int) -> tuple:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, lo: int, hi: int):
    """``(field number, value)`` of each field of the message in
    ``buf[lo:hi]``; a length-delimited value is its ``(start, end)``."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _text(buf, span: tuple) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def op_scopes(serialized: bytes) -> dict:
    """``{device plane: {op event name: op_name path}}`` from a
    serialized XSpace: the string stat :data:`SCOPE_STAT` of each event
    metadata of the device planes (``XPlane`` fields: 2 name, 4 event
    metadata, 5 stat metadata; ``XEventMetadata``: 2 name, 5 stats;
    ``XStat``: 1 metadata id, 5 string, 7 reference to an interned
    string)."""
    buf = memoryview(serialized)
    out = {}
    for num, plane in _fields(buf, 0, len(buf)):
        if num != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f, v in _fields(buf, *plane):
            if f == 2:
                name = _text(buf, v)
            elif f in (4, 5):
                entry = dict(_fields(buf, *v))
                if 2 not in entry:
                    continue
                if f == 4:
                    metas.append(entry[2])
                else:
                    sm = dict(_fields(buf, *entry[2]))
                    stat_names[entry.get(1, 0)] = _text(buf, sm[2]) \
                        if 2 in sm else ""
        if not name.startswith(traces.DEVICE_PREFIX):
            continue
        found = {}
        for meta in metas:
            ev_name, value = None, None
            for f, v in _fields(buf, *meta):
                if f == 2:
                    ev_name = _text(buf, v)
                elif f == 5:
                    st = dict(_fields(buf, *v))
                    if stat_names.get(st.get(1)) != SCOPE_STAT:
                        continue
                    if 5 in st:
                        value = _text(buf, st[5])
                    elif 7 in st:
                        value = stat_names.get(st[7])
            if ev_name is not None and value is not None:
                found.setdefault(ev_name, value)
        out[name] = found
    return out


def scope_of(op_name: str | None) -> str:
    """The ``ivf.*`` component of an op's ``op_name`` path."""
    for part in (op_name or "").split("/"):
        if part.startswith(SCOPE_PREFIX):
            return part
    return UNSCOPED


def newest_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def events(trace_dir: str) -> list:
    """Every event of the newest trace under ``trace_dir``, as
    :func:`chipbench.traces.events` gives them, and besides: ``args``
    (the event's stats) on a host event that has any, ``scope`` on each
    device op."""
    from jax.profiler import ProfileData
    path = newest_xplane(trace_dir)
    if path is None:
        return []
    with open(path, "rb") as f:
        raw = f.read()
    scopes = op_scopes(raw)
    out = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        device = plane.name.startswith(traces.DEVICE_PREFIX)
        names = scopes.get(plane.name, {})
        for line in plane.lines:
            ops = device and line.name == traces.OPS_LINE
            for ev in line.events:
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start_ns": float(ev.start_ns),
                       "dur_ns": float(ev.duration_ns)}
                if ops:
                    rec["scope"] = scope_of(names.get(ev.name))
                elif not device:
                    args = dict(ev.stats)
                    if args:
                        rec["args"] = args
                out.append(rec)
    return out


# -- the reduction -----------------------------------------------------------

def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _minus(outer: tuple, inner: list) -> list:
    """``outer`` less the intervals of ``inner`` (sorted, disjoint)."""
    out, lo = [], outer[0]
    for s, e in inner:
        if s > lo:
            out.append((lo, min(s, outer[1])))
        lo = max(lo, e)
    if outer[1] > lo:
        out.append((lo, outer[1]))
    return out


def reduce(evs: list, module: str) -> dict | None:
    """Numbers of the traced window, or ``None`` where the trace has no
    window span or no device plane.

    ``scope_s``: device seconds of the ops that start inside a ``module``
    event, by scope (:data:`UNSCOPED` for the rest), clipped to the
    window and averaged over devices, as
    :func:`chipbench.traces.reduce` counts op time.  ``batches``: the
    ``serve.batch`` spans with ``rows`` that lie inside the window, each
    as ``{"rows", <span>: ms, "host_ms"}`` for itself and its children,
    ``host_ms`` being the span less its ``serve.wait``.
    ``idle_in_tier_s``: the device's idle time (no op running) inside
    those spans and outside their ``serve.wait``, averaged over devices.  ``gaps``: the
    :data:`N_GAPS` longest idle gaps as ``[offset s, ms, where]``, where
    being the innermost ``serve.*`` span covering most of the gap, or
    "outside the tier".
    """
    window = [e for e in evs if e["name"] == traces.WINDOW_SPAN
              and not e["plane"].startswith(traces.DEVICE_PREFIX)]
    devices = sorted({e["plane"] for e in evs
                      if e["plane"].startswith(traces.DEVICE_PREFIX)
                      and e["line"] == traces.OPS_LINE})
    if not window or not devices:
        return None
    lo = window[0]["start_ns"]
    hi = lo + window[0]["dur_ns"]

    tier = [e for e in evs if e["name"].startswith("serve.")
            and not e["plane"].startswith(traces.DEVICE_PREFIX)]
    batches, busy_in = [], []
    for b in tier:
        if b["name"] != BATCH_SPAN or "rows" not in b.get("args", {}):
            continue
        s, e = b["start_ns"], b["start_ns"] + b["dur_ns"]
        if s < lo or e > hi:
            continue
        kids = [c for c in tier if c is not b and c["plane"] == b["plane"]
                and c["line"] == b["line"] and c["start_ns"] >= s
                and c["start_ns"] + c["dur_ns"] <= e]
        row = {"rows": int(b["args"]["rows"]), BATCH_SPAN: b["dur_ns"] / 1e6}
        for name in CHILD_SPANS:
            row[name] = sum(c["dur_ns"] for c in kids
                            if c["name"] == name) / 1e6
        row["host_ms"] = row[BATCH_SPAN] - row[WAIT_SPAN]
        batches.append(row)
        waits = sorted((c["start_ns"], c["start_ns"] + c["dur_ns"])
                       for c in kids if c["name"] == WAIT_SPAN)
        busy_in += _minus((s, e), waits)

    scope_s = {}
    idle_in = 0.0
    gaps = []
    for dev in devices:
        mods = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                      for e in evs if e["plane"] == dev
                      and e["line"] == traces.MODULES_LINE
                      and e["name"].startswith(module))
        starts = [m[0] for m in mods]
        ivs = []
        for e in evs:
            if e["plane"] != dev or e["line"] != traces.OPS_LINE:
                continue
            c = traces._clip(e, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            k = bisect.bisect_right(starts, e["start_ns"]) - 1
            if k >= 0 and e["start_ns"] < mods[k][1]:
                key = e.get("scope") or UNSCOPED
                scope_s[key] = scope_s.get(key, 0.0) + \
                    (c[1] - c[0]) / 1e9 / len(devices)
        merged = traces._union(ivs)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_in += _overlap(idle, sorted(busy_in)) / len(devices)
        gaps += idle
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:N_GAPS]:
        cover = [(h["dur_ns"], h["name"]) for h in tier
                 if (c := traces._clip(h, s, e)) is not None
                 and c[1] - c[0] > 0.5 * (e - s)]
        where = min(cover)[1] if cover else "outside the tier"
        named.append([(s - lo) / 1e9, (e - s) / 1e6, where])
    return {"scope_s": scope_s, "batches": batches,
            "idle_in_tier_s": idle_in / 1e9, "gaps": named}


# -- what the readers share --------------------------------------------------

def load(run) -> dict | None:
    """The reduction of the run's trace, read once per run and kept in
    ``run.info``; prints the breakdown on lines of its own the first
    time.  ``None`` where the run has no trace."""
    if "spans" in run.info:
        return run.info["spans"]
    red = None
    if run.trace is not None:
        red = reduce(events(str(harness.TRACE_DIR)),
                     run.system.SEARCH_MODULE)
    run.info["spans"] = red
    if red is not None:
        for line in describe(red, readers.search_module(run)):
            print(f"chipbench: {line}", flush=True)
    return red


def describe(red: dict, module) -> list:
    """The earlier lines: device ms a batch of each scope and the share
    of the search module they cover, the median ms a batch of each tier
    span, and the longest idle gaps with the span each fell in."""
    out = []
    if module is not None and red["scope_s"]:
        secs, count = module
        per = {k: v / count * 1e3 for k, v in red["scope_s"].items()}
        scoped = sum(per.get(k, 0.0) for k in SCOPES)
        out.append("device ms a batch by scope " + ", ".join(
            f"{k} {per.get(k, 0.0):.4f}" for k in (*SCOPES, UNSCOPED))
            + f"; the scopes cover {100 * scoped / (secs / count * 1e3):.2f}%"
            f" of the search module ({secs / count * 1e3:.4f} ms)")
    if red["batches"]:
        out.append(f"tier spans, median ms a batch over "
                   f"{len(red['batches'])} batches: " + ", ".join(
                       f"{k} {statistics.median(b[k] for b in red['batches']):.4f}"
                       for k in (BATCH_SPAN, *CHILD_SPANS, "host_ms")))
    if red["gaps"]:
        out.append("longest idle gaps (at s, ms, in): " + ", ".join(
            f"({at:.3f}, {ms:.3f}, {where})" for at, ms, where in red["gaps"]))
    return out


def scope_ms(run, scope: str):
    """Device ms a batch of the ops in ``scope``, over the search
    module's events; ``None`` where the trace shows no such op."""
    red = load(run)
    mod = readers.search_module(run)
    if red is None or mod is None:
        return None
    secs = red["scope_s"].get(scope, 0.0)
    return secs / mod[1] * 1e3 if secs > 0 else None


def tier_host_ms(run):
    """Median over the window's batches of ``serve.batch`` less its
    ``serve.wait``."""
    red = load(run)
    if red is None or not red["batches"]:
        return None
    return statistics.median(b["host_ms"] for b in red["batches"])


def idle_in_tier_ms(run):
    """Device idle time inside ``serve.batch`` and outside its
    ``serve.wait``, per batch."""
    red = load(run)
    if red is None or not red["batches"]:
        return None
    return red["idle_in_tier_s"] / len(red["batches"]) * 1e3
