"""From a profiler trace to numbers: device busy time, per-module and
per-op device time, and the longest idle gaps with what the host was
doing in them.

:func:`events` flattens an ``.xplane.pb`` into plain records; everything
after that (:func:`reduce`) works on those records, so a small recorded
trace can be checked without a chip.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

#: the host span the harness opens at the window's start and closes at
#: its end; the reduction clips every device event to it
WINDOW_SPAN = "chipbench_window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def events(trace_dir: str) -> list:
    """Every event of the newest trace under ``trace_dir``, as
    ``{"plane", "line", "name", "start_ns", "dur_ns"}``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": float(ev.start_ns),
                            "dur_ns": float(ev.duration_ns)})
    return out


def _union(intervals: list) -> list:
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(ev: dict, lo: float, hi: float):
    s = max(ev["start_ns"], lo)
    e = min(ev["start_ns"] + ev["dur_ns"], hi)
    return (s, e) if e > s else None


def reduce(evs: list, *, top: int = 10) -> dict | None:
    """Numbers of the traced window, or ``None`` where the trace has no
    window span or no device plane.

    ``busy_s``: union of the device's op intervals in the window, averaged
    over devices.  ``modules``/``ops``: name -> [device seconds, count]
    (count of events that start in the window), averaged over devices.
    ``idle_gaps``: the longest gaps with no op on the device, each named
    by the shortest host event that covers most of it.
    """
    spans = [e for e in evs if e["name"] == WINDOW_SPAN
             and not e["plane"].startswith(DEVICE_PREFIX)]
    devices = sorted({e["plane"] for e in evs
                      if e["plane"].startswith(DEVICE_PREFIX)
                      and e["line"] == OPS_LINE})
    if not spans or not devices:
        return None
    lo = spans[0]["start_ns"]
    hi = lo + spans[0]["dur_ns"]
    busy = 0.0
    modules = defaultdict(lambda: [0.0, 0])
    ops = defaultdict(lambda: [0.0, 0])
    gaps = []
    for dev in devices:
        ivs = []
        for e in evs:
            if e["plane"] != dev or e["line"] not in (OPS_LINE, MODULES_LINE):
                continue
            c = _clip(e, lo, hi)
            if c is None:
                continue
            acc = ops if e["line"] == OPS_LINE else modules
            acc[e["name"]][0] += (c[1] - c[0]) / len(devices)
            acc[e["name"]][1] += (lo <= e["start_ns"] < hi) / len(devices)
            if e["line"] == OPS_LINE:
                ivs.append(c)
        merged = _union(ivs)
        busy += sum(e - s for s, e in merged) / len(devices)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    host = [e for e in evs if not e["plane"].startswith(DEVICE_PREFIX)
            and e["name"] != WINDOW_SPAN and e["dur_ns"] > 0]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        over = [(c[1] - c[0], h) for h in host
                if (c := _clip(h, s, e)) is not None]
        most = max((o for o, _ in over), default=0.0)
        # the most specific host event that covers most of the gap
        cover = [h for o, h in over if o >= 0.9 * most and most > 0]
        best = min(cover, key=lambda h: h["dur_ns"])["name"] if cover \
            else "no host event"
        named.append([best, (e - s) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9,
        "modules": {k: [v[0] / 1e9, v[1]] for k, v in modules.items()},
        "ops": {k: [v[0] / 1e9, v[1]] for k, v in ops.items()},
        "idle_gaps": named,
    }


def top_ops(reduced: dict, n: int = 10) -> list:
    """The device ops that took most time, ``[[name, seconds], ...]``."""
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1][0])
    return [[name[:160], v[0]] for name, v in ops[:n]]


def matching(table: dict, prefixes) -> list:
    """Entries of ``table`` whose instruction name (an op's text before
    `` = ``, a module's whole name) starts with one of ``prefixes``."""
    return [v for k, v in table.items()
            if k.split(" = ", 1)[0].startswith(tuple(prefixes))]
