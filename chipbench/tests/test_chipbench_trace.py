"""The reduction from trace events to busy time, module and op time, and
named idle gaps."""
import json
from pathlib import Path

from chipbench import traces

DEV = "/device:TPU:0"


def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_reduce_clips_to_the_window_and_unions_ops():
    evs = [
        _ev("/host:CPU", "python", traces.WINDOW_SPAN, 1000, 10_000),
        _ev(DEV, traces.MODULES_LINE, "jit__ivf_search", 500, 3000),
        _ev(DEV, traces.OPS_LINE, "fusion.1", 500, 1500),      # clipped
        _ev(DEV, traces.OPS_LINE, "_kernel", 1800, 1700),      # overlaps
        _ev(DEV, traces.OPS_LINE, "fusion.2", 6000, 1200),
        _ev(DEV, traces.OPS_LINE, "copy.3", 10_500, 2000),     # clipped
        _ev("/host:CPU", "python", "np.stack", 3600, 2000),
        _ev("/host:CPU", "python", "wait", 7200, 3000),
    ]
    red = traces.reduce(evs)
    assert red["window_s"] == 10_000 / 1e9
    # busy: [1000,3500] + [6000,7200] + [10500,11000]
    assert red["busy_s"] == (2500 + 1200 + 500) / 1e9
    assert red["modules"]["jit__ivf_search"] == [2500 / 1e9, 0]
    assert red["ops"]["fusion.1"] == [1000 / 1e9, 0]
    assert red["ops"]["_kernel"] == [1700 / 1e9, 1]
    gaps = red["idle_gaps"]
    assert gaps[0] == ["wait", 3300 / 1e9]
    assert gaps[1] == ["np.stack", 2500 / 1e9]
    assert traces.top_ops(red, 2) == [["_kernel", 1700 / 1e9],
                                      ["fusion.2", 1200 / 1e9]]


def test_reduce_without_window_or_device_reads_nothing():
    assert traces.reduce([_ev(DEV, traces.OPS_LINE, "x", 0, 10)]) is None
    assert traces.reduce([_ev("/host:CPU", "python", traces.WINDOW_SPAN,
                              0, 10)]) is None


def test_recorded_chip_trace_matches_a_sweep_line_count():
    """A 150 ms slice of a trace recorded on a TPU v5e serving
    sift1m.closed: busy time against a sweep-line count of the same ops,
    and the search module and coarse kernels found by their names."""
    from chipbench.systems import ivf
    evs = json.loads((Path(__file__).parent / "fixtures"
                      / "trace_v5e_sift1m.json").read_text())
    red = traces.reduce(evs)
    span = next(e for e in evs if e["name"] == traces.WINDOW_SPAN)
    lo, hi = span["start_ns"], span["start_ns"] + span["dur_ns"]
    edges = []
    for e in evs:
        if e["plane"] == DEV and e["line"] == traces.OPS_LINE:
            s, t = max(e["start_ns"], lo), min(e["start_ns"] + e["dur_ns"], hi)
            if t > s:
                edges += [(s, 1), (t, -1)]
    busy, active, last = 0.0, 0, lo
    for x, step in sorted(edges):
        if active > 0:
            busy += x - last
        active += step
        last = x
    assert abs(red["busy_s"] - busy / 1e9) < 1e-9
    assert 0 < red["busy_s"] < red["window_s"] == 0.15
    assert traces.matching(red["modules"], (ivf.SEARCH_MODULE,))
    kernels = traces.matching(red["ops"], ivf.COARSE_KERNELS)
    assert len(kernels) == 2 and all(k[0] > 0 for k in kernels)
