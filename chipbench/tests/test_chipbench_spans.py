"""The reduction of the program's own spans and scopes: device time by
stage, the tier's host time per batch, the device's idle time inside the
tier, and the longest idle gaps named by the span they fell in."""
import pytest

from chipbench import readers, spans, traces

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, end, **extra):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(end - start), **extra}


def _op(start, end, scope):
    return _ev(DEV, traces.OPS_LINE, f"op{start}", start, end, scope=scope)


def _span(name, start, end, **args):
    extra = {"args": args} if args else {}
    return _ev(HOST, "exec", name, start, end, **extra)


def _batch(start, bounds, rows, seq):
    """A ``serve.batch`` from ``start`` over five children ending at
    ``bounds`` in order."""
    out = [_span("serve.batch", start, bounds[-1], rows=rows, seq=seq)]
    lo = start
    for name, hi in zip(spans.CHILD_SPANS, bounds):
        out.append(_span(name, lo, hi))
        lo = hi
    return out


#: two batches in a 10,000 ns window, worked out by hand below
EVENTS = [
    _ev(HOST, "python", traces.WINDOW_SPAN, 0, 10_000),
    _ev(DEV, traces.MODULES_LINE, "jit__ivf_search(1)", 1000, 4000),
    _ev(DEV, traces.MODULES_LINE, "jit__ivf_search(1)", 6000, 9000),
    _op(1000, 1500, "ivf.coarse"),
    _op(1500, 3000, "ivf.scan"),
    _op(3000, 3500, spans.UNSCOPED),
    _op(3500, 4000, "ivf.rerank"),
    _op(6000, 8000, "ivf.scan"),
    _op(8000, 9000, "ivf.cut"),
    _op(9500, 9700, "ivf.scan"),            # outside any search module
    *_batch(300, (900, 1100, 4000, 4300, 4500), rows=64, seq=1),
    *_batch(5000, (5500, 6000, 9000, 9400, 9800), rows=32, seq=2),
    *_batch(9900, (9950, 9980, 10_300, 10_400, 10_500), rows=5, seq=3),
    _span("serve.batch", 200, 250),         # a step that found no batch
]


def test_reduce_by_hand():
    red = spans.reduce(EVENTS, "jit__ivf_search")
    # op time inside the two module events, by scope
    assert red["scope_s"] == pytest.approx({
        "ivf.coarse": 500e-9, "ivf.scan": 3500e-9, spans.UNSCOPED: 500e-9,
        "ivf.rerank": 500e-9, "ivf.cut": 1000e-9})
    # the third batch ends after the window, the fourth has no rows
    assert [b["rows"] for b in red["batches"]] == [64, 32]
    first, second = red["batches"]
    assert first["serve.wait"] == pytest.approx(2900 / 1e6)
    # the batch less its wait: 4200 - 2900 and 4800 - 3000 ns
    assert [first["host_ms"], second["host_ms"]] == pytest.approx(
        [1300 / 1e6, 1800 / 1e6])
    # idle [0,1000] [4000,6000] [9000,9500] [9700,10000] against the
    # batches less their waits [300,1100] [4000,4500] [5000,6000]
    # [9000,9800]: 700 + 500 + 1000 + 500 + 100 ns
    assert red["idle_in_tier_s"] == pytest.approx(2800e-9)
    # [4000,6000]: no span covers more than half; [0,1000]: the batch
    # (700) and its form (600) do, the form is innermost; [9000,9500]:
    # the batch and its d2h (400); [9700,10000]: 100 of 300 at most
    assert red["gaps"] == [[4000e-9, 2000e-6, "outside the tier"],
                           [0.0, 1000e-6, "serve.form"],
                           [9000e-9, 500e-6, "serve.d2h"],
                           [9700e-9, 300e-6, "outside the tier"]]


class _Run:
    """What the readers read of a run: its trace, its system, its info."""

    class system:
        SEARCH_MODULE = "jit__ivf_search"

    def __init__(self, evs):
        self.trace = traces.reduce(evs)
        self.info = {}


def _run(evs):
    run = _Run(evs)
    run.info["spans"] = spans.reduce(evs, run.system.SEARCH_MODULE)
    return run


def test_readers_by_hand():
    run = _run(EVENTS)
    # two module events start in the window
    assert spans.scope_ms(run, "ivf.scan") == pytest.approx(3500 / 2 / 1e6)
    assert spans.scope_ms(run, "ivf.rerank") == pytest.approx(500 / 2 / 1e6)
    assert spans.tier_host_ms(run) == pytest.approx(1550 / 1e6)
    assert spans.idle_in_tier_ms(run) == pytest.approx(1400 / 1e6)
    lines = spans.describe(run.info["spans"], [6000e-9, 2])
    # 5500 of the modules' 6000 ns lie in the four scopes
    assert lines[0].endswith("the scopes cover 91.67% of the search module "
                             "(0.0030 ms)")
    # the median of 4200 and 4800 ns
    assert "over 2 batches: serve.batch 0.0045," in lines[1]
    assert "(0.000, 0.001, serve.form)" in lines[2]


def test_a_program_without_spans_or_scopes_reads_nothing():
    """The parent of the change that added them: every op unscoped, no
    tier span; each reader returns None and raises nothing."""
    bare = [dict(e, scope=spans.UNSCOPED) if "scope" in e else e
            for e in EVENTS if not e["name"].startswith("serve.")]
    run = _run(bare)
    assert spans.scope_ms(run, "ivf.scan") is None
    assert spans.scope_ms(run, "ivf.rerank") is None
    assert spans.tier_host_ms(run) is None
    assert spans.idle_in_tier_ms(run) is None
    untraced = _Run([])
    assert untraced.trace is None
    assert spans.load(untraced) is None
    assert spans.tier_host_ms(untraced) is None


def test_op_scopes_reads_the_stat_from_event_metadata():
    """The scope stat sits in each op's event metadata, as a string or as
    a reference to an interned one; ProfileData shows neither."""
    from jax.profiler import ProfileData
    stat = spans.SCOPE_STAT
    text = f'''
    planes {{
      id: 1 name: "{DEV}"
      lines {{ id: 1 name: "XLA Ops"
        events {{ metadata_id: 7 offset_ps: 1000 duration_ps: 5000 }}
        events {{ metadata_id: 8 offset_ps: 9000 duration_ps: 2000 }}
        events {{ metadata_id: 9 offset_ps: 12000 duration_ps: 1000 }} }}
      event_metadata {{ key: 7 value {{ id: 7 name: "%fusion.1 = gather"
        stats {{ metadata_id: 1 str_value: "jit(_ivf_search)/ivf.scan/gather" }}
        stats {{ metadata_id: 2 int64_value: 4 }} }} }}
      event_metadata {{ key: 8 value {{ id: 8 name: "%fusion.8 = top_k"
        stats {{ metadata_id: 1 ref_value: 3 }} }} }}
      event_metadata {{ key: 9 value {{ id: 9 name: "%copy.61 = copy"
        stats {{ metadata_id: 1 str_value: "base" }} }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "{stat}" }} }}
      stat_metadata {{ key: 2 value {{ id: 2 name: "flops" }} }}
      stat_metadata {{ key: 3 value {{ id: 3
        name: "jit(_ivf_search)/ivf.cut/top_k" }} }}
    }}
    planes {{
      id: 2 name: "{HOST}"
      event_metadata {{ key: 1 value {{ id: 1 name: "serve.batch"
        stats {{ metadata_id: 1 str_value: "jit(x)/ivf.scan" }} }} }}
      stat_metadata {{ key: 1 value {{ id: 1 name: "{stat}" }} }}
    }}'''
    raw = ProfileData.text_proto_to_serialized_xspace(text)
    found = spans.op_scopes(raw)
    assert found == {DEV: {
        "%fusion.1 = gather": "jit(_ivf_search)/ivf.scan/gather",
        "%fusion.8 = top_k": "jit(_ivf_search)/ivf.cut/top_k",
        "%copy.61 = copy": "base"}}
    assert {k: spans.scope_of(v) for k, v in found[DEV].items()} == {
        "%fusion.1 = gather": "ivf.scan", "%fusion.8 = top_k": "ivf.cut",
        "%copy.61 = copy": spans.UNSCOPED}
    assert spans.scope_of(None) == spans.UNSCOPED


def test_recorded_chip_trace_by_hand():
    """A 179 ms slice of a trace recorded on a TPU v5e serving
    gist1m.closed: two whole batches and their two search modules.  The
    expected numbers come from a sweep over the slice's own intervals:
    the ops of each scope summed, each batch less its wait, and the
    idle instants inside a batch and outside its wait."""
    import json
    from pathlib import Path

    from chipbench import spec
    evs = json.loads((Path(__file__).parent / "fixtures"
                      / "trace_v5e_gist1m_spans.json").read_text())
    run = _run(evs)
    read = {name: spec.Bench().reader(name)(run) for name in (
        "scan_device_ms", "rerank_device_ms", "tier_host_ms.qps",
        "tier_host_ms.p95", "idle_in_tier_ms")}
    assert read == pytest.approx({
        "scan_device_ms": 127.147628 / 2,
        "rerank_device_ms": 0.036566 / 2,
        "tier_host_ms.qps": (7.29223 + 7.50292) / 2,
        "tier_host_ms.p95": (7.29223 + 7.50292) / 2,
        "idle_in_tier_ms": 14.625219 / 2}, rel=1e-9)
    # whole-index relayouts (copy.61, copy.51) carry their parameter's
    # op_name, so a fifth of the module is unscoped
    assert run.info["spans"]["scope_s"][spans.UNSCOPED] == pytest.approx(
        33.078953e-3)
    [cover] = [line for line in spans.describe(
        run.info["spans"], readers.search_module(run)) if "cover" in line]
    assert cover.endswith("the scopes cover 79.45% of the search module "
                          "(80.4819 ms)")
