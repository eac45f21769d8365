"""An angular deployment is added by files and entries alone, and its run
is held to the ip reference: a sound run reads correct, while its
control, the l2 kernels serving its unit vectors, and an unknown metric
do not."""
import json
import shutil

import pytest

from chipbench import control, harness, spec
from chipbench.tests.conftest import ANGULAR, TINY

REPO = spec.ROOT
CELL = "angular_tiny.closed"


@pytest.fixture()
def bench(tmp_path):
    """A copy of the benchmark with one angular configuration and its cell
    added as new files and entries; yields the bench, then checks that no
    file that was there changed."""
    here = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    cfg = harness._merge(
        harness._merge(json.loads((here / "configs" / "sift1m.json")
                                  .read_text()), TINY), ANGULAR)
    cfg["name"] = "angular_tiny"
    assert cfg["data"]["n"] % cfg["data"]["clusters"]
    (here / "configs" / "angular_tiny.json").write_text(json.dumps(cfg))
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": CELL, "config": "angular_tiny",
                             "traffic": "closed", "chips": 1,
                             "why": "an angular cell added by files alone"})
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "sift1m.closed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    yield spec.Bench(root=tmp_path, here=here)
    for p, b in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == b, p


def _run(bench, **kw):
    return harness.execute(CELL, 2**31 + 21, 0.5, False, bench=bench,
                           require_chip=False, say=lambda s: None, **kw)


def test_angular_cell_is_correct_and_its_control_is_not(bench):
    got = {}
    res = _run(bench, inspect=lambda run: got.update(
        run=run, control=control.control_numbers(run)))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"qps", "recall_at_10", "build_s",
                                   "setup_s"}
    assert res["metrics"]["recall_at_10"]["value"] > 0.5
    run = got["run"]
    assert run.system.stated(run.config)["metric"] == "ip"
    limits = run.system.limits(run.config)
    assert got["control"]["dist_err"] > 10 * limits["dist_err"], got
    assert not all(limits[k] is None or got["control"][k] <= limits[k]
                   for k in run.system.reference.NUMBERS)


def test_l2_kernels_on_angular_data_are_not_correct(bench, monkeypatch):
    """A planted fault: the backend serves the unit vectors by l2."""
    from repro.anns.backends.ivf import IvfBackend
    orig = IvfBackend.__init__

    def as_l2(self, variant=None, *, metric="l2", seed=0):
        orig(self, variant, metric="l2", seed=seed)
    monkeypatch.setattr(IvfBackend, "__init__", as_l2)
    res = _run(bench)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("metric", ["cosine", "ip", "L2"])
def test_unknown_metric_raises(bench, metric):
    with pytest.raises(ValueError, match=repr(metric)):
        _run(bench, config_override={"data": {"metric": metric}})
    system = bench.system("ivf")
    with pytest.raises(ValueError, match=repr(metric)):
        system.backend_metric({"data": {"metric": metric}})
