"""The harness finds configurations, mixes and metric readers by name:
a later change adds one by adding files and entries, never by editing a
file that is there.  And the command refuses to print a result without a
chip, or without the system under test beside it."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from chipbench import harness, spec
from chipbench.tests.conftest import TINY

REPO = spec.ROOT


def _copy_bench(tmp: Path) -> Path:
    here = tmp / "chipbench"
    shutil.copytree(spec.HERE, here, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp / "BENCHMARK.json")
    return here


def test_added_files_are_found_by_name(tmp_path):
    here = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in here.rglob("*") if p.is_file()}
    cfg = json.loads((here / "configs" / "sift1m.json").read_text())
    cfg["name"] = "dummy"
    (here / "configs" / "dummy.json").write_text(json.dumps(cfg))
    (here / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"kind": "closed", "clients_per_slot": 2, "queue_per_slot": 4}))
    (here / "metrics" / "dummy_answered.py").write_text(
        "def read(run):\n    return len(run.answered())\n")
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    doc["workloads"].append({"name": "dummy.dummy_mix", "config": "dummy",
                             "traffic": "dummy_mix", "chips": 1,
                             "why": "a cell added by files alone"})
    doc["end_to_end"].append({"name": "dummy_answered", "unit": "requests",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock",
                              "workloads": ["dummy.dummy_mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    bench = spec.Bench(root=tmp_path, here=here)
    listed = bench.listing()
    assert "dummy" in listed["configs"]
    assert "dummy_mix" in listed["traffic"]
    assert "dummy_answered" in listed["metrics"]
    names = [m.name for m in bench.metrics_for("dummy.dummy_mix", False)]
    assert "dummy_answered" in names and "qps" not in names
    res = harness.execute("dummy.dummy_mix", 3, 0.5, False, bench=bench,
                          require_chip=False, config_override=TINY,
                          say=lambda s: None)
    assert res["correct"]
    assert res["metrics"]["dummy_answered"]["value"] == res["attempted"]
    for p, b in before.items():
        assert p.read_bytes() == b, p


def test_metrics_follow_their_cells():
    bench = spec.Bench()
    for cell in bench.cells:
        e2e = {m.name for m in bench.metrics_for(cell, False)}
        layer = {m.name for m in bench.metrics_for(cell, True)}
        assert "setup_s" in e2e and len(e2e) >= 3
        assert any(m.startswith("recall_at_10") for m in e2e)
        assert layer and all(m.moves in e2e for m in bench.metrics
                             if m.name in layer)
    assert {m.name for m in bench.metrics_for("sift1m.closed", True)} == {
        "device_idle_share", "search_device_ms", "search_roofline",
        "coarse_kernels_ms", "batch_compute_ms.qps", "tier_host_ms.qps",
        "idle_in_tier_ms", "scan_device_ms", "rerank_device_ms"}
    for name in bench.listing()["metrics"]:
        bench.reader(name)


def _command(cwd: Path, env: dict):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "sift1m.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _command(REPO, env)
    assert out.returncode == 3, out.stderr[-2000:]
    assert "{" not in out.stdout


def test_bare_checkout_no_result(tmp_path):
    _copy_bench(tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _command(tmp_path, env)
    assert out.returncode not in (0, None)
    assert "{" not in out.stdout
