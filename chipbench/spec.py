"""Find a cell's configuration, traffic mix, system and metric readers by
the names ``BENCHMARK.json`` gives them; nothing here names a cell."""
from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

#: the checkout root: ``chipbench/`` sits directly below it
ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A name that resolves to no file, or a file that does not parse."""


def _checked(name: str) -> str:
    if not _NAME.match(name):
        raise SpecError(f"bad name {name!r}")
    return name


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: Path, tag: str):
    """Import one file by path (metric readers are named ``a.b.py``)."""
    if not path.is_file():
        raise SpecError(f"missing file {path}")
    mod_name = f"chipbench_{tag}_" + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    per_layer: bool
    moves: str | None
    workloads: tuple | None


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Bench:
    """``BENCHMARK.json`` plus the files it names, under ``here``."""

    def __init__(self, root: Path = ROOT, here: Path = HERE):
        self.root, self.here = Path(root), Path(here)
        self.doc = load_json(self.root / "BENCHMARK.json")
        self.cells = {w["name"]: Cell(w["name"], w["config"], w["traffic"],
                                      int(w["chips"]))
                      for w in self.doc["workloads"]}
        self.metrics = [
            Metric(m["name"], m["unit"], m["better"], m["source"], per, m.get(
                "moves"), tuple(m["workloads"]) if "workloads" in m else None)
            for key, per in (("end_to_end", False), ("per_layer", True))
            for m in self.doc[key]]

    def cell(self, name: str) -> Cell:
        try:
            return self.cells[name]
        except KeyError:
            raise SpecError(f"no workload {name!r} in BENCHMARK.json; "
                            f"have {sorted(self.cells)}") from None

    def config(self, name: str) -> dict:
        return load_json(self.here / "configs" / f"{_checked(name)}.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.here / "traffic" / f"{_checked(name)}.json")

    def system(self, name: str):
        return load_module(self.here / "systems" / f"{_checked(name)}.py",
                           "system")

    def reader(self, metric: str):
        return load_module(self.here / "metrics" / f"{_checked(metric)}.py",
                           "metric").read

    def metrics_for(self, cell: str, per_layer: bool) -> list:
        """The metrics a run of ``cell`` reports: end-to-end ones with
        ``--trace 0``, per-layer ones with ``--trace 1``.  A metric without
        ``workloads`` goes where the metric it moves (or, end to end,
        every cell) goes."""
        e2e = [m for m in self.metrics if not m.per_layer
               and (m.workloads is None or cell in m.workloads)]
        if not per_layer:
            return e2e
        moved = {m.name for m in e2e}
        return [m for m in self.metrics if m.per_layer and (
            cell in m.workloads if m.workloads is not None
            else m.moves in moved)]

    def listing(self) -> dict:
        """Every configuration, mix and metric reader present as a file,
        by name: what a later change adds by adding files."""
        def names(sub, suffix):
            return sorted(p.name[: -len(suffix)]
                          for p in (self.here / sub).glob(f"*{suffix}"))
        return {"configs": names("configs", ".json"),
                "traffic": names("traffic", ".json"),
                "metrics": names("metrics", ".py"),
                "systems": names("systems", ".py")}
