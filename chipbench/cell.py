"""Find a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration file, its traffic mix (``traffic/<mix>.json``) and the
arrival process the mix names (``arrivals/<loop>.py``), its limits
(``limits/<cell>.json``), the reference and adapter its configuration
names (``reference/<family>.py``, ``adapters/<family>.py``) and one
reader per metric (``metrics/<metric>.py``).  Adding a cell,
configuration, mix, arrival process or metric adds files and entries;
no file here changes."""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end

    @property
    def family(self) -> str:
        return self.config["reference"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    m = load_manifest(root)
    cells = {w["name"]: w for w in m["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in m["configs"]}
    bench = root / "chipbench"
    return Cell(
        name=name, chips=w["chips"],
        config=load_json(root / configs[w["config"]]["file"]),
        mix=load_json(bench / "traffic" / f"{w['traffic']}.json"),
        limits=load_json(bench / "limits" / f"{name}.json"),
        end_to_end=[x for x in m["end_to_end"] if _reports(x, name)],
        per_layer=[x for x in m["per_layer"] if _reports(x, name)])


def _load_file(path: Path, label: str):
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, bench: Path = BENCH) -> Callable:
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    return _load_file(bench / "metrics" / f"{metric}.py",
                      f"chipbench_metric_{metric}").read


def arrivals(loop: str, bench: Path = BENCH):
    """The arrival process ``arrivals/<loop>.py``: its
    ``admit_max(mix, prompt_max)`` and its ``Arrivals(traffic)``, with a
    ``closed`` flag, ``schedule()`` and ``after(completion)``."""
    return _load_file(bench / "arrivals" / f"{loop}.py",
                      f"chipbench_arrivals_{loop}")


def family(kind: str, name: str):
    """``chipbench.reference.<name>`` or ``chipbench.adapters.<name>``."""
    return importlib.import_module(f"chipbench.{kind}.{name}")
