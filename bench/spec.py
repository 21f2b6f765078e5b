"""Find a cell's configuration, traffic mix and metrics by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own; ``BENCHMARK.json`` names them:

* a configuration is the JSON file its ``configs`` entry names;
* a traffic mix ``<mix>`` is ``bench/traffic/<mix>.json``;
* a metric ``<name>`` is read by ``bench/metrics/<name>.py``, which
  defines ``read(ctx)`` and returns a number, or None when it finds
  nothing to read.  A name with a suffix, ``<stem>.<part>`` (one
  quantity split by the end-to-end metric it moves), falls back to
  ``bench/metrics/<stem>.py`` when it has no file of its own.

Adding a cell, a configuration or a metric therefore adds files and
entries and edits no code.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, as run
    traffic: dict         # the traffic mix file
    end_to_end: list      # BENCHMARK.json entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_json: Path = REPO / "BENCHMARK.json") -> Cell:
    spec = json.loads(bench_json.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((REPO / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in spec["per_layer"] if _reports(m, name)])


def metric_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``, or of
    ``bench/metrics/<stem>.py`` for ``<stem>.<part>``."""
    path = BENCH / "metrics" / f"{name}.py"
    if not path.exists():
        path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read
