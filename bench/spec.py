"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

Nothing here knows a particular configuration, traffic mix or metric: a
later cell adds files (``configs/<name>.json``, ``traffic/<mix>.json``,
``metrics/<metric>.py``) and entries, and this module finds them.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
TRAFFIC_DIR = BENCH_DIR / "traffic"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file's contents, plus "name"
    traffic: dict         # the traffic file's contents, plus "name"
    end_to_end: list      # the BENCHMARK.json entries this cell reports
    per_layer: list


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_traffic(name: str, traffic_dir: pathlib.Path = TRAFFIC_DIR) -> dict:
    path = traffic_dir / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} (looked for {path})")
    return {"name": name, **json.loads(path.read_text())}


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:            # per-layer without a list: every cell
        return metric["moves"] in e2e_names   # that reports what it moves
    return True


def find_cell(name: str, bench: dict | None = None,
              root: pathlib.Path = ROOT,
              traffic_dir: pathlib.Path = TRAFFIC_DIR) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[w["config"]]
    config = {"name": entry["name"],
              **json.loads((root / entry["file"]).read_text())}
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=load_traffic(w["traffic"], traffic_dir),
                end_to_end=e2e, per_layer=per_layer)


def config_module(config: dict):
    """The module that makes the configuration's banks and inputs:
    ``configs/<module>.py`` (``module`` defaults to the configuration's
    own name)."""
    return importlib.import_module(
        f"bench.configs.{config.get('module', config['name'])}")


def reference_module(config: dict):
    """The configuration's plain reference, beside its module."""
    return importlib.import_module(
        f"bench.configs.{config.get('module', config['name'])}_ref")


def metric_module(name: str):
    """``metrics/<name>.py``: ``read(ctx)``, and for a kernel's roofline
    ``KERNEL`` and its trace name ``PATTERNS``."""
    return importlib.import_module(f"bench.metrics.{name}")


def metric_reader(name: str):
    return metric_module(name).read
