"""What ``BENCHMARK.json`` names, resolved to the benchmark's own files.

A cell ``<config>.<mix>`` resolves to ``bench/configs/<config>.json`` (its
``file`` entry), the configuration's plain reference beside it,
``bench/traffic/<mix>.json``, and one ``bench/metrics/<metric>.py`` per
per-layer metric. Adding a configuration, a mix or a metric is adding files
and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def load_module(path: Path) -> ModuleType:
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: str
    config: dict
    mix: dict
    reference: ModuleType
    end_to_end: List[dict]
    per_layer: List[dict]

    def metric_module(self, name: str) -> ModuleType:
        return load_module(BENCH / "metrics" / f"{name}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str, bench: Dict | None = None) -> Cell:
    bench = bench if bench is not None else benchmark()
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / c["file"])
    ref = load_module((ROOT / c["file"]).parent / config["reference"])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=w["chips"], traffic=w["traffic"],
                config=config, mix=mix,
                reference=ref,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def model_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    program's registry entry ``base`` with the file's sizes and pruning."""
    import dataclasses as dc

    from repro.configs import get_config

    base = get_config(config["base"])
    pr = dict(config["pruning"])
    pr["tdm_layers"] = tuple(pr["tdm_layers"])
    return base.replace(name=config["name"], head_dim=0,
                        pruning=dc.replace(base.pruning, **pr),
                        **config["model"])


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
