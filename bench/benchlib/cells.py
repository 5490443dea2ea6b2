"""Find every part of a cell by name, as data.

Layout, from the checkout's root::

    BENCHMARK.json                 cells, configurations, metrics
    bench/cells/<cell>.json        engine flags, traffic overrides, limits
    bench/configs/<config>.json    the model configuration as it is run
    bench/traffic/<mix>.json       a traffic mix (benchlib/traffic.py)
    bench/metrics/<metric>.py      one reader per metric: read(run)
    bench/reference/<family>.py    plain float32 references
    bench/peaks.json               chip peaks keyed by device_kind

A later cell, mix, configuration or metric is new files plus new
entries in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Callable, Dict, List


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict               # bench/configs/<config>.json
    traffic: dict              # the mix with the cell's overrides applied
    engine_flags: List[str]    # launch/serve.py's engine flags
    limits: Dict[str, float]   # limit of each number `correct` compares
    end_to_end: List[dict]     # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark under one checkout root."""

    def __init__(self, root: str):
        self.root = root
        self.dir = os.path.join(root, "bench")
        self.spec = _load_json(os.path.join(root, "BENCHMARK.json"))
        self._modules: Dict[str, ModuleType] = {}

    def path(self, *parts: str) -> str:
        return os.path.join(self.dir, *parts)

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> Cell:
        w = self._entry("workloads", name)
        conf = self._entry("configs", w["config"])
        own = _load_json(self.path("cells", f"{name}.json"))
        mix = _load_json(self.path("traffic", f"{w['traffic']}.json"))
        mix.update(own.get("traffic", {}))
        # a metric without a "workloads" list is reported by every cell
        # (end to end) or by every cell that reports what it moves
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        names = {m["name"] for m in e2e}
        layer = [m for m in self.spec["per_layer"]
                 if ("workloads" in m and name in m["workloads"])
                 or ("workloads" not in m and m["moves"] in names)]
        return Cell(name=name, chips=int(w["chips"]),
                    config_name=w["config"],
                    config=_load_json(os.path.join(self.root, conf["file"])),
                    traffic=mix,
                    engine_flags=list(own["engine"]),
                    limits=dict(own.get("limits", {})),
                    end_to_end=e2e, per_layer=layer)

    def module(self, kind: str, name: str) -> ModuleType:
        key = f"{kind}/{name}"
        if key not in self._modules:
            self._modules[key] = _load_module(
                self.path(kind, f"{name}.py"),
                f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"))
        return self._modules[key]

    def reader(self, metric: str) -> Callable:
        """``bench/metrics/<metric>.py``'s ``read(run) -> float | None``."""
        return self.module("metrics", metric).read

    def reference(self, family: str) -> ModuleType:
        return self.module("reference", family)

    def peaks(self, device_kind: str) -> dict:
        table = _load_json(self.path("peaks.json"))["devices"]
        if device_kind not in table:
            raise KeyError(f"device_kind {device_kind!r} is not in "
                           f"bench/peaks.json ({sorted(table)}); add its "
                           f"published peaks there")
        return table[device_kind]
