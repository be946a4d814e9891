"""Finds every piece of a cell by its name, in files of its own.

``BENCHMARK.json`` names the cells, metrics and configurations.  The rest
is looked up under the benchmark's directory:

* ``configs/<config>.json``: the configuration as it is run;
* ``traffic/<mix>.json``: a traffic mix (``harness.traffic`` reads it);
* ``cells/<cell>.json``: what belongs to one cell alone (its rate);
* ``metrics/<metric>.py``: the reader of one metric, ``read(run)``;
* ``arch/<arch>.py``: weights, plain reference, control and work counts
  of one architecture, named by a configuration's ``arch``;
* ``peaks.json``: the chip's peaks by ``device_kind``, with their source.

A name that has no file, or is not in ``BENCHMARK.json``, is an error.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(KeyError):
    """A cell, configuration, mix, metric, architecture or device kind
    that the benchmark does not define."""

    def __str__(self) -> str:
        return str(self.args[0])


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    own: dict                    # cells/<cell>.json, or {}
    end_to_end: tuple            # metric entries of BENCHMARK.json
    per_layer: tuple

    @property
    def rate(self) -> float | None:
        return self.own.get("rate_per_s")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Registry:
    def __init__(self, bench_path: str | None = None,
                 dirs: tuple[str, ...] | None = None):
        bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
        with open(bench_path) as f:
            self.bench = json.load(f)
        self.dirs = tuple(dirs or (HERE,))

    def _find(self, kind: str, name: str, ext: str) -> str:
        if not _NAME.match(name):
            raise UnknownName(f"{kind} name {name!r} is not a valid name")
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise UnknownName(f"no {kind}/{name}{ext} for {kind[:-1]} {name!r}")

    def _json(self, kind: str, name: str) -> dict:
        with open(self._find(kind, name, ".json")) as f:
            return json.load(f)

    def config(self, name: str) -> dict:
        if name not in {c["name"] for c in self.bench["configs"]}:
            raise UnknownName(f"unknown config {name!r}")
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise UnknownName(
                f"unknown workload {name!r}; known: "
                f"{[w['name'] for w in self.bench['workloads']]}")
        try:
            own = self._json("cells", name)
        except UnknownName:
            own = {}
        traffic = self.traffic(entry["traffic"])
        if traffic.get("rate") == "per cell" and "rate_per_s" not in own:
            raise UnknownName(f"mix {entry['traffic']!r} takes its rate from "
                              f"cells/{name}.json, which gives none")
        return Cell(
            name=name, chips=int(entry["chips"]),
            config=self.config(entry["config"]), traffic=traffic, own=own,
            end_to_end=tuple(m for m in self.bench["end_to_end"]
                             if _applies(m, name)),
            per_layer=tuple(m for m in self.bench["per_layer"]
                            if _applies(m, name)))

    def _module(self, kind: str, name: str):
        path = self._find(kind, name, ".py")
        key = f"chipbench_{kind}_{name}_{abs(hash(path))}"
        if key not in sys.modules:
            spec = importlib.util.spec_from_file_location(key, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[key] = mod
            spec.loader.exec_module(mod)
        return sys.modules[key]

    def reader(self, metric: str):
        """``read(run) -> float | None`` of one metric."""
        return self._module("metrics", metric).read

    def arch(self, name: str):
        return self._module("arch", name)

    def peaks(self, device_kind: str) -> dict:
        for d in self.dirs:
            path = os.path.join(d, "peaks.json")
            if os.path.isfile(path):
                with open(path) as f:
                    table = json.load(f)
                if device_kind in table:
                    return table[device_kind]
        raise UnknownName(f"no peaks for device kind {device_kind!r}")
