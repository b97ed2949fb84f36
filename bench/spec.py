"""Finds each piece of the benchmark by its name in ``BENCHMARK.json``.

- a cell: an entry of ``workloads``;
- a configuration: the ``file`` of its entry in ``configs``;
- a traffic mix: ``bench/traffic/<mix>.json``;
- what one cell sets for its mix, such as an open loop's arrival rate:
  ``bench/cells/<cell>.json``, where the cell needs it;
- a metric: ``bench/metrics/<metric>.py``, a module with
  ``read(ctx) -> float | None`` (None where the run has nothing to read).
  A metric split by the cells it serves, ``<metric>.<part>``, falls back
  to ``bench/metrics/<metric>.py`` where it has no file of its own.

A later cell, mix or metric is added as files and entries; nothing here
names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any


def load_benchmark(root: Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_config(root: Path, bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            conf = json.loads((root / c["file"]).read_text())
            if conf["name"] != name:
                raise ValueError(f"{c['file']} names {conf['name']!r}, "
                                 f"not {name!r}")
            return conf
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(root: Path, name: str) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json")
                      .read_text())


def load_cell(root: Path, name: str) -> dict:
    path = root / "bench" / "cells" / f"{name}.json"
    return json.loads(path.read_text()) if path.is_file() else {}


def metric_path(root: Path, name: str) -> Path:
    """The reader of a metric: its own file, else that of the name before
    its last dot."""
    base = root / "bench" / "metrics"
    path = base / f"{name}.py"
    if not path.is_file() and "." in name:
        path = base / f"{name.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} in {base}")
    return path


def load_metric(root: Path, name: str):
    path = metric_path(root, name)
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def cell_metrics(bench: dict, workload: str, trace: bool) -> list[str]:
    """The cell's end-to-end metrics (untraced) or per-layer ones (traced)."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m["name"] for m in group if applies(m, workload)]


def units(bench: dict) -> dict[str, str]:
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


@dataclasses.dataclass
class Context:
    """What a metric reader may read of one run (host clock: perf_counter
    seconds)."""
    model: Any            # bench.weights.Model
    cfg: Any              # the program's ArchConfig
    conf: dict            # the configuration file
    mix: dict             # the traffic mix
    cell: dict            # the workload entry
    seconds: float        # --seconds
    recs: list            # bench.run.Rec, one per generated request
    ticks: list           # bench.run.Tick, one per engine.tick() call
    t_open: float
    t_close: float
    t_end: float          # after the open loop's drain
    window_s: float
    tokens_window: int    # tokens emitted inside the window
    setup_s: float
    page: int
    chunk: int            # prefill chunk length
    n_window_ticks: int   # ticks[:n_window_ticks] ran inside the window
    traced: Any           # [host start, host end, decode steps at start]
    trace: Any            # bench.trace_reduce result, traced runs only
    peak: Any             # bench.peaks entry, traced runs only

    def window_recs(self) -> list:
        """Requests submitted inside the window (the open loop: all due)."""
        return [r for r in self.recs if r.due is not None
                and self.t_open <= r.due < self.t_close]

    def traced_ticks(self) -> list:
        lo, hi = self.traced[0], self.traced[1]
        return [t for t in self.ticks if t.start >= lo and t.end <= hi]

    def program_seconds(self, name: str) -> tuple[float, float] | None:
        """(device seconds, calls) of a jitted program in the traced slice,
        None where it did not run."""
        p = self.trace["programs"].get(name) if self.trace else None
        return None if not p or not p["calls"] else (p["seconds"],
                                                     p["calls"])
