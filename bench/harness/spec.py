"""Finds a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own:

* ``BENCHMARK.json`` (checkout root) names the cells, and for each the
  configuration and the traffic mix;
* the configuration is the JSON file that ``BENCHMARK.json`` gives as its
  ``file``.  Its ``dqf`` block is the keyword arguments of the program's
  ``DQFConfig``; where a field of ``DQFConfig`` is itself a dataclass
  (``quant``, ``tier``), the block may give it as a nested block, which
  :func:`dqf_config` builds into that dataclass.  Its ``metric`` and
  ``dtype`` are checked against what the reference implements (squared
  L2 over float32 rows);
* the traffic mix is ``bench/traffic/<traffic>.json``;
* a per-layer metric is ``bench/metrics/<metric name>.py``, a module with
  one function ``read(run)`` that returns a number or None, and
  ``TRACE_LOG = True`` if it reads the engine's per-query trace log,
  which the traced run then turns on.

A new cell, configuration, mix or metric is new files and new entries;
no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

__all__ = ["BENCH", "ROOT", "Cell", "load_cell", "load_reader",
           "uses_trace_log", "enable_compile_cache", "dqf_config"]

# what the reference (``reference.py``) and the row generator implement
REFERENCE_SEMANTICS = {"metric": "l2", "dtype": "float32"}


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at ``<root>/.bench_cache/jax``,
    a fixed place inside the checkout, for every program however short
    its compile."""
    import jax

    path = os.path.join(root, ".bench_cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``; KeyError if none,
    ValueError if its configuration states a ``metric`` or ``dtype`` that
    the reference does not implement."""
    spec = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json "
                       f"(cells: {', '.join(sorted(cells))})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]))
    for field, want in REFERENCE_SEMANTICS.items():
        if config.get(field) != want:
            raise ValueError(
                f"configuration {w['config']!r}: {field} "
                f"{config.get(field)!r} is not {want!r}, the only one the "
                f"reference implements")
    traffic = _read_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])


def dqf_config(config: dict):
    """The program's ``DQFConfig`` for a configuration: its ``dqf`` block,
    with ``k`` from its ``guarantee``.  A nested block becomes the
    dataclass that its field's default is an instance of.  A key that
    the dataclass lacks raises ValueError, naming both."""
    from repro.core import DQFConfig

    block = config["dqf"]
    if "k" in block:
        raise ValueError("dqf: key 'k' is given by guarantee.k")
    return _dataclass(DQFConfig, dict(block, k=config["guarantee"]["k"]),
                      "dqf")


def _dataclass(cls, block: dict, where: str):
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = sorted(set(block) - set(fields))
    if unknown:
        raise ValueError(f"{where}: {cls.__name__} has no field "
                         f"{', '.join(map(repr, unknown))}")
    kw = {}
    for name, value in block.items():
        default = fields[name].default
        if isinstance(value, dict) and dataclasses.is_dataclass(default):
            value = _dataclass(type(default), value, f"{where}.{name}")
        kw[name] = value
    return cls(**kw)


def _reader_module(metric: str, root: str):
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _reader_module(metric, root).read


def uses_trace_log(metric: str, root: str = ROOT) -> bool:
    """Whether the reader of ``metric`` needs the per-query trace log."""
    return bool(getattr(_reader_module(metric, root), "TRACE_LOG", False))
