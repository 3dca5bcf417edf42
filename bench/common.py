"""Finding the pieces of a cell by name, the device check, the compile cache.

Everything that belongs to one configuration, traffic mix, cell or metric
sits in a file of its own, found by the name `BENCHMARK.json` gives it:

    bench/configs/<config>.json   the deployment: a `generator`, sizes, source
    bench/generators/<name>.py    a graph generator: `edges(config, seed)`
    bench/mixes/<traffic>.json    the traffic mix: a `kind` and its parameters
    bench/kinds/<kind>.py         the work of a kind of mix: its `Work` class
    bench/limits/<cell>.json      the limit of each number the check compares
    bench/metrics/<metric>.py     the reader of one metric: `read(ctx)`
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _json(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def cell(bm: dict, name: str) -> dict:
    return _by_name(bm["workloads"], name, "workload")


def config(bm: dict, cell_entry: dict, root: str = ROOT) -> dict:
    entry = _by_name(bm["configs"], cell_entry["config"], "config")
    return _json(os.path.join(root, entry["file"]))


def mix(cell_entry: dict, bench: str = BENCH) -> dict:
    return _json(os.path.join(bench, "mixes", cell_entry["traffic"] + ".json"))


def limits(cell_name: str, bench: str = BENCH) -> dict:
    return _json(os.path.join(bench, "limits", cell_name + ".json"))["limits"]


def metrics_of(bm: dict, cell_name: str, kind: str) -> list[dict]:
    """The `end_to_end` or `per_layer` entries a cell reports: those whose
    `workloads` lists it, or that have no `workloads` key."""
    return [m for m in bm[kind] if cell_name in m.get("workloads", [cell_name])]


def _module(folder: str, name: str, bench: str):
    """`bench/<folder>/<name>.py`, loaded from its file (a name may hold dots)."""
    path = os.path.join(bench, folder, name + ".py")
    if not os.path.isfile(path):
        raise KeyError(f"no bench/{folder}/{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(metric_name: str, bench: str = BENCH):
    """The `read(ctx)` function of `bench/metrics/<metric>.py`."""
    return _module("metrics", metric_name, bench).read


def work_class(kind: str, bench: str = BENCH):
    """The `Work` class of `bench/kinds/<kind>.py`."""
    return _module("kinds", kind, bench).Work


@dataclasses.dataclass
class Graph:
    """A cell's input graph, as the benchmark made it from the seed."""

    name: str
    num_nodes: int
    src: np.ndarray
    dst: np.ndarray

    @property
    def num_edges(self) -> int:
        return int(self.src.size)


def make_graph(cfg: dict, seed: int, bench: str = BENCH) -> Graph:
    """The configuration's graph from its `generator`, `bench/generators/<name>.py`."""
    src, dst = _module("generators", cfg["generator"], bench).edges(cfg, seed)
    return Graph(cfg["name"], cfg["num_nodes"], src, dst)


def peaks(device_kind: str, bench: str = BENCH) -> dict:
    table = _json(os.path.join(bench, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")
    return table[device_kind]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache: `JAX_COMPILATION_CACHE_DIR` where
    it is set, else the fixed `artifacts/jax_cache/` of the checkout.  Every
    program is cached, however short its compile, so that a program the
    window builds anew is found there and not compiled again."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, "artifacts", "jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips: int) -> list:
    """The first `chips` TPU devices; `NoChip` when there are fewer."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: jax found {len(devices)} {devices[0].platform} device(s)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} TPU chips, jax found {len(devices)}")
    return devices[:chips]
