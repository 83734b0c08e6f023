"""Resolve a cell of ``BENCHMARK.json`` to its files, by name alone.

Plain Python, no JAX: the entry module reads the manifest before it pays
for any import, and the manifest test reads it without a device.

* ``benchmark/configs/<config>.json`` -- the configuration as it is run
  (its ``file`` entry in ``BENCHMARK.json``): model fields, ``source``,
  ``reduced``, ``assumed``, ``job`` (a module of ``benchmark/jobs``) and
  ``reference`` (a module of ``benchmark/reference``).
* ``benchmark/traffic/<traffic>.json`` -- chips, mesh axes, batch,
  sequence or image size, pool.
* ``benchmark/metrics/<metric>.py`` -- one reader, ``read(ctx)``.
* ``benchmark/peaks.json`` -- published peaks by ``device_kind``.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load() -> dict:
    """``BENCHMARK.json`` of the checkout this package sits in."""
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def metric_names(manifest: dict, group: str, workload: str) -> list[dict]:
    """Entries of ``end_to_end`` or ``per_layer`` that ``workload``
    reports: those with no ``workloads`` key, or with it listed."""
    return [m for m in manifest[group]
            if workload in m.get("workloads", [workload])]


def cell(workload: str, manifest: dict | None = None) -> dict:
    """Everything one run needs to know about ``workload``."""
    manifest = manifest or load()
    entries = {w["name"]: w for w in manifest["workloads"]}
    if workload not in entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"there are {sorted(entries)}")
    entry = entries[workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == entry["config"])
    return {
        "name": workload,
        "chips": entry["chips"],
        "config_name": entry["config"],
        "config": load_json(os.path.join(ROOT, config_entry["file"])),
        "traffic_name": entry["traffic"],
        "traffic": load_json(os.path.join(
            HERE, "traffic", entry["traffic"] + ".json")),
        "end_to_end": metric_names(manifest, "end_to_end", workload),
        "per_layer": metric_names(manifest, "per_layer", workload),
    }


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", name + ".py")


def load_reader(name: str):
    """The ``read(ctx)`` of ``benchmark/metrics/<name>.py``.  Loaded by
    path: a metric's name may hold a dot, which a module's may not."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics._" + name.replace(".", "_").replace("-", "_"),
        metric_path(name))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_job(name: str):
    return importlib.import_module("benchmark.jobs." + name)


def load_reference(name: str):
    return importlib.import_module("benchmark.reference." + name)


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a kind that is not in the table
    is an error, never a default."""
    table = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table:
        raise KeyError(f"benchmark/peaks.json has no device kind "
                       f"{device_kind!r}; it has {sorted(table)}")
    return table[device_kind]
