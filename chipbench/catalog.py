"""Finds the benchmark's files by the names ``BENCHMARK.json`` gives them.

Every directory in the manifest's ``paths`` is searched, in order, for
``<path>/<kind>/<name><suffix>``: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.json``, and the modules
``jobs/<kind>.py``, ``readers/<reader>.py`` and, both under the name the
configuration file gives as its ``reference``, ``references/<module>.py``
(the plain model) and ``accounting/<module>.py`` (its sizes, FLOPs a token
and compared leaves). So a new configuration, architecture, traffic mix,
job kind or metric is new files and new entries in the manifest; nothing
here or in ``run.py`` lists them.
"""
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find(manifest: dict, kind: str, name: str, suffix: str,
         root: str = ROOT) -> str:
    """Path, relative to the checkout, of the file ``name`` of ``kind``."""
    tried = []
    for base in manifest["paths"]:
        rel = os.path.join(base, kind, name + suffix)
        if os.path.isfile(os.path.join(root, rel)):
            return rel
        tried.append(rel)
    raise FileNotFoundError(
        f"no {kind} file for {name!r}: looked for {', '.join(tried)}")


def load_json(manifest: dict, kind: str, name: str, root: str = ROOT) -> dict:
    with open(os.path.join(root, find(manifest, kind, name, ".json",
                                      root))) as f:
        return json.load(f)


def module_name(manifest: dict, kind: str, name: str,
                root: str = ROOT) -> str:
    """Dotted name of the module ``<path>/<kind>/<name>.py``: importable
    in every process whose ``sys.path`` holds the checkout's root, which
    is how a train worker finds the job it is sent."""
    rel = find(manifest, kind, name, ".py", root)
    return rel[:-len(".py")].replace(os.sep, ".")


def load_module(manifest: dict, kind: str, name: str, root: str = ROOT):
    return importlib.import_module(module_name(manifest, kind, name, root))


def metrics_of(manifest: dict, cell: str, group: str) -> list:
    """The manifest's ``end_to_end`` or ``per_layer`` entries that the
    cell reports: all but those whose ``workloads`` leaves it out."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def resolve_cell(manifest: dict, cell: str, group: str,
                 root: str = ROOT) -> dict:
    """Everything one run of ``cell`` needs, as plain data that can be
    sent to a worker: the workload entry, its configuration and traffic
    files, its architecture's reference and accounting modules, and for
    each metric of ``group`` its reader's module and arguments."""
    entries = [w for w in manifest["workloads"] if w["name"] == cell]
    if not entries:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json "
                       f"(it has: {known})")
    workload = entries[0]
    metrics = []
    for m in metrics_of(manifest, cell, group):
        spec = load_json(manifest, "metrics", m["name"], root)
        metrics.append({
            "name": m["name"], "unit": m["unit"],
            "reader": module_name(manifest, "readers", spec["reader"], root),
            "args": spec.get("args", {})})
    model = load_json(manifest, "configs", workload["config"], root)
    return {
        "workload": workload,
        "model": model,
        "traffic": load_json(manifest, "traffic", workload["traffic"], root),
        "reference": module_name(manifest, "references",
                                 model["reference"], root),
        "accounting": module_name(manifest, "accounting",
                                  model["reference"], root),
        "metrics": metrics,
    }
