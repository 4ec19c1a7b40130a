"""``BENCHMARK.json`` and the files it names. Whatever belongs to one
configuration, traffic mix, loop kind or per-layer metric is a file of its
own, found by name: adding one edits no file that is there."""

import importlib
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_manifest(path=None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES = {}


def load_module(directory: str, name: str):
    """Import ``<BENCH_DIR>/<directory>/<name>.py`` by path (metric names hold
    dots, so they are no module names), once. None when there is no such
    file."""
    path = os.path.join(BENCH_DIR, directory, name + ".py")
    if path in _MODULES:
        return _MODULES[path]
    if not os.path.exists(path):
        return None
    mod_name = "benchmark_%s_%s" % (directory, "".join(
        c if c.isalnum() else "_" for c in name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    _MODULES[path] = module
    return module


def _need(directory: str, name: str):
    module = load_module(directory, name)
    if module is None:
        raise SystemExit(
            f"benchmark: no file benchmark/{directory}/{name}.py")
    return module


class Cell:
    """One entry of ``workloads`` with its configuration and traffic files
    and the metrics it reports. ``data_dir`` holds ``traffic/`` and
    ``limits/`` (the benchmark's own directory; the tests keep theirs
    apart)."""

    def __init__(self, manifest: dict, name: str, data_dir: str = BENCH_DIR):
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"benchmark: no workload {name!r}; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config_name = self.entry["config"]
        self.config = _load_json(
            os.path.join(ROOT, configs[self.config_name]["file"]))
        self.traffic_name = self.entry["traffic"]
        self.data_dir = data_dir
        self.traffic = _load_json(os.path.join(
            data_dir, "traffic", self.traffic_name + ".json"))
        self.end_to_end = [
            m for m in manifest["end_to_end"]
            if "workloads" not in m or name in m["workloads"]
        ]
        self.per_layer = [
            m for m in manifest["per_layer"]
            if "workloads" not in m or name in m["workloads"]
        ]

    def model(self):
        """The configuration's model family as the program builds it:
        ``benchmark/models/<config["model"]>.py``."""
        return _need("models", self.config["model"])

    def reference(self):
        """Its plain reference: ``benchmark/references/<config["reference"]>
        .py``, which imports nothing of the program."""
        return _need("references", self.config["reference"])

    def limits(self) -> dict:
        """What ``correct`` holds each compared number to:
        ``limits/<cell>.json``."""
        return _load_json(os.path.join(
            self.data_dir, "limits", self.name + ".json"))["limits"]

    def loop(self):
        """The loop kind's module: ``benchmark/loops/<kind>.py``."""
        kind = self.traffic["loop"].replace("-", "_")
        try:
            return importlib.import_module("benchmark.loops." + kind)
        except ModuleNotFoundError as e:
            if e.name != "benchmark.loops." + kind:
                raise
            raise SystemExit(f"benchmark: no loop kind {kind!r}") from None
