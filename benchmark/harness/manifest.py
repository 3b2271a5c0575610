"""BENCHMARK.json and the files it names: a cell's configuration, traffic
mix, reference and per-layer metric specs are all found BY NAME, so a later
PR adds files and manifest entries and edits nothing that is there."""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything it resolves to."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = self.entry["chips"]
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config = load_json(os.path.join(root, configs[self.entry["config"]]["file"]))
        bench = os.path.join(root, self.manifest["paths"][0])
        self.bench_dir = bench
        self.traffic = load_json(os.path.join(bench, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in self.manifest["end_to_end"] if self._mine(m)]
        self.per_layer = [m for m in self.manifest["per_layer"] if self._mine(m)]

    def _mine(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def reference_module(self, type_name: str | None = None):
        """``reference/<TYPE>.py`` of one of the configuration's types (of
        its only type, where it states one)."""
        if type_name is None:
            type_name = self.config["type"]
        return load_module(os.path.join(self.bench_dir, "reference", type_name + ".py"))

    def layer_spec(self, metric_name: str) -> dict:
        return load_json(os.path.join(self.bench_dir, "layer_metrics", metric_name + ".json"))


def load_module(path: str):
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def types_of(config: dict, rehearse: bool) -> list[dict]:
    """The data types a `sized` configuration states, each as its
    ``{"type", "state", "check"}`` block: the file's ``types`` list (a block
    may carry ``rehearse`` overrides of its own), or the one type that
    ``type``, ``state`` and ``check`` at the top of the file describe."""
    if "types" in config:
        blocks = [sized(b, rehearse) for b in config["types"]]
    else:
        blocks = [{k: config[k] for k in ("type", "state", "check")}]
    names = [b["type"] for b in blocks]
    if len(set(names)) != len(names):
        raise ValueError(f"a configuration states a type once: {names}")
    return blocks


def sized(block: dict, rehearse: bool) -> dict:
    """A config or traffic block with its ``rehearse`` overrides applied
    (tiny sizes for the CPU rehearsal; never for a measuring run)."""
    out = {k: v for k, v in block.items() if k != "rehearse"}
    if rehearse:
        out.update(block.get("rehearse", {}))
    return out
