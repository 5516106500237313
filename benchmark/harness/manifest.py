"""Everything the harness knows about a cell, found by name.

`BENCHMARK.json` names cells, configurations and metrics; whatever
belongs to one of them sits in a file of its own under `benchmark/`:
`configs/<config>.json`, `traffic/<mix>.json`, `metrics/<metric>.json`
(a metric split by cells, `<quantity>.<cells>`, reads `<quantity>.json`
where it has no file of its own), and the Python a file names
(`runners/<kind>.py`, `models/<family>.py`, `readers/<reader>.py`,
`references/<name>.py`, `flops/<name>.py`). A later PR adds files and
entries; nothing here lists them.
"""

from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def manifest():
    return load_json(ROOT, "BENCHMARK.json")


def metric_spec(name):
    """The reader and arguments of a metric: `metrics/<name>.json`, or
    the file of the longest dotted prefix of the name that has one, so
    that `x.serve` and `x.train` can share `x.json`."""
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        path = os.path.join(BENCH_DIR, "metrics", ".".join(parts[:n]) + ".json")
        if os.path.exists(path):
            return load_json(path)
    raise SystemExit(f"no file under benchmark/metrics/ for metric {name!r}")


def plugin(group, name):
    """The module `benchmark/<group>/<name>.py`."""
    return importlib.import_module(f"benchmark.{group}.{name}")


def model_adapter(config, needs=()):
    """The model adapter of a configuration's `family`: the module
    `benchmark/models/<family>.py`, through which alone the runners,
    `sweep.py` and the tests reach the program's model
    (`models/dense.py` says what one holds). `needs` are the names the
    caller takes from it: a family that lacks one cannot run that kind
    of cell yet, and the run ends here, at set-up, saying which."""
    family = config["family"]
    adapter = plugin("models", family)
    missing = [n for n in needs if not hasattr(adapter, n)]
    if missing:
        raise SystemExit(
            f"model family {family!r} cannot run a cell of kind "
            f"{config.get('kind')!r} yet: benchmark/models/{family}.py has no "
            f"{', '.join(missing)}")
    return adapter


class Cell:
    """One entry of `workloads` with the files it names."""

    def __init__(self, name, bench=None):
        bench = bench or manifest()
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = load_json(ROOT, configs[self.entry["config"]]["file"])
        self.traffic = load_json(BENCH_DIR, "traffic",
                                 self.entry["traffic"] + ".json")
        self.metrics = {"end_to_end": [], "per_layer": []}
        for group in self.metrics:
            for m in bench[group]:
                if name in m.get("workloads", [name]):
                    self.metrics[group].append(
                        {**metric_spec(m["name"]), **m})

    def runner(self):
        return plugin("runners", self.config["kind"])
