"""Finding a cell's parts by the names ``BENCHMARK.json`` gives them.

* a workload names its configuration and its traffic mix;
* a configuration's ``file`` holds its sizes, and its ``problem`` key names
  the yardstick ``bench/problems/<problem>.py`` and the per-chunk glue
  ``bench/glue/<problem>.py``;
* a traffic mix is ``bench/traffic/<traffic>.json``;
* a per-layer metric is read by ``bench/metrics/<name>.py``.

A problem module gives ``generate(cfg, seed)``, ``reference(prob)``,
``control(prob, device)`` and ``chunk_work(prob, start, stop)``; its glue
gives ``make(prob, acc_chunk)``, whose closures include
``begin_loop(loop)``, told the number of each loop from 0 (the first
warm-up loop).  A problem whose answer changes from loop to loop sets
``PER_LOOP = True``; its ``reference`` and ``control`` then take
``loop=i`` and give loop ``i``'s answer.  Every loop's inputs are made in
``generate``, outside the measured window, and ``begin_loop`` does only
what a caller would do to hand them over.

A new cell, mix or metric is new files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional


class Benchmark:
    """``BENCHMARK.json`` of a checkout, and the files it names."""

    def __init__(self, root) -> None:
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self.bench = self.root / self.spec["paths"][0]

    def workload(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> Dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        return json.loads((self.bench / "traffic" / f"{name}.json").read_text())

    def problem(self, kind: str):
        return load_file(self.bench / "problems" / f"{kind}.py")

    def glue(self, kind: str):
        return load_file(self.bench / "glue" / f"{kind}.py")

    def end_to_end(self) -> List[Dict]:
        """Every cell reports every end-to-end metric."""
        return self.spec["end_to_end"]

    def per_layer(self, workload: str) -> List[Dict]:
        """The per-layer metrics whose ``workloads`` list names this cell."""
        out = []
        for m in self.spec["per_layer"]:
            if "workloads" not in m:
                raise KeyError(f"per-layer metric {m['name']!r} has no 'workloads' list")
            if workload in m["workloads"]:
                out.append(m)
        return out

    def metric_reader(self, name: str) -> Callable:
        return load_file(self.bench / "metrics" / f"{name}.py").read


def load_file(path: Path):
    """Import ``path`` as a module, once per process."""
    path = Path(path).resolve()
    key = "bench_" + re.sub(r"\W", "_", str(path))
    mod: Optional[object] = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        if spec is None or spec.loader is None:
            raise ImportError(f"cannot load {path}")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return mod
