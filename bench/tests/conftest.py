"""CPU rehearsal of the benchmark: tiny sizes, interpreted kernels.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests

Four virtual CPU devices stand in for the four-chip layout.
"""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4").strip()

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(ROOT / "bench"))

import pytest  # noqa: E402

def copy_benchmark(src: Path, dest: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` of ``src``, without the tests."""
    dest.mkdir(parents=True, exist_ok=True)
    shutil.copy(src / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(src / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return dest


def _rehearse(path: Path) -> None:
    """Put the file's own ``rehearsal`` sizes, which a test run holds, over
    its real ones; every other key stays as committed."""
    data = json.loads(path.read_text())
    if "rehearsal" not in data:
        raise KeyError(f"{path} has no 'rehearsal' sizes for the CPU tests")
    data.update(data["rehearsal"])
    path.write_text(json.dumps(data))


def make_tiny_checkout(dest: Path, src: Path = ROOT) -> Path:
    """A checkout of ``src``'s benchmark with every configuration and
    traffic mix that a cell names cut to its rehearsal sizes."""
    copy_benchmark(src, dest)
    spec = json.loads((dest / "BENCHMARK.json").read_text())
    for c in spec["configs"]:
        _rehearse(dest / c["file"])
    for name in sorted({w["traffic"] for w in spec["workloads"]}):
        _rehearse(dest / "bench" / "traffic" / f"{name}.json")
    return dest


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_tiny_checkout(tmp_path_factory.mktemp("checkout"))


@pytest.fixture(scope="session")
def bench_spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="session")
def recorded_trace() -> Path:
    return ROOT / "bench" / "tests" / "data" / "hotspot-hybrid.xplane.pb.gz"
