"""A benchmark root with the repository's cells cut to a tiny size, for
tests on the CPU: Kronecker scale 10."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO / "src") not in sys.path:
    sys.path.insert(0, str(REPO / "src"))

from bench import harness  # noqa: E402
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
SNAPSHOT = "g500-22-snapshot.khop2-closed16"


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    (tmp / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "metrics"):
        shutil.copytree(REPO / "bench" / sub, tmp / "bench" / sub)
    for c in spec["configs"]:
        cfg = json.loads((REPO / c["file"]).read_text())
        cfg["graph"]["scale"] = 10
        (tmp / c["file"]).write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run_cell(monkeypatch, root: pathlib.Path, cell: str, *, seed: int = 3,
             seconds: float = 1.5, trace: bool = False) -> harness.Run:
    """One run of ``cell`` under ``root``, with the chip gate stubbed and
    the persistent compilation cache left alone."""
    monkeypatch.setattr(harness, "enable_cache", lambda: None)
    return harness.execute(harness.load_cell(root, cell), seed, seconds,
                           trace, DEVICE, time.time())
