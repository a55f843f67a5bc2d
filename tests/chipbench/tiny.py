"""A checkout in miniature: the benchmark's own files copied into a temporary
root, with small configurations and mixes beside them, so that a whole
run fits a test (and a chip trace small enough to keep). The open-loop
cells, which the benchmark has none of yet, report the chat tails."""
from __future__ import annotations

import json
import pathlib
import shutil

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
DATA = HERE / "data"

CELLS = {"f32-batch": ("tiny-f32", "tiny-batch"),
         "fmt-batch": ("tiny-fmt", "tiny-batch"),
         "fmt-chat": ("tiny-fmt", "tiny-chat"),
         "f32-chat": ("tiny-f32", "tiny-chat")}


def make_root(tmp: pathlib.Path):
    """(root, bench_dir): ``root/BENCHMARK.json`` is the repository's, with
    its configurations and cells swapped for the small ones; ``bench_dir``
    is a copy of ``chipbench/`` with the small mixes added."""
    bench_dir = tmp / "chipbench"
    shutil.copytree(REPO / "chipbench", bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for f in (DATA / "mixes").glob("*.json"):
        shutil.copy(f, bench_dir / "mixes" / f.name)
    (tmp / "configs").mkdir()
    for f in (DATA / "configs").glob("*.json"):
        shutil.copy(f, tmp / "configs" / f.name)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": n, "source": "tests/chipbench/data",
                         "file": f"configs/{n}.json", "reduced": [],
                         "why": "small"} for n in ("tiny-f32", "tiny-fmt")]
    bench["workloads"] = [{"name": w, "config": c, "traffic": t, "chips": 1,
                           "why": "small"} for w, (c, t) in CELLS.items()]
    chat = [w for w, (_, t) in CELLS.items() if t == "tiny-chat"]
    bench["end_to_end"] += [
        {"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
         "source": "host_clock", "workloads": chat}
        for n in ("itl_p95_ms", "ttft_p90_ms")]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp, bench_dir
