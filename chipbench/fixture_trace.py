#!/usr/bin/env python3
"""Records the small chip trace that ``tests/chipbench`` reads.

    python3 chipbench/fixture_trace.py OUT_DIR

Runs the small format cell of ``tests/chipbench/data`` (both Pallas
kernels, prefill, insert and decode) under the profiler for a fraction of
a second, on the TPU, and writes ``OUT_DIR/fixture.xplane.pb`` with
``OUT_DIR/fixture_kernels.json`` (which Pallas kernel each instruction
of its programs runs) and ``OUT_DIR/fixture.json``: what
:func:`chipbench.trace.reduce` made of them, which the test holds the
reduction to. It also prints the trace's planes
and lines, and a few events of each, for a reader who needs the names.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests" / "chipbench")]

from chipbench import run, spec, trace  # noqa: E402
from chipbench.driver import Driver  # noqa: E402

SECONDS = 0.05


def describe(path: str):
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            seen = set()
            for ev in evs:
                if ev.name in seen or len(seen) >= 6:
                    continue
                seen.add(ev.name)
                print(f"    {ev.name!r} {ev.duration_ns} ns "
                      f"{dict(ev.stats)!r}"[:400])


def main(argv=None):
    import tiny
    out = pathlib.Path((argv or sys.argv[1:])[0])
    out.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory() as tmp:
        root, bench_dir = tiny.make_root(pathlib.Path(tmp))
        cell = spec.load_cell(root, "fmt-batch", bench_dir)
        run.use_cache(root)
        run.devices(1)
        ref = spec.reference(cell.config["reference"], bench_dir)
        weights = run.make_weights(ref, cell.config, 7)
        drv = Driver(cell, weights, 7, tracing=True, bench_dir=bench_dir)
        drv.warm_up()
        tdir = pathlib.Path(tmp) / "trace"
        drv.run_backlog(SECONDS, str(tdir))
        kernels = drv.kernel_maps()
        path = trace.find_xplane(str(tdir))
        shutil.copy(path, out / "fixture.xplane.pb")
    (out / "fixture_kernels.json").write_text(json.dumps(kernels, indent=1)
                                              + "\n")
    describe(str(out / "fixture.xplane.pb"))
    got = trace.reduce(str(out / "fixture.xplane.pb"), kernels)
    (out / "fixture.json").write_text(json.dumps(got, indent=1) + "\n")
    print(json.dumps(got)[:3000])


if __name__ == "__main__":
    main()
