#!/usr/bin/env python3
"""Records the small chip trace of the engine with its own spans that
``tests/chipbench`` reads.

    python3 chipbench/fixture_spans.py OUT_DIR

Runs the small format cell of ``tests/chipbench/data`` under the profiler
for a fraction of a second on the TPU, as ``fixture_trace.py`` does, and
writes ``OUT_DIR/fixture.xplane.pb`` (with the engine's ``engine.*``
spans) with ``OUT_DIR/fixture_scopes.json`` (the model scope of each
instruction of its programs) and ``OUT_DIR/fixture_spans.json``: what
:func:`chipbench.spans.reduce` made of them, which the test holds the
reduction to.
"""
from __future__ import annotations

import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(ROOT / "tests" / "chipbench")]

from chipbench import fixture_trace, run, spans, spec, trace  # noqa: E402
from chipbench.driver import Driver  # noqa: E402


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
        drv.run_backlog(fixture_trace.SECONDS, str(tdir))
        scopes = spans.scope_maps(drv)
        shutil.copy(trace.find_xplane(str(tdir)), out / "fixture.xplane.pb")
    (out / "fixture_scopes.json").write_text(json.dumps(scopes, indent=1)
                                             + "\n")
    got = spans.reduce(str(out / "fixture.xplane.pb"), scopes)
    (out / "fixture_spans.json").write_text(json.dumps(got, indent=1)
                                            + "\n")
    print(json.dumps(got)[:3000])


if __name__ == "__main__":
    main()
