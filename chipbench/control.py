#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s> [--out FILE]

In one process, for each seed: the weights and the engine of a run, a
window of ``--seconds`` at the cell's own load, the sample a run compares,
and the program's ``logit_gap`` and ``logit_dev`` against the reference.
For the control seeds, the control's readings on the same sample too: the
configuration's ``control`` entry names it,

- ``{"kind": "reference", "precision": "bf16x3"}``: the reference in that
  precision, in the program's place;
- ``{"kind": "format", "k": 8}``: the program's own format path with every
  scope of the map narrowed to ``k`` significant bits, run over each whole
  sequence.

One JSON line per seed on standard output (and in ``--out``). The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import compare, run, spec  # noqa: E402
from chipbench.driver import Driver, log  # noqa: E402


def format_control(cell, ref, weights, reqs, seq_len, n_rows, k: int):
    """The program's format path under the map narrowed to ``k`` bits: its
    first choice at each served row, and its logits there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import batching, serve
    from repro.models import transformer as T
    from repro.models.transformer import ArchConfig

    conf = cell.config
    narrow = {s: dict(f, k=k) for s, f in conf["format_map"].items()}
    sc = serve.ServeConfig(arch=cell.config_name, batch=1, max_seq=seq_len,
                           precision_layer_format=narrow)
    bk = batching.make_backend(sc)
    arch = ArchConfig(**ref.program_arch(conf, cell.config_name))
    ids = jnp.asarray(compare.probe_ids(conf["vocab_size"]))

    @jax.jit
    def rows_of(w, seq, rows):
        lg = T.forward(bk, w, arch, seq[None, :])[0][0][rows]
        am = jnp.argmax(lg, -1).astype(jnp.int32)
        return jnp.max(lg, -1), lg[:, ids], am

    emitted, picks = [], []
    for r in reqs:
        seq, rows, _, n = compare.padded(r, seq_len, n_rows)
        best, probes, am = (np.asarray(a)[:n] for a in
                            jax.device_get(rows_of(weights, seq, rows)))
        emitted.append(np.concatenate([best[:, None], probes], axis=1))
        picks.append(am)
    ref_rows = compare.reference_rows(ref, conf, weights, reqs, seq_len,
                                      n_rows, tokens=picks)
    return compare.numbers(ref_rows, emitted)


def control_numbers(cell, ref, weights, reqs, seq_len, n_rows):
    c = cell.config["control"]
    if c["kind"] == "reference":
        return compare.reference_control(ref, cell.config, weights, reqs,
                                         seq_len, n_rows, c["precision"])
    if c["kind"] == "format":
        return format_control(cell, ref, weights, reqs, seq_len, n_rows,
                              c["k"])
    raise ValueError(f"unknown control {c!r}")


def readings(root, workload, seeds, control_seeds, seconds, *,
             bench_dir=spec.HERE, require_tpu=True):
    cell = spec.load_cell(root, workload, bench_dir)
    run.use_cache(root)
    if require_tpu:
        run.devices(cell.chips)
    ref = spec.reference(cell.config["reference"], bench_dir)
    seq_len = cell.config["engine"]["max_seq"]
    n_rows = run.rows_bucket(cell)
    for seed in seeds:
        t0 = time.perf_counter()
        weights = run.make_weights(ref, cell.config, seed)
        drv = Driver(cell, weights, seed, bench_dir=bench_dir)
        drv.warm_up()
        if cell.traffic["loop"] == "open":
            drv.run_open(seconds)
        else:
            drv.run_backlog(seconds)
        finished = drv.finished()
        drv.free()
        reqs = compare.sample(finished, seed)
        out = {"workload": workload, "seed": seed,
               "setup_s": drv.rec.t_open - t0, "finished": len(finished),
               "program": compare.program_numbers(ref, cell.config, weights,
                                                  reqs, seq_len, n_rows)}
        if seed in control_seeds:
            out["control"] = control_numbers(cell, ref, weights, reqs,
                                             seq_len, n_rows)
        del weights
        yield out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    for line in readings(ROOT, args.workload, seeds, ctrl, args.seconds):
        text = json.dumps(line)
        log(text)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")


if __name__ == "__main__":
    main()
