#!/usr/bin/env python3
"""One run of one benchmark cell, on the chips of the machine it starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, traffic mix and metrics come from
``BENCHMARK.json`` at the root of the checkout (see ``chipbench/spec.py``).
The run fails, with no result, where JAX finds no TPU or fewer chips than
the cell asks for. Then it draws the weights on the chip from the seed,
builds the engine, loads or compiles and runs every program the cell's
traffic uses, brings the engine to its steady state, and measures for
``--seconds`` (``--trace 1``: at most TRACE_S, under the profiler). After
the window it reads the peak memory, frees the engine, checks a sample of
the served tokens against the plain reference, and prints the result as
the last line of standard output:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "compare": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics. The compared numbers, each with its limit, are also the
last lines of standard error. The compile cache is ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import compare, spec, trace  # noqa: E402
from chipbench.driver import Driver, log  # noqa: E402

TRACE_S = 8.0


class CompileCount:
    """Programs JAX compiles or loads from its persistent cache, from the
    moment this is made: after the warm-up there should be none."""

    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        self.requests += event == self.REQUESTS
        self.hits += event == self.HITS


def use_cache(root: pathlib.Path):
    """JAX's persistent compile cache at one fixed path in the checkout,
    every program in it, whatever the environment says.

    Not ``repro.launch.jitcache.use_compile_cache``: that one defers to a
    ``JAX_COMPILATION_CACHE_DIR`` from the environment, which could be
    shared by the two sides of a comparison, and keeps JAX's one-second
    floor, below which a program is compiled anew in every run."""
    import jax
    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU; JAX found "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devs)}")
    return devs


def make_weights(ref, hf, seed: int):
    """The weights, drawn on the device in one compiled call from the seed
    (its two 32-bit halves are arguments, so every seed shares it)."""
    import jax
    import jax.numpy as jnp
    s = int(seed) % 2 ** 64
    fn = jax.jit(functools.partial(ref.init_weights, hf))
    return jax.block_until_ready(
        fn(jnp.uint32(s & 0xFFFFFFFF), jnp.uint32(s >> 32)))


def rows_bucket(cell) -> int:
    return -(-cell.traffic["output"]["max"] // 128) * 128


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             traced: bool, *, bench_dir: pathlib.Path = spec.HERE,
             require_tpu: bool = True, t0: float = T0):
    """Everything of one run but the printing; returns (result, record)."""
    cell = spec.load_cell(root, workload, bench_dir)
    use_cache(root)
    import jax
    devs = devices(cell.chips) if require_tpu else jax.devices()
    count = CompileCount()
    marks = {"imports": time.perf_counter() - t0}
    ref = spec.reference(cell.config["reference"], bench_dir)
    hf = cell.config
    weights = make_weights(ref, hf, seed)
    marks["weights"] = time.perf_counter() - t0
    drv = Driver(cell, weights, seed, tracing=traced, bench_dir=bench_dir)
    drv.warm_up()
    marks["warm_up"] = time.perf_counter() - t0
    loaded = count.requests
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if traced else None
    window = min(seconds, TRACE_S) if traced else seconds
    if cell.traffic["loop"] == "open":
        drv.run_open(window, trace_dir)
    else:
        drv.run_backlog(window, trace_dir)
    rec = drv.rec
    rec.setup_s = rec.t_open - t0
    log(f"set-up marks (s from start) {marks}, window opened at "
        f"{rec.setup_s!r}; {count.requests} programs compiled or loaded "
        f"({count.requests - count.hits} compiled), "
        f"{count.requests - loaded} of them after the warm-up")
    rec.device_kind, rec.chips = devs[0].device_kind, cell.chips
    used = devs[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    late = drv.lateness()
    finished = drv.finished()
    if rec.open_loop:
        attempted = len(rec.measured())
        failed = rec.rejected + sum(r.tokens is None for r in rec.measured())
    else:
        attempted = sum(any(rec.in_window(t) for t in r.times)
                        for r in rec.reqs.values())
        failed = rec.rejected
    kernels = drv.kernel_maps() if traced else None
    drv.free()
    if trace_dir is not None:
        rec.trace = trace.reduce(trace.find_xplane(trace_dir), kernels)
        shutil.rmtree(trace_dir, ignore_errors=True)

    got = compare.program_numbers(
        ref, hf, weights, compare.sample(finished, seed),
        cell.config["engine"]["max_seq"], rows_bucket(cell))
    limits = cell.config["limits"]
    correct = got["rows"] > 0 and all(got[k] <= v for k, v in limits.items())

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = spec.metric_reader(m["name"], bench_dir)(rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if rec.trace is not None:
        device.update(busy_s=rec.trace["busy_s"],
                      window_s=rec.trace["window_s"])
        result["breakdown"] = {"device_ops": trace.top(rec.trace["ops"]),
                               "idle_gaps": trace.top(rec.trace["idle"])}
    result["compare"] = {k: {"value": got[k], "limit": v}
                         for k, v in limits.items()}
    log(f"window {rec.seconds!r} s, {len(rec.window_steps())} steps, "
        f"{len(rec.window_admits())} admissions; generator lateness "
        f"{late}; compared {got['rows']} served tokens")
    return result, rec


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {ROOT / 'src'}")
    result, _ = run_cell(ROOT, args.workload, args.seed, args.seconds,
                         bool(args.trace))
    for k, c in result["compare"].items():
        log(f"compare {k} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
