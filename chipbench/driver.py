"""Builds a cell's engine and drives it: set-up, steady state, the window.

The system under test is ``repro.launch.batching.ContinuousBatchingEngine``
at the configuration's sizes and arithmetic. The benchmark gives it the
weights (drawn by the configuration's reference module), composes its
prefill and decode programs with :func:`chipbench.compare.probe`, wraps
them in host spans and clocks, and drives ``submit``/``step`` from its own
loop: a standing backlog (``loop: backlog``) or an arrival schedule
(``loop: open``). Everything it learns goes into a :class:`Record`.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import inspect
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from chipbench import compare, traffic

QUEUE_DEPTH = 1 << 16     # the load loop, not the queue, bounds what waits

# The engine's programs the benchmark wraps, and the arguments it expects
# of each: an engine whose calls change shape has to fail here, not feed
# the admission and step clocks something else.
ENGINE_CALLS = {"_prefill": ("params", "tokens", "length"),
                "_decode": ("params", "cache", "tokens", "offsets"),
                "_insert": ("cache", "sl", "lane")}


@dataclasses.dataclass
class Req:
    rid: int
    prompt: List[int]
    max_new: int
    measured: bool
    due: Optional[float] = None     # host clock; None in a backlog
    submit: Optional[float] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[List[int]] = None
    logits: Optional[np.ndarray] = None


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    lanes: int          # active lanes the decode program served
    kv: int             # sum over them of the positions each attended
    decode_s: float
    admit_s: float
    queue: int          # requests waiting for a lane after the step


@dataclasses.dataclass
class Admit:
    t0: float
    t1: float           # first token in hand
    prompt: int


@dataclasses.dataclass
class Record:
    cell: Any
    arch: Dict[str, Any]
    fmt_map: Optional[Dict[str, Dict[str, int]]]
    seconds: float
    reqs: Dict[int, Req] = dataclasses.field(default_factory=dict)
    steps: List[Step] = dataclasses.field(default_factory=list)
    admits: List[Admit] = dataclasses.field(default_factory=list)
    t_open: float = 0.0
    t_close: float = 0.0
    t_end: float = 0.0      # the last request done, or the drain's end
    rejected: int = 0
    done: int = 0           # measured requests finished
    setup_s: float = 0.0
    device_kind: str = ""
    chips: int = 1
    trace: Optional[Dict[str, Any]] = None   # trace.reduce() of the window

    def in_window(self, t: float) -> bool:
        return self.t_open <= t <= self.t_close

    def window_steps(self) -> List[Step]:
        return [s for s in self.steps if self.in_window(s.t1)]

    def window_admits(self) -> List[Admit]:
        return [a for a in self.admits if self.in_window(a.t0)]

    def measured(self) -> List[Req]:
        return [r for r in self.reqs.values() if r.measured]

    @property
    def open_loop(self) -> bool:
        return self.cell.traffic["loop"] == "open"

    def gaps(self) -> List[float]:
        """Every inter-token gap: of the measured requests in an open
        loop, and of every gap that ends in the window in a backlog."""
        out: List[float] = []
        for r in (self.measured() if self.open_loop
                  else self.reqs.values()):
            t = np.asarray(r.times)
            g = np.diff(t)
            if not self.open_loop:
                g = g[(t[1:] >= self.t_open) & (t[1:] <= self.t_close)]
            out += g.tolist()
        return out


def span(tracing: bool, name: str):
    if not tracing:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Driver:
    """One engine for one cell, and the loop that drives it."""

    def __init__(self, cell, weights, seed: int, *, tracing: bool = False,
                 bench_dir=None):
        from repro.launch import batching, serve
        from repro.models.transformer import ArchConfig
        from chipbench import spec

        self.cell, self.seed, self.tracing = cell, seed, tracing
        conf = cell.config
        self.ref = spec.reference(conf["reference"],
                                  bench_dir or spec.HERE)
        self.arch = self.ref.program_arch(conf, cell.config_name)
        eng_conf = conf["engine"]
        self.page = eng_conf["page"]
        self.max_seq = eng_conf["max_seq"]
        self.fmt_map = conf.get("format_map")
        sc = serve.ServeConfig(arch=cell.config_name,
                               batch=eng_conf["lanes"],
                               max_seq=self.max_seq,
                               precision_layer_format=self.fmt_map)
        self.eng = batching.ContinuousBatchingEngine(
            ArchConfig(**self.arch), sc, weights,
            n_lanes=eng_conf["lanes"], max_seq=self.max_seq,
            page_size=self.page, queue_depth=QUEUE_DEPTH, keep_logits=True)
        self._compose()
        self.rec = Record(cell=cell, arch=self.arch, fmt_map=self.fmt_map,
                          seconds=0.0)
        self._recording = False

    # -- the engine's programs, composed and wrapped --------------------------

    def _compose(self):
        import jax
        import jax.numpy as jnp
        eng = self.eng
        ids = jnp.asarray(compare.probe_ids(self.arch["vocab"]))
        prefill, decode, insert = eng._prefill, eng._decode, eng._insert
        for fn, want in ((prefill, ENGINE_CALLS["_prefill"]),
                         (decode, ENGINE_CALLS["_decode"]),
                         (insert, ENGINE_CALLS["_insert"])):
            got = tuple(inspect.signature(fn).parameters)
            if got != want:
                raise RuntimeError(
                    f"chipbench: the engine's {fn.__name__}{got} is not the "
                    f"call {want} the benchmark wraps; its admission and "
                    f"step clocks would not mean what they say")

        def prefill_step(params, tokens, length):
            tok, row, cache = prefill(params, tokens, length)
            return tok, compare.probe(row[None], ids)[0], cache

        def decode_step(params, cache, tokens, offsets):
            nxt, rows, cache = decode(params, cache, tokens, offsets)
            return nxt, compare.probe(rows, ids), cache

        self.prefill_jit = jax.jit(prefill_step)
        self.decode_jit = jax.jit(decode_step, donate_argnums=(1,))
        self.insert_jit = insert
        eng._prefill = self._prefill
        eng._decode = self._decode
        eng._insert = self._insert

    def _prefill(self, params, tokens, length):
        if self._recording:
            self._admit_t0.append(time.perf_counter())
            self._admit_len.append(int(length))
        with span(self.tracing, "chipbench.prefill"):
            return self.prefill_jit(params, tokens, length)

    def _insert(self, cache, sl, lane):
        with span(self.tracing, "chipbench.insert"):
            return self.insert_jit(cache, sl, lane)

    def _decode(self, params, cache, tokens, offsets):
        if self._recording:
            live = [l for l in self.eng.lanes if l is not None]
            self._step_lanes = len(live)
            self._step_kv = sum(l.length + 1 for l in live)
        with span(self.tracing, "chipbench.decode"):
            return self.decode_jit(params, cache, tokens, offsets)

    # -- set-up -----------------------------------------------------------------

    def warm_up(self):
        """Run every program at every shape the cell's traffic uses: the
        prefill of each page bucket its prompts fall in, the insert and the
        decode step. With a warm compile cache this only loads programs."""
        import jax
        import jax.numpy as jnp
        eng = self.eng
        for pages in traffic.prefill_pages(self.cell.traffic, self.page):
            n = min(pages * self.page, self.max_seq)
            toks = jnp.zeros((1, n), jnp.int32)
            out = self._prefill(eng.params, toks, jnp.asarray(n, jnp.int32))
            eng.cache = self._insert(eng.cache, out[2],
                                     jnp.asarray(0, jnp.int32))
            jax.block_until_ready(eng.cache)
        z = jnp.zeros((eng.n_lanes,), jnp.int32)
        nxt, _, eng.cache = self._decode(eng.params, eng.cache, z, z)
        jax.block_until_ready(nxt)

    def kernel_maps(self) -> Dict[str, Dict[str, str]]:
        """``{program: {instruction: kernel}}`` of the cell's programs,
        from their compiled text (the same programs, so the compile cache
        serves them). Instructions that name different kernels in different
        prefill shapes are left out."""
        import jax
        import jax.numpy as jnp
        from chipbench import trace
        eng = self.eng
        sds = lambda t: jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        text = lambda f, *a: trace.kernel_map(f.lower(*a).compile().as_text())
        p = sds(eng.params)
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        out = {"decode_step": text(self.decode_jit, p, sds(eng.cache),
                                   i32(eng.n_lanes), i32(eng.n_lanes))}
        seen: Dict[str, Optional[str]] = {}
        for pages in traffic.prefill_pages(self.cell.traffic, self.page):
            n = min(pages * self.page, self.max_seq)
            for inst, k in text(self.prefill_jit, p, i32(1, n), i32()).items():
                seen[inst] = k if seen.get(inst, k) == k else None
        out["prefill_step"] = {i: k for i, k in seen.items() if k}
        return out

    # -- bookkeeping around one engine step ----------------------------------

    def _step(self):
        eng, rec = self.eng, self.rec
        self._admit_t0, self._admit_len = [], []
        self._step_lanes = self._step_kv = 0
        n_resp = len(eng.responses)
        dec0 = eng.decode_s
        t0 = time.perf_counter()
        with span(self.tracing, "chipbench.step"):
            busy = eng.step()
        t1 = time.perf_counter()
        with span(self.tracing, "chipbench.record"):
            admit_s = 0.0
            live = {l.req.rid: l for l in eng.lanes if l is not None}
            firsts = sorted(l.t_admit for l in live.values()
                            if not rec.reqs[l.req.rid].times)
            if self._recording and len(firsts) != len(self._admit_t0):
                raise RuntimeError(
                    f"chipbench: {len(self._admit_t0)} prefill calls in one "
                    f"step admitted {len(firsts)} requests; the benchmark "
                    f"pairs one prefill with each admission")
            for a0, n, a1 in zip(self._admit_t0, self._admit_len, firsts):
                rec.admits.append(Admit(a0, a1, n))
                admit_s += a1 - a0
            for lane in live.values():
                self._tokens(rec.reqs[lane.req.rid], len(lane.out),
                             lane.t_admit, t1)
            for resp in eng.responses[n_resp:]:
                r = rec.reqs[resp["id"]]
                self._tokens(r, len(resp["tokens"]), None, t1)
                r.tokens, r.logits = resp["tokens"], resp["logits"]
                rec.done += r.measured
            if self._step_lanes:
                rec.steps.append(Step(t0, t1, self._step_lanes,
                                      self._step_kv,
                                      eng.decode_s - dec0, admit_s,
                                      len(eng.queue)))
        return busy

    @staticmethod
    def _tokens(r: Req, n: int, t_first, t: float):
        if not r.times and n:
            r.times.append(t_first if t_first is not None else t)
        r.times += [t] * (n - len(r.times))

    def _submit(self, p: traffic.Planned, due: Optional[float]):
        from repro.launch.batching import Request
        r = Req(p.rid, p.prompt, p.max_new, p.measured, due=due)
        self.rec.reqs[p.rid] = r
        r.submit = time.perf_counter()
        if not self.eng.submit(Request(rid=p.rid, prompt=p.prompt,
                                       max_new_tokens=p.max_new)):
            self.rec.rejected += 1

    # -- the loops ----------------------------------------------------------------

    def run_backlog(self, seconds: float, trace_dir: Optional[str] = None):
        """Fill every lane, run until half the lanes have been recycled
        (steady state), then measure ``seconds`` with the queue held at
        ``backlog`` requests."""
        mix, eng, rec = self.cell.traffic, self.eng, self.rec
        stream = traffic.backlog(mix, self.seed, self.arch["vocab"])
        want = int(mix["backlog"])

        def top_up():
            while len(eng.queue) < want:
                self._submit(next(stream), None)

        self._recording = True
        while len(eng.responses) < eng.n_lanes // 2:
            top_up()
            self._step()
        gc.collect()
        gc.freeze()
        self._open(trace_dir)
        end = rec.t_open + seconds
        while True:
            top_up()
            self._step()
            if time.perf_counter() >= end:
                break
        self._close(trace_dir)
        gc.unfreeze()

    def run_open(self, seconds: float, trace_dir: Optional[str] = None):
        """Lead-in arrivals, then the window's arrivals at their due times;
        after the window the engine drains its measured requests, for at
        most ``drain_s``."""
        mix, eng, rec = self.cell.traffic, self.eng, self.rec
        plan = traffic.open_loop(mix, self.seed, self.arch["vocab"], seconds)
        self._recording = True
        gc.collect()
        gc.freeze()
        start = time.perf_counter() + mix["lead_s"]
        rec.t_open = start          # the lead-in is steady-state set-up
        i = 0
        n_measured = sum(p.measured for p in plan)
        traced = False
        deadline = start + seconds + mix["drain_s"]
        while True:
            now = time.perf_counter()
            if not traced and now >= start:
                self._open(trace_dir)
                rec.t_open, traced = start, True
            while i < len(plan) and start + plan[i].due <= now:
                self._submit(plan[i], start + plan[i].due)
                i += 1
            if (i == len(plan) and rec.done == n_measured) or now >= deadline:
                break
            if traced and rec.t_close == 0.0 and now >= start + seconds:
                self._close(trace_dir)
            if not self._step() and i < len(plan):
                with span(self.tracing, "chipbench.idle_wait"):
                    time.sleep(max(0.0, start + plan[i].due
                                   - time.perf_counter()))
        if rec.t_close == 0.0:
            self._close(trace_dir)
        rec.t_end = time.perf_counter()
        gc.unfreeze()       # not before: the drain serves the window's requests

    def _open(self, trace_dir):
        import jax
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
            self._window = jax.profiler.TraceAnnotation("chipbench.window")
            self._window.__enter__()
        self.rec.t_open = time.perf_counter()

    def _close(self, trace_dir):
        import jax
        jax.block_until_ready(self.eng.cache)
        self.rec.t_close = time.perf_counter()
        if trace_dir is not None:
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
        self.rec.seconds = self.rec.t_close - self.rec.t_open

    def finished(self) -> List[Dict[str, Any]]:
        """Requests that finished, as the comparison takes them."""
        return [{"rid": r.rid, "prompt": r.prompt, "tokens": r.tokens,
                 "logits": r.logits}
                for r in self.rec.reqs.values() if r.tokens is not None]

    def lateness(self) -> Dict[str, float]:
        late = [r.submit - r.due for r in self.rec.measured()
                if r.due is not None and r.submit is not None]
        if not late:
            return {"late_max_ms": 0.0, "late_p99_ms": 0.0}
        return {"late_max_ms": 1e3 * max(late),
                "late_p99_ms": 1e3 * float(np.percentile(late, 99))}

    def free(self):
        """Drop the engine's cache and programs; the weights stay (the
        benchmark made them, and the reference reads them)."""
        self.eng.cache = None
        self.eng = None
        gc.collect()


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)
