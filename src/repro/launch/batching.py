"""Continuous batching: a decode scheduler over a lane-structured KV cache.

The classic serving loop (:mod:`repro.launch.serve`) runs lock-step: one
prefill, then B sequences decode together and finish together. This module
adds the production shape — a persistent decode batch of ``n_lanes`` lanes
that requests join and leave independently:

- **Admission control**: a bounded FIFO queue in front of the lanes; a
  request is admitted when a lane is free AND its worst-case KV footprint
  (``ceil((prompt + max_new) / page_size)`` fixed-size pages) fits the page
  pool. Reserving worst-case at admission means an admitted request can
  never OOM mid-flight — the rejection happens at the door, with a metric,
  not at token 37. Over-capacity submissions are rejected outright.
- **Batched prefill-insert**: a new request prefills at batch 1 (padded to
  a whole number of pages) and its cache slice + per-lane index are
  inserted into the running [L, B, ...] cache at the free lane —
  the decode batch never drains to let someone in.
- **Lane recycling**: on EOS / max-new-tokens the lane's pages return to
  the pool and the lane is immediately reusable; stale cache contents need
  no scrubbing because every mask in the ragged decode path is
  length-limited (positions ≥ the lane's length are unreachable).

Bit-for-bit contract: a request's tokens are identical to running that
request ALONE through the single-device eager reference
(:func:`reference_generate`: ``UnrolledLayerLoop``-composed backend, batch
1, the same page-padded prefill shape, no mesh). This holds because every
per-lane row of the transformer is bitwise independent of batch
composition — f32 matmul rows don't see other rows, masked-softmax columns
beyond a lane's length contribute exact zeros, cache writes touch each
lane's own window only — which the engine tests assert against staggered-arrival
schedules. Prefill is compared at like shapes: a GEMM's rows are not
bitwise independent of its row COUNT (XLA:CPU blocks a 6-row and a 16-row
product differently), so the reference pads exactly as the engine does.

Mesh execution: with a (data, model) mesh from
:func:`repro.launch.mesh.make_serving_mesh`, params shard column-parallel
(:func:`repro.parallel.sharding.shard_params_serving` — output dims only,
never a contraction, so the math stays bitwise), lanes shard over "data",
and the scanned layer body re-constrains activations each layer
(``shard_hint('act_batch')``).
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.core.backend import JOps, UnrolledLayerLoop
from repro.launch import mesh as meshlib
from repro.launch.jitcache import use_compile_cache
from repro.launch import serve
from repro.models import transformer as T
from repro.parallel import sharding as sh

log = obs.get_logger("batching")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: Sequence[int]
    max_new_tokens: int = 16
    arrival_step: int = 0


@dataclasses.dataclass
class _Lane:
    req: Request
    length: int                 # tokens currently in this lane's cache
    pages: int                  # pages reserved from the pool
    out: List[int] = dataclasses.field(default_factory=list)
    t_admit: float = 0.0
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)


def make_backend(sc: serve.ServeConfig, *, mesh=None, unrolled: bool = False):
    """The serving backend for a ServeConfig — optionally composed with
    :class:`UnrolledLayerLoop` (the eager per-layer differential baseline;
    scope resolution degrades to the static ``layer{i}`` path, which the
    lane machinery is bitwise against)."""
    dt = jnp.bfloat16 if sc.compute_dtype == "bfloat16" else jnp.float32

    def cls(base):
        if not unrolled:
            return base
        return type("Unrolled" + base.__name__, (UnrolledLayerLoop, base), {})

    if sc.precision_layer_format:
        return cls(serve.FormatQuantJOps)(sc.precision_layer_format, None,
                                          dt, jnp.float32, mesh=mesh)
    if sc.precision_layer_k:
        if sc.precision_k is None:
            raise ValueError("precision_layer_k needs precision_k")
        return cls(serve.MixedQuantJOps)(sc.precision_layer_k, sc.precision_k,
                                         dt, jnp.float32, mesh=mesh)
    if sc.precision_k is not None:
        return cls(serve.QuantJOps)(sc.precision_k, dt, jnp.float32,
                                    mesh=mesh)
    return cls(JOps)(dt, jnp.float32, mesh=mesh)


class ContinuousBatchingEngine:
    """Decode scheduler: admission queue → lanes → recycled lanes.

    ``params`` may live on host; with a mesh they are placed under the
    bitwise-safe column-parallel serving sharding. ``registry`` (a
    :class:`repro.obs.MetricsRegistry`) receives occupancy / queue-depth
    gauges, request counters and the ``serve.decode_latency_s`` histogram
    of decode steps. :meth:`step` opens ``engine.*`` spans.
    With ``keep_logits`` every response also carries ``"logits"``, an f32
    ``[n_tokens, vocab]`` host array: row t is the distribution token t
    was taken from (row 0 from the prefill, the rest from decode steps) —
    what a correctness check compares against a reference.
    """

    def __init__(self, arch_cfg, sc: serve.ServeConfig, params, *,
                 mesh=None, n_lanes: int = 4, max_seq: int = 64,
                 page_size: int = 16, queue_depth: int = 8,
                 total_pages: Optional[int] = None, eos_id: int = -1,
                 registry=None, certset=None, keep_logits: bool = False):
        if max_seq % page_size:
            raise ValueError(f"max_seq {max_seq} must be a whole number of "
                             f"pages (page_size {page_size})")
        self.arch_cfg, self.sc = arch_cfg, sc
        self.n_lanes, self.max_seq = n_lanes, max_seq
        self.page_size = page_size
        self.queue_depth = queue_depth
        self.total_pages = (n_lanes * (max_seq // page_size)
                            if total_pages is None else total_pages)
        self.free_pages = self.total_pages
        self.eos_id = eos_id
        self.registry = registry
        self.certset = certset
        self.keep_logits = keep_logits
        self.mesh = mesh
        self.bk = make_backend(sc, mesh=mesh)

        self.queue: Deque[Request] = collections.deque()
        self.lanes: List[Optional[_Lane]] = [None] * n_lanes
        self.responses: List[Dict[str, Any]] = []
        self.steps = 0
        self.admitted = 0
        self.decode_tokens = 0
        self.decode_s = 0.0

        cache = T.init_cache(arch_cfg, n_lanes, max_seq, jnp.float32,
                             per_lane_idx=True)
        if not (isinstance(cache, dict) and "idx" in cache):
            raise NotImplementedError(
                f"continuous batching needs an indexed KV cache "
                f"(family {arch_cfg.family!r} has none)")
        if mesh is not None:
            p_sh = sh.shard_params_serving(params, mesh)
            self._c_sh = sh.shard_cache_serving(cache, mesh)
            params = jax.device_put(params, p_sh)
            cache = jax.device_put(cache, self._c_sh)
        self.params, self.cache = params, cache
        self._build_steps()

    # -- jitted steps -------------------------------------------------------

    def _build_steps(self):
        cfg, bk, S = self.arch_cfg, self.bk, self.max_seq
        # logits leave the device only when asked for: on a mesh they are
        # gathered from vocab shards
        keep = self.keep_logits

        def prefill_fn(params, tokens, length):
            # batch-1 prefill into a fresh cache; bitwise == the same rows
            # of any batched prefill (row independence), == the unpadded
            # prefill (pad columns are causally masked). The returned
            # slice's index is pinned to the TRUE length so pad-region
            # junk is overwritten by the first decode steps.
            cache = T.init_cache(cfg, 1, S, jnp.float32, per_lane_idx=True)
            logits, cache = T.forward(bk, params, cfg, tokens, cache=cache,
                                      q_offset=jnp.zeros((1,), jnp.int32))
            row = logits[0, length - 1, :]
            tok = jnp.argmax(row, axis=-1)
            cache = {**cache, "idx": jnp.full_like(cache["idx"], length)}
            return tok.astype(jnp.int32), row if keep else None, cache

        def insert_fn(cache, sl, lane):
            def one(b, s):
                z = jnp.zeros((), jnp.int32)
                starts = (z, lane) + (z,) * (b.ndim - 2)
                return jax.lax.dynamic_update_slice(
                    b, s.astype(b.dtype), starts)
            return jax.tree_util.tree_map(one, cache, sl)

        def decode_fn(params, cache, tokens, offsets):
            # pin every lane's write index to the scheduler's view of its
            # length — idle lanes neither drift nor clamp at the buffer edge
            idx = jnp.broadcast_to(offsets[None, :], cache["idx"].shape)
            cache = {**cache, "idx": idx.astype(cache["idx"].dtype)}
            logits, cache = T.forward(bk, params, cfg, tokens[:, None],
                                      cache=cache, q_offset=offsets)
            rows = logits[:, -1, :]
            nxt = jnp.argmax(rows, axis=-1)
            return nxt.astype(jnp.int32), rows if keep else None, cache

        if self.mesh is not None:
            rep = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec())
            self._prefill = jax.jit(prefill_fn)
            self._insert = jax.jit(insert_fn, donate_argnums=(0,),
                                   out_shardings=self._c_sh)
            self._decode = jax.jit(decode_fn, donate_argnums=(1,),
                                   out_shardings=(rep, rep, self._c_sh))
        else:
            self._prefill = jax.jit(prefill_fn)
            self._insert = jax.jit(insert_fn, donate_argnums=(0,))
            self._decode = jax.jit(decode_fn, donate_argnums=(1,))

    # -- scheduling ---------------------------------------------------------

    def _pages_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    def submit(self, req: Request) -> bool:
        """Enqueue; False = rejected (queue full / can never fit)."""
        worst = len(req.prompt) + req.max_new_tokens
        if worst > self.max_seq or self._pages_for(worst) > self.total_pages:
            self._count("serve.requests_rejected{reason=too_long}")
            return False
        if len(self.queue) >= self.queue_depth:
            self._count("serve.requests_rejected{reason=queue_full}")
            return False
        self.queue.append(req)
        return True

    def _count(self, name, inc=1):
        if self.registry is not None:
            self.registry.counter(name, inc)

    def _gauges(self):
        if self.registry is None:
            return
        occ = sum(l is not None for l in self.lanes) / self.n_lanes
        self.registry.gauge("serve.batch_occupancy", occ)
        self.registry.gauge("serve.admission_queue_depth", len(self.queue))
        self.registry.gauge("serve.kv_pages_free", self.free_pages)

    def _admit(self):
        rec = obs.recording()
        while self.queue:
            free = [i for i, l in enumerate(self.lanes) if l is None]
            if not free:
                break
            req = self.queue[0]
            P = len(req.prompt)
            pages = self._pages_for(P + req.max_new_tokens)
            if pages > self.free_pages:
                break                      # honest FIFO: no head-of-line skip
            self.queue.popleft()
            lane = free[0]
            # pad the prompt to whole pages: one prefill compilation per
            # page-count bucket, and the cache slice lands page-aligned
            toks = page_padded(req.prompt, self.page_size, self.max_seq)
            attrs = (dict(n=1, rid=req.rid, lane=lane, tokens=P,
                          padded=toks.shape[1]) if rec else {})
            with obs.span("engine.admit", **attrs):
                with obs.span("engine.prefill"):
                    tok, row, sl = self._prefill(self.params,
                                                 jnp.asarray(toks),
                                                 jnp.asarray(P, jnp.int32))
                with obs.span("engine.insert"):
                    self.cache = self._insert(self.cache, sl,
                                              jnp.asarray(lane, jnp.int32))
                with obs.span("engine.first_token"):
                    first = int(tok)
                    t_admit = time.perf_counter()
                    if self.keep_logits:
                        row = np.asarray(row)
                self.free_pages -= pages
                self.lanes[lane] = _Lane(req=req, length=P, pages=pages,
                                         out=[first], t_admit=t_admit)
                if self.keep_logits:
                    self.lanes[lane].logits.append(row)
                self.admitted += 1
                self._count("serve.requests_admitted")
                self._finish_if_done(lane, first)

    def _finish_if_done(self, i: int, last_tok: int):
        lane = self.lanes[i]
        if lane is None:
            return
        done = (last_tok == self.eos_id
                or len(lane.out) >= lane.req.max_new_tokens
                or lane.length + 1 >= self.max_seq)
        if not done:
            return
        r: Dict[str, Any] = {"id": lane.req.rid, "tokens": list(lane.out),
                             "n_prompt": len(lane.req.prompt)}
        if self.keep_logits:
            r["logits"] = np.stack(lane.logits)
        if self.certset is not None:
            r["certificate"] = dict(self.certset.error_bars(),
                                    params_digest=self.certset.params_digest)
        self.responses.append(r)
        self.free_pages += lane.pages
        self.lanes[i] = None
        self._count("serve.requests_completed")

    def step(self) -> bool:
        """Admit + one decode step for every active lane. False = idle.

        Spans (``obs.span``, recorded only under a tracer or the JAX
        profiler): ``engine.step`` (attrs ``queue``, ``admitted``) holds
        one ``engine.admit`` per admission (⊃ ``engine.prefill``,
        ``engine.insert``, ``engine.first_token``), then
        ``engine.schedule``, ``engine.decode`` (attrs ``lanes``, ``kv``:
        the positions the active lanes attend), ``engine.decode_wait``,
        ``engine.readback`` and ``engine.bookkeep``."""
        rec = obs.recording()
        with obs.span("engine.step") as step_span:
            before = self.admitted
            self._admit()
            with obs.span("engine.schedule"):
                self._gauges()
                active = [i for i, l in enumerate(self.lanes)
                          if l is not None]
                if active:
                    tokens = np.zeros((self.n_lanes,), np.int32)
                    offsets = np.zeros((self.n_lanes,), np.int32)
                    for i in active:
                        tokens[i] = self.lanes[i].out[-1]
                        offsets[i] = self.lanes[i].length
                    t0 = time.perf_counter()
                    tokens, offsets = jnp.asarray(tokens), jnp.asarray(offsets)
            if rec:
                step_span.set(queue=len(self.queue),
                              admitted=self.admitted - before)
            if not active:
                return bool(self.queue)
            attrs = (dict(lanes=len(active),
                          kv=sum(self.lanes[i].length + 1 for i in active))
                     if rec else {})
            with obs.span("engine.decode", **attrs):
                nxt, rows, self.cache = self._decode(self.params, self.cache,
                                                     tokens, offsets)
            with obs.span("engine.decode_wait"):
                nxt = jax.block_until_ready(nxt)
            dt = time.perf_counter() - t0
            with obs.span("engine.readback"):
                nxt = np.asarray(nxt)
                rows = None if rows is None else np.asarray(rows)
            with obs.span("engine.bookkeep"):
                self.steps += 1
                self.decode_tokens += len(active)
                self.decode_s += dt
                if self.registry is not None:
                    self.registry.observe("serve.decode_latency_s", dt)
                    self._count("serve.tokens", len(active))
                for i in active:
                    lane = self.lanes[i]
                    lane.length += 1
                    lane.out.append(int(nxt[i]))
                    if rows is not None:
                        lane.logits.append(rows[i])
                    self._finish_if_done(i, int(nxt[i]))
        return True

    def run(self, requests: Sequence[Request] = (),
            max_steps: int = 100_000) -> List[Dict[str, Any]]:
        """Drive the schedule to completion: requests enter the queue at
        their ``arrival_step``; returns the responses in completion order."""
        pending = sorted(requests, key=lambda r: r.arrival_step)
        pi = 0
        for _ in range(max_steps):
            while pi < len(pending) and pending[pi].arrival_step <= self.steps:
                self.submit(pending[pi])
                pi += 1
            busy = self.step()
            if (not busy and pi >= len(pending)
                    and all(l is None for l in self.lanes)
                    and not self.queue):
                break
        self._gauges()
        if self.registry is not None and self.decode_s > 0:
            self.registry.gauge("serve.decode_tokens_per_s",
                                self.decode_tokens / self.decode_s)
        return self.responses


def page_padded(prompt: Sequence[int], page_size: int,
                max_seq: int) -> np.ndarray:
    """``[1, Ppad]`` token ids: the prompt zero-padded to whole pages (at
    most ``max_seq``). The pad columns sit after every real position, so
    causal masking keeps them out of the real rows."""
    P = len(prompt)
    toks = np.zeros((1, min(max_seq, page_size * -(-P // page_size))),
                    np.int32)
    toks[0, :P] = np.asarray(prompt, np.int32)
    return toks


def reference_generate(arch_cfg, sc: serve.ServeConfig, params,
                       prompt: Sequence[int], max_new_tokens: int, *,
                       max_seq: int, page_size: int,
                       eos_id: int = -1) -> List[int]:
    """Single-device eager reference: batch 1, unrolled per-layer backend,
    no mesh — the bitwise oracle the engine must match. ``max_seq`` and
    ``page_size`` must equal the engine's: the cache width is part of the
    masked-softmax shape, and the prefill runs at the engine's page-padded
    shape (pad columns masked, the write index pinned to the true
    length)."""
    bk = make_backend(sc, mesh=None, unrolled=True)
    cache = T.init_cache(arch_cfg, 1, max_seq, jnp.float32,
                         per_lane_idx=True)
    toks = jnp.asarray(page_padded(prompt, page_size, max_seq))
    logits, cache = T.forward(bk, params, arch_cfg, toks, cache=cache,
                              q_offset=jnp.zeros((1,), jnp.int32))
    P = len(prompt)
    cache = {**cache, "idx": jnp.full_like(cache["idx"], P)}
    tok = int(jnp.argmax(logits[0, P - 1, :]))
    out = [tok]
    while (tok != eos_id and len(out) < max_new_tokens
           and P + len(out) < max_seq):
        offs = jnp.asarray([P + len(out) - 1], jnp.int32)
        logits, cache = T.forward(bk, params, arch_cfg,
                                  jnp.asarray([[tok]], jnp.int32),
                                  cache=cache, q_offset=offs)
        tok = int(jnp.argmax(logits[0, -1, :]))
        out.append(tok)
    return out


def _arch(name: str):
    try:
        return name, configs.get(name).SMOKE
    except KeyError:
        if name == "transformer":       # certify-CLI alias, same default
            return "qwen2_7b", configs.get("qwen2_7b").SMOKE
        raise


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="continuous-batching serving demo / smoke")
    ap.add_argument("--arch", default="qwen2_7b")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--queue-depth", type=int, default=8)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--arrival-stride", type=int, default=2,
                    help="steps between request arrivals (staggered joins)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data", type=int, default=None,
                    help="mesh data-axis size (default: all devices)")
    ap.add_argument("--model", type=int, default=None,
                    help="mesh model-axis size (default: 1)")
    ap.add_argument("--precision-k", type=int, default=None)
    ap.add_argument("--certificates", default=None, metavar="STORE_DIR")
    ap.add_argument("--certify-mixed", action="store_true")
    ap.add_argument("--certify-formats", action="store_true")
    ap.add_argument("--certify-k-max", type=int, default=None)
    ap.add_argument("--check-ref", action="store_true",
                    help="re-serve every request through the single-device "
                         "eager reference and assert token-for-token "
                         "equality (exits 1 on any mismatch)")
    ap.add_argument("--metrics", default=None, metavar="OUT.JSONL")
    ap.add_argument("--prom", default=None, metavar="OUT.PROM")
    args = ap.parse_args(argv)
    use_compile_cache()
    if ((args.certify_mixed or args.certify_formats
         or args.certify_k_max is not None) and args.certificates is None):
        ap.error("--certify-* require --certificates STORE_DIR")

    arch, arch_cfg = _arch(args.arch)
    sc = serve.ServeConfig(arch=arch, batch=args.lanes,
                           max_seq=args.max_seq,
                           precision_k=args.precision_k,
                           certificates=args.certificates)
    params = T.init_params(jax.random.PRNGKey(0), arch_cfg)
    certset = None
    if args.certificates is not None:
        kw = {}
        if args.certify_mixed or args.certify_formats:
            kw.update(mixed=args.certify_mixed, formats=args.certify_formats,
                      k_max=args.certify_k_max or 53)
        elif args.certify_k_max is not None:
            kw["k_max"] = args.certify_k_max
        sc, certset = serve.apply_certificates(sc, arch_cfg, params, **kw)
        log.info("certificate resolved", k=sc.precision_k,
                 mixed_scopes=(None if sc.precision_layer_k is None
                               else len(sc.precision_layer_k)),
                 format_scopes=(None if sc.precision_layer_format is None
                                else len(sc.precision_layer_format)),
                 error_bars=certset.error_bars())

    mesh = meshlib.make_serving_mesh(data=args.data, model=args.model)
    registry = obs.MetricsRegistry()
    registry.meta.update(arch=arch, lanes=args.lanes,
                         devices=meshlib.device_count(),
                         mesh=dict(zip(mesh.axis_names, mesh.devices.shape)),
                         precision_k=sc.precision_k)
    engine = ContinuousBatchingEngine(
        arch_cfg, sc, params, mesh=mesh, n_lanes=args.lanes,
        max_seq=args.max_seq, page_size=args.page_size,
        queue_depth=args.queue_depth, registry=registry, certset=certset)

    rng = np.random.RandomState(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.randint(max(1, args.prompt_len // 2),
                               args.prompt_len + 1))
        reqs.append(Request(
            rid=i, prompt=rng.randint(0, arch_cfg.vocab, plen).tolist(),
            max_new_tokens=args.max_new,
            arrival_step=i * args.arrival_stride))
    t0 = time.perf_counter()
    responses = engine.run(reqs)
    wall = time.perf_counter() - t0
    log.info("served", requests=len(responses), steps=engine.steps,
             wall_s=round(wall, 2),
             decode_tokens_per_s=round(
                 engine.decode_tokens / engine.decode_s, 1)
             if engine.decode_s else None,
             sample=responses[0]["tokens"][:8] if responses else None)
    if certset is not None:
        for r in responses:
            assert "certificate" in r, r
        log.info("responses certified",
                 bars=responses[0]["certificate"] if responses else None)
    if args.check_ref:
        bad = []
        for req in reqs:
            got = next(r["tokens"] for r in responses if r["id"] == req.rid)
            want = reference_generate(arch_cfg, sc, params, req.prompt,
                                      req.max_new_tokens,
                                      max_seq=args.max_seq,
                                      page_size=args.page_size)
            if got != want:
                bad.append((req.rid, got, want))
        if bad:
            log.error("reference mismatch", n=len(bad), first=bad[0])
            raise SystemExit(1)
        log.info("reference check passed", requests=len(reqs),
                 contract="batched+sharded == single-device eager, "
                          "token-for-token")
    if args.metrics:
        registry.write_jsonl(args.metrics)
    if args.prom:
        registry.write_prometheus(args.prom)
    return engine, responses


if __name__ == "__main__":
    main()
