"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Read with ``jax.profiler.ProfileData``. Device planes are the planes named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run and the ``XLA Modules`` line one per program run. Host
spans are the ``chipbench.*`` events of the host plane, written by
``chipbench/driver.py`` with ``jax.profiler.TraceAnnotation``;
``chipbench.window`` bounds the measured window, on the trace's clock.

:func:`reduce` gives, over the window and averaged over the chips that ran
anything:

- ``busy_s``: the union of the intervals in which an operation ran;
- ``programs``: device seconds and runs per program, by the program's
  stable name (``jit_decode_step(12)`` → ``decode_step``);
- ``kernels``: device seconds per ``<program>/<kernel>`` for the Pallas
  kernels of :data:`KERNELS`. A kernel's event names only its HLO
  instruction (``closed_call.49``), so :func:`kernel_map` finds which
  kernel each instruction runs in the compiled program's text: the
  kernel function's name in the Mosaic body;
- ``ops``: device seconds per ``<program>/<instruction or kernel>``,
  leaving out loops, whose events span the operations inside them;
- ``idle``: idle seconds per host span that held the midpoint of each
  gap between busy intervals (the innermost such span; ``none`` outside
  every span).
"""
from __future__ import annotations

import base64
import collections
import glob
import os
import re
from typing import Any, Dict, List, Optional, Tuple

# kernel -> the name of its Pallas kernel function
KERNELS = {"quant_matmul_format": "_quant_matmul_format_kernel",
           "flash_decode": "_flash_decode_kernel"}
WINDOW = "chipbench.window"
SPAN_PREFIX = "chipbench."


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def program_name(module_event_name: str) -> str:
    name = re.sub(r"\(\d+\)$", "", module_event_name)
    return name[4:] if name.startswith("jit_") else name


def instruction(op_event_name: str) -> str:
    m = re.match(r"\s*(?:ROOT\s+)?%?([^\s=]+)\s*=", op_event_name)
    return m.group(1) if m else op_event_name[:64]


def kernel_map(hlo_text: str) -> Dict[str, str]:
    """{instruction: kernel} of the Pallas calls in a compiled program's
    text: the first of :data:`KERNELS`' functions named in the call's
    serialized body."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = re.search(r'"body":"([^"]+)"', line)
        raw = base64.b64decode(body.group(1)) if body else b""
        hits = [(raw.find(fn.encode()), k) for k, fn in KERNELS.items()
                if fn.encode() in raw]
        if hits:
            out[instruction(line)] = min(hits)[1]
    return out


def _is_loop(op_event_name: str) -> bool:
    return "custom-call(" not in op_event_name and bool(
        re.search(r"\b(while|conditional)\(", op_event_name))


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def host_spans(pd) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name[len(SPAN_PREFIX):], ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def reduce(path: str, kernels: Optional[Dict[str, Dict[str, str]]] = None
           ) -> Dict[str, Any]:
    """The reduction of the trace at ``path``; ``kernels`` is
    ``{program: kernel_map(...)}`` of the programs that ran."""
    from jax.profiler import ProfileData
    kernels_of = kernels or {}
    pd = ProfileData.from_file(path)
    spans = host_spans(pd)
    windows = [(s, e) for n, s, e in spans if n == WINDOW[len(SPAN_PREFIX):]]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} span")
    lo, hi = windows[0]
    segments = _innermost([sp for sp in spans
                           if sp[0] != WINDOW[len(SPAN_PREFIX):]])
    busy = 0.0
    chips = 0
    programs: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    kernels: Dict[str, float] = collections.defaultdict(float)
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       program_name(ev.name))
                      for ev in lines["XLA Modules"].events
                      if lo <= ev.start_ns < hi) if "XLA Modules" in lines \
            else []
        for s, e, name in mods:
            programs[name][0] += (min(e, hi) - s) * 1e-9
            programs[name][1] += 1
        op_iv = []
        mi = 0
        for ev in sorted(lines["XLA Ops"].events, key=lambda v: v.start_ns):
            s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns, lo, hi)
            if e <= s:
                continue
            op_iv.append((s, e))
            while mi < len(mods) and mods[mi][1] <= s:
                mi += 1
            prog = (mods[mi][2] if mi < len(mods) and mods[mi][0] <= s
                    else "none")
            if _is_loop(ev.name):
                continue
            inst = instruction(ev.name)
            k = kernels_of.get(prog, {}).get(inst)
            ops[f"{prog}/{k or inst}"] += (e - s) * 1e-9
            if k is not None:
                kernels[f"{prog}/{k}"] += (e - s) * 1e-9
        if not op_iv:
            continue
        chips += 1
        merged = _union(op_iv)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        for (gs, ge), label in zip(gaps, _labels(
                segments, [(gs + ge) / 2 for gs, ge in gaps])):
            idle[label] += (ge - gs) * 1e-9
    if chips == 0:
        raise ValueError(f"{path}: no operation ran on a TPU in the window")
    per_chip = lambda d: {k: v / chips for k, v in d.items()}
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy / chips,
        "chips": chips,
        "programs": {k: {"s": v[0] / chips, "n": v[1] / chips}
                     for k, v in programs.items()},
        "kernels": per_chip(kernels),
        "ops": per_chip(ops),
        "idle": per_chip(idle),
    }


def _innermost(spans) -> List[Tuple[float, float, str]]:
    """Host spans of one thread nest; cut the timeline into segments, each
    labelled with the innermost span that holds it."""
    bounds = sorted([(s, 1, -(e - s), n) for n, s, e in spans]
                    + [(e, 0, 0.0, n) for n, s, e in spans])
    out, stack, t = [], [], None
    for x, opening, _, name in bounds:
        if stack and t is not None and x > t:
            out.append((t, x, stack[-1]))
        if opening:
            stack.append(name)
        elif name in stack:
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        t = x
    return out


def _labels(segments, times: List[float]) -> List[str]:
    """Label of each of ``times`` (ascending): the segment that holds it."""
    out, i = [], 0
    for t in times:
        while i < len(segments) and segments[i][1] < t:
            i += 1
        hit = i < len(segments) and segments[i][0] <= t
        out.append(segments[i][2] if hit else "none")
    return out


def top(d: Dict[str, float], n: int = 10) -> List[List[Any]]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
