"""The program's own spans and scopes in a profiler trace, which
:func:`chipbench.trace.reduce` leaves out.

The engine (``repro.launch.batching``) opens ``engine.*`` spans, which
``repro.obs`` records as profiler host events with the spans' attributes
as stats, so they share the device trace's clock. The model names its
parts (``Backend.scope`` enters ``jax.named_scope``), so a compiled
program carries ``embed``, ``layer*``, ``attn``, ``mlp`` and ``head`` in
each instruction's ``op_name``. Over the ``chipbench.window`` of a trace,
averaged over the chips that ran anything, :func:`reduce` gives:

- ``runs``: runs per program, by its stable name;
- ``scopes``: device seconds per ``<program>/<scope>``, the scope that
  :func:`scope_map` finds for each instruction in the program's compiled
  text (loops left out, as in ``trace.reduce``'s ``ops``);
- ``spans``: per ``engine.*`` name, of the spans that start in the window:
  ``s`` (seconds, clipped to the window's end), ``count``, and ``sums`` of
  their numeric stats;
- ``idle``: idle seconds per innermost host span of either family
  (``chipbench.*`` without its prefix, ``engine.*`` whole): each gap
  between busy intervals is split over the spans it overlaps, in
  proportion to the overlap (``none`` outside every span), so the labels
  sum to the window less the busy time.

:func:`readings` turns a reduction into per-step numbers. ``run.py`` does
not call this module yet; ``tests/chipbench`` checks it on a chip trace of
the engine with its spans (``data/spans``, made by ``fixture_spans.py``).
"""
from __future__ import annotations

import collections
import re
from typing import Any, Dict, Optional

from chipbench import trace, traffic

ENGINE_PREFIX = "engine."
# the model's named scopes (repro Backend.scope); the innermost one wins
MODEL_SCOPES = ("attn", "mlp", "head", "embed")
WINDOW = trace.WINDOW[len(trace.SPAN_PREFIX):]


def op_scope(op_name: str) -> str:
    """The innermost model scope in an ``op_name`` path; failing that
    ``loop`` for a path through a ``while`` (the layer scan itself, its
    stacked-weight slices, cache copies and other overhead), else
    ``other``."""
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in MODEL_SCOPES:
            return part
    return "loop" if "while" in parts else "other"


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction: scope} of a compiled program's text: :func:`op_scope`
    of each instruction's ``op_name``. One that XLA made without an
    ``op_name`` takes the scope of the loop whose body or condition holds
    it, else of its first operand (so a copy of the layer scan's result is
    the scan's). A fusion carries its root op's ``op_name``."""
    op_names: Dict[str, Optional[str]] = {}
    first: Dict[str, Optional[str]] = {}
    comp_of: Dict[str, Optional[str]] = {}
    loop_of: Dict[str, str] = {}         # body or condition -> its while
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace():
            head = re.search(r"%([\w.\-]+)", line)
            comp = head.group(1) if head and line.rstrip().endswith("{") \
                else None
            continue
        if " = " not in line:
            continue
        inst, rhs = trace.instruction(line), line.split(" = ", 1)[1]
        m = re.search(r'op_name="([^"]*)"', rhs)
        op_names[inst] = m.group(1) if m else None
        operand = re.search(r"(?<![=\w])%([\w.\-]+)", rhs)
        first[inst] = operand.group(1) if operand else None
        comp_of[inst] = comp
        for called in re.findall(r"(?:body|condition)=%([\w.\-]+)", rhs):
            loop_of[called] = inst
    out: Dict[str, str] = {}

    def scope(inst, seen) -> str:
        if inst in out:
            return out[inst]
        if inst in seen or inst not in op_names:
            return "other"
        seen.add(inst)
        if op_names[inst] is not None:
            got = op_scope(op_names[inst])
        elif comp_of[inst] in loop_of:
            got = scope(loop_of[comp_of[inst]], seen)
        else:
            got = scope(first[inst], seen)
        out[inst] = got
        return got

    for inst in op_names:
        scope(inst, set())
    return out


def scope_maps(drv) -> Dict[str, Dict[str, str]]:
    """``{program: scope_map(...)}`` of a :class:`chipbench.driver.Driver`'s
    decode and prefill programs, compiled as ``Driver.kernel_maps``
    compiles them (so the compile cache serves them). Instructions whose
    scope differs between prefill shapes are left out."""
    import jax
    import jax.numpy as jnp
    eng = drv.eng
    sds = lambda t: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    text = lambda f, *a: scope_map(f.lower(*a).compile().as_text())
    p = sds(eng.params)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    out = {"decode_step": text(drv.decode_jit, p, sds(eng.cache),
                               i32(eng.n_lanes), i32(eng.n_lanes))}
    seen: Dict[str, Optional[str]] = {}
    for pages in traffic.prefill_pages(drv.cell.traffic, drv.page):
        n = min(pages * drv.page, drv.max_seq)
        for inst, sc in text(drv.prefill_jit, p, i32(1, n), i32()).items():
            seen[inst] = sc if seen.get(inst, sc) == sc else None
    out["prefill_step"] = {i: s for i, s in seen.items() if s}
    return out


def host_events(pd):
    """(label, start, end, stats) of the host plane's ``chipbench.*``
    (label without the prefix) and ``engine.*`` events."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(trace.SPAN_PREFIX):
                    label = ev.name[len(trace.SPAN_PREFIX):]
                elif ev.name.startswith(ENGINE_PREFIX):
                    label = ev.name
                else:
                    continue
                yield (label, ev.start_ns, ev.start_ns + ev.duration_ns,
                       list(ev.stats))


def engine_spans(events, lo, hi) -> Dict[str, Dict[str, Any]]:
    """Per ``engine.*`` name, of the events that start in ``[lo, hi)``:
    ``s`` (seconds clipped to ``hi``), ``count``, and ``sums`` of their
    numeric stats."""
    out: Dict[str, Dict[str, Any]] = {}
    for name, s, e, stats in events:
        if not (name.startswith(ENGINE_PREFIX) and lo <= s < hi):
            continue
        d = out.setdefault(name, {"s": 0.0, "count": 0, "sums": {}})
        d["s"] += (min(e, hi) - s) * 1e-9
        d["count"] += 1
        for k, v in stats:
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                d["sums"][k] = d["sums"].get(k, 0) + v
    return out


def split(segments, gaps) -> Dict[str, float]:
    """Each of ``gaps`` (ascending, disjoint) split over the labelled
    ``segments`` (``trace._innermost``) it overlaps, in proportion to the
    overlap; the rest of a gap is ``none``. The parts sum to the gaps."""
    out: Dict[str, float] = collections.defaultdict(float)
    i = 0
    for gs, ge in gaps:
        while i < len(segments) and segments[i][1] <= gs:
            i += 1
        rest = ge - gs
        j = i
        while j < len(segments) and segments[j][0] < ge:
            s, e, name = segments[j]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                out[name] += part
                rest -= part
            j += 1
        if rest > 0:
            out["none"] += rest
    return dict(out)


def reduce(path: str, scopes: Optional[Dict[str, Dict[str, str]]] = None
           ) -> Dict[str, Any]:
    """The reduction of the trace at ``path``; ``scopes`` is
    ``{program: scope_map(...)}`` of the programs that ran."""
    from jax.profiler import ProfileData
    scopes_of = scopes or {}
    pd = ProfileData.from_file(path)
    events = list(host_events(pd))
    windows = [(s, e) for n, s, e, _ in events if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {trace.WINDOW} span")
    lo, hi = windows[0]
    segments = trace._innermost([(n, s, e) for n, s, e, _ in events
                                 if n != WINDOW])
    chips = 0
    runs: Dict[str, float] = collections.defaultdict(float)
    by_scope: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                       trace.program_name(ev.name))
                      for ev in lines["XLA Modules"].events
                      if lo <= ev.start_ns < hi) if "XLA Modules" in lines \
            else []
        for _, _, name in mods:
            runs[name] += 1
        op_iv = []
        mi = 0
        for ev in sorted(lines["XLA Ops"].events, key=lambda v: v.start_ns):
            s, e = trace._clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                               lo, hi)
            if e <= s:
                continue
            op_iv.append((s, e))
            while mi < len(mods) and mods[mi][1] <= s:
                mi += 1
            prog = (mods[mi][2] if mi < len(mods) and mods[mi][0] <= s
                    else "none")
            if prog in scopes_of and not trace._is_loop(ev.name):
                sc = scopes_of[prog].get(trace.instruction(ev.name), "other")
                by_scope[f"{prog}/{sc}"] += (e - s) * 1e-9
        if not op_iv:
            continue
        chips += 1
        merged = trace._union(op_iv)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps = [(gs, ge) for gs, ge in zip(edges[::2], edges[1::2])
                if ge > gs]
        for label, ns in split(segments, gaps).items():
            idle[label] += ns * 1e-9
    if chips == 0:
        raise ValueError(f"{path}: no operation ran on a TPU in the window")
    per_chip = lambda d: {k: v / chips for k, v in d.items()}
    return {"window_s": (hi - lo) * 1e-9, "chips": chips,
            "runs": per_chip(runs), "scopes": per_chip(by_scope),
            "spans": engine_spans(events, lo, hi), "idle": per_chip(idle)}


# engine.step time that is not the engine's own host work
NOT_HOST = ("engine.admit", "engine.decode", "engine.decode_wait")


def readings(red: Dict[str, Any]) -> Dict[str, Optional[float]]:
    """Per-step numbers of a reduction, in ms, each None where the program
    has no such span or names no model scope:

    - ``engine_admit_ms``: ``engine.admit`` seconds over the sum of its
      ``n`` (requests admitted);
    - ``engine_host_ms_step``: ``engine.step`` less ``engine.admit``,
      ``engine.decode`` and ``engine.decode_wait``, per ``engine.decode``;
    - ``engine_idle_ms_step``: device idle inside ``engine.*`` spans, per
      ``engine.decode``;
    - ``decode_attn_ms``, ``decode_mlp_ms``, ``decode_loop_ms``: device
      time of the decode program under ``attn``, ``mlp`` and the layer
      scan's overhead, per run of the program."""
    sp, out = red["spans"], {}
    admit = sp.get("engine.admit")
    n = admit["sums"].get("n") if admit else None
    out["engine_admit_ms"] = 1e3 * admit["s"] / n if n else None
    steps = sp["engine.decode"]["count"] if "engine.decode" in sp else 0
    own = (sp["engine.step"]["s"] - sum(sp[k]["s"] for k in NOT_HOST
                                        if k in sp)
           if "engine.step" in sp else None)
    out["engine_host_ms_step"] = (1e3 * own / steps
                                  if steps and own is not None else None)
    idle = sum(v for k, v in red["idle"].items()
               if k.startswith(ENGINE_PREFIX))
    out["engine_idle_ms_step"] = 1e3 * idle / steps if steps else None
    runs = red["runs"].get("decode_step")
    named = any(f"decode_step/{m}" in red["scopes"] for m in MODEL_SCOPES)
    for scope in ("attn", "mlp", "loop"):
        t = red["scopes"].get(f"decode_step/{scope}")
        ok = runs and named and t is not None
        out[f"decode_{scope}_ms"] = 1e3 * t / runs if ok else None
    return out
