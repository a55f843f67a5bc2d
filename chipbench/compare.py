"""The comparison that decides ``correct``.

The window's engine runs with ``keep_logits`` and its prefill and decode
programs are composed with :func:`probe`, so each emitted row leaves the
device as 128 numbers: its best logit (the served token's, under greedy
decoding), and the logits at :func:`probe_ids`, a fixed spread of
vocabulary ids. After the
window, a sample of finished requests drawn from the seed (the longest
among them) is run through the plain reference, teacher-forced on the
served tokens, and two numbers are taken over every served row of the
sample, each as a share of the largest |reference logit| there:

- ``logit_gap``: the widest gap by which a served token's reference logit
  lies below the reference's best (a greedy token may lose to a near-tie
  by rounding, never by more);
- ``logit_dev``: the widest distance between a logit the program emitted
  and the reference's logit of the same token (for the best logit: of the
  token served).

A configuration's ``limits`` name the numbers it is held to.

The control takes the program's place in the same readings: the
reference in the precision below the configuration's, or the program's
own format path with a narrower map. Its token is the one it puts first.
"""
from __future__ import annotations

import functools
import json
from typing import Any, Dict, List, Sequence

import numpy as np

from chipbench import traffic

N_PROBE = 127
# (the vocabulary sizes of the configurations here are >= 127 * 1193)
PROBE_STRIDE = 1193
SAMPLE_TOKENS = 1024      # served tokens the sample reaches, at least...
SAMPLE_REQUESTS = 8       # ...unless it holds this many requests first


def probe_ids(vocab: int) -> np.ndarray:
    return (np.arange(N_PROBE) * PROBE_STRIDE) % vocab


def probe(rows, ids):
    """``[B, 1 + N_PROBE]``: each row's best logit, then its logits at
    ``ids``, of logit rows ``[B, V]``. Under greedy decoding the best logit
    is the served token's; compared with the reference's logit of the
    token that was served, it also catches a token altered after its
    row was computed."""
    import jax.numpy as jnp
    return jnp.concatenate([jnp.max(rows, axis=1, keepdims=True),
                            rows[:, ids]], axis=1)


def sample(finished: Sequence[Dict[str, Any]], seed: int
           ) -> List[Dict[str, Any]]:
    """The longest finished request, then others in an order drawn from
    the seed, until the sample serves SAMPLE_TOKENS or holds
    SAMPLE_REQUESTS requests."""
    if not finished:
        return []
    longest = max(range(len(finished)),
                  key=lambda i: (len(finished[i]["prompt"])
                                 + len(finished[i]["tokens"]), -i))
    rest = [i for i in traffic.rng_for(seed, 7).permutation(len(finished))
            if i != longest]
    out, n = [], 0
    for i in [longest] + rest:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_REQUESTS:
            break
        out.append(finished[i])
        n += len(finished[i]["tokens"])
    return out


@functools.lru_cache(maxsize=None)
def _ref_rows_fn(ref, hf_json: str, precision: str):
    import jax
    import jax.numpy as jnp
    hf = json.loads(hf_json)
    ids = probe_ids(hf["vocab_size"])

    def fn(w, seq, rows, tok):
        h = ref.hidden(w, hf, seq, precision)[rows]
        lg = ref.logits(w, hf, h, precision)
        at = jnp.take_along_axis(lg, tok[:, None], axis=1)[:, 0]
        return (jnp.max(lg, -1), at, lg[:, ids], jnp.max(jnp.abs(lg), -1),
                jnp.argmax(lg, -1).astype(jnp.int32))

    return jax.jit(fn)


def padded(req, seq_len: int, n_rows: int):
    """A request teacher-forced for the reference: prompt and served
    tokens in ``seq_len`` positions, and the rows and tokens of its
    emitted logits padded to ``n_rows``."""
    prompt, toks = list(req["prompt"]), list(req["tokens"])
    seq = np.zeros((seq_len,), np.int32)
    full = prompt + toks[:-1]
    seq[:len(full)] = full
    n = len(toks)
    rows = np.zeros((n_rows,), np.int32)
    rows[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
    tok = np.zeros((n_rows,), np.int32)
    tok[:n] = toks
    return seq, rows, tok, n


def reference_rows(ref, hf, w, reqs, seq_len: int, n_rows: int,
                   precision: str = "highest", tokens=None):
    """Per request ``(best, at, probes, amax, argmax)`` host arrays of the
    reference at each served row; ``at`` is taken at ``tokens[i]`` where
    given, else at the served tokens."""
    import jax
    fn = _ref_rows_fn(ref, json.dumps(hf, sort_keys=True), precision)
    out = []
    for i, r in enumerate(reqs):
        seq, rows, tok, n = padded(r, seq_len, n_rows)
        if tokens is not None:
            tok[:n] = tokens[i]
        got = jax.device_get(fn(w, seq, rows, tok))
        out.append(tuple(np.asarray(a)[:n] for a in got))
    return out


def numbers(ref_rows, emitted) -> Dict[str, float]:
    """``logit_gap`` and ``logit_dev`` of emitted rows against the
    reference. ``ref_rows[i]`` is :func:`reference_rows`' tuple taken at
    the emitted tokens; ``emitted[i]`` is ``[n, 1 + N_PROBE]``: the
    best logit of each row, then the logits at the probe ids."""
    gap = dev = scale = 0.0
    for (best, at, probes, amax, _), e in zip(ref_rows, emitted):
        e = np.asarray(e, np.float64)
        gap = max(gap, float(np.max(best - at)))
        dev = max(dev, float(np.max(np.abs(e[:, 0] - at))),
                  float(np.max(np.abs(e[:, 1:] - probes))))
        scale = max(scale, float(np.max(amax)))
    scale = scale or 1.0           # no rows: nothing compared
    return {"logit_gap": gap / scale, "logit_dev": dev / scale}


def program_numbers(ref, hf, w, reqs, seq_len, n_rows) -> Dict[str, float]:
    rows = reference_rows(ref, hf, w, reqs, seq_len, n_rows)
    out = numbers(rows, [r["logits"] for r in reqs])
    out["rows"] = sum(len(r["tokens"]) for r in reqs)
    return out


def reference_control(ref, hf, w, reqs, seq_len, n_rows, precision):
    """The reference in ``precision`` put in the program's place: its
    token is its own first choice."""
    low = reference_rows(ref, hf, w, reqs, seq_len, n_rows, precision)
    picks = [a[4] for a in low]
    rows = reference_rows(ref, hf, w, reqs, seq_len, n_rows,
                          tokens=picks)
    emitted = [np.concatenate([a[0][:, None], a[2]], axis=1) for a in low]
    return numbers(rows, emitted)
