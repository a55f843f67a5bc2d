"""The DeepSeek-V2-Lite cell in miniature: a whole run on the CPU against
the plain reference ``references/deepseek_v2.py``, and the three readers of
its work model (``work_mla_moe.py``) on hand-built records."""
import functools
import json
import math
import time
import types

import jax
import jax.numpy as jnp
import pytest

import tiny
from chipbench import driver, peaks, run, spec, work, work_mla_moe

CELL = "dsv2l-fmt-batch"
READERS = ("fmt_moe_gemm_roofline", "mla_moe_mfu", "mla_moe_prefill_mfu")
FULL = tiny.REPO / "chipbench" / "configs" / "deepseek_v2_lite-5L-fmt.json"


@pytest.fixture
def root(tmp_path, monkeypatch):
    """The small root of ``tiny.make_root``, with the DeepSeek cell's name
    on a small DeepSeek-V2 configuration and mix; the metrics' lists of
    cells are ``BENCHMARK.json``'s, which name the cell."""
    monkeypatch.setattr(run, "use_cache", lambda root: None)
    r, bench_dir = tiny.make_root(tmp_path)
    bench = json.loads((r / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-dsv2", "source": "x",
                             "file": "configs/tiny-dsv2.json",
                             "reduced": [], "why": "small"})
    bench["workloads"].append({"name": CELL, "config": "tiny-dsv2",
                               "traffic": "tiny-moe-batch", "chips": 1,
                               "why": "small"})
    (r / "BENCHMARK.json").write_text(json.dumps(bench))
    return r, bench_dir


def test_small_deepseek_cell_runs_correct(root):
    """At these widths (top 3 of 8 experts) the k-12 router flips
    near-tied experts, and a flip moves that row's logits by up to 5% of
    the largest, so the small configuration's limits (0.1, 0.15) are
    wider than the small Qwen2's; a float32 program reads 1.6e-6."""
    r, bench_dir = root
    result, rec = run.run_cell(r, CELL, 2 ** 31 + 11, 0.5, False,
                               bench_dir=bench_dir, require_tpu=False,
                               t0=time.perf_counter())
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"out_tok_s", "setup_s"}
    assert rec.arch["first_k_dense"] == 1 and rec.arch["q_rank"] is None
    for c in result["compare"].values():
        assert c["value"] <= c["limit"]


def test_an_altered_token_is_not_correct_on_the_moe_cell(root,
                                                         monkeypatch):
    """The limits that routing flips leave room for still catch a served
    token that is not the program's: it reads about 1.0."""
    from repro.launch import batching
    build = batching.ContinuousBatchingEngine._build_steps

    def broken(self):
        build(self)
        decode = self._decode

        def altered(params, cache, tokens, offsets):
            nxt, rows, new = decode(params, cache, tokens, offsets)
            hit = offsets % 16 == 5
            return jnp.where(hit, (nxt + 1) % rows.shape[-1], nxt), rows, new
        self._decode = jax.jit(altered)

    monkeypatch.setattr(batching.ContinuousBatchingEngine, "_build_steps",
                        broken)
    r, bench_dir = root
    result, _ = run.run_cell(r, CELL, 2 ** 31 + 11, 0.5, False,
                             bench_dir=bench_dir, require_tpu=False,
                             t0=time.perf_counter())
    assert result["correct"] is False
    assert all(c["value"] > 3 * c["limit"]
               for c in result["compare"].values())


def _full_arch():
    hf = json.loads(FULL.read_text())
    return hf, spec.reference(hf["reference"]).program_arch(hf, "dsv2l")


def _record(arch, fmt_map, lanes=32, kv=32 * 700, steps=10, prompt=300):
    cell = types.SimpleNamespace(traffic={"loop": "backlog"})
    rec = driver.Record(cell=cell, arch=arch, fmt_map=fmt_map, seconds=0.0)
    rec.t_open, rec.t_close = 0.0, 2.0
    rec.steps = [driver.Step(0.1 * i, 0.1 * i + 0.05, lanes, kv, 0.04, 0.0, 0)
                 for i in range(1, steps + 1)]
    rec.admits = [driver.Admit(0.5, 0.52, prompt)]
    rec.device_kind, rec.chips = "TPU v5 lite", 1
    return rec


def test_readers_reach_100_at_the_least_time_and_no_further():
    """A hand-built record whose kernel and window take exactly the least
    time the work model allows reads 100%; any real time is longer."""
    hf, arch = _full_arch()
    peak = peaks.peaks("TPU v5 lite")
    rec = _record(arch, hf["format_map"])
    need = sum(work.roofline_s(work_mla_moe.decode_gemms(
        arch, hf["format_map"], s.lanes), peak) for s in rec.steps)
    flops = (sum(work_mla_moe.decode_flops(arch, s.lanes, s.kv)
                 for s in rec.steps)
             + work_mla_moe.prefill_flops(arch, rec.admits[0].prompt))
    prefill = work_mla_moe.prefill_flops(arch, rec.admits[0].prompt)
    rec.trace = {"window_s": flops / peak["flops"],
                 "kernels": {"decode_step/quant_matmul_format": need},
                 "programs": {"prefill_step": {
                     "s": prefill / peak["flops"], "runs": 1}}}
    read = lambda name: spec.metric_reader(name)(rec)
    for name in READERS:
        assert read(name) == pytest.approx(100.0), name
    rec.trace["window_s"] *= 3
    rec.trace["kernels"]["decode_step/quant_matmul_format"] *= 3
    rec.trace["programs"]["prefill_step"]["s"] *= 3
    for name in READERS:
        v = read(name)
        assert math.isfinite(v) and 0 < v <= 100.0 / 3 + 1e-9


def test_readers_leave_a_dense_record_alone():
    rec = _record({"d_model": 64, "n_heads": 2}, {"": {"k": 12, "emax": 15,
                                                      "emin": -24}})
    rec.trace = {"window_s": 1.0,
                 "kernels": {"decode_step/quant_matmul_format": 0.1},
                 "programs": {"prefill_step": {"s": 0.1, "runs": 1}}}
    for name in READERS:
        assert spec.metric_reader(name)(rec) is None


def test_prefill_reader_reads_nothing_without_a_prefill_in_the_window():
    hf, arch = _full_arch()
    rec = _record(arch, hf["format_map"])
    rec.trace = {"window_s": 1.0, "kernels": {}, "programs": {}}
    assert spec.metric_reader("mla_moe_prefill_mfu")(rec) is None
    rec.trace["programs"]["prefill_step"] = {"s": 0.1, "runs": 1}
    rec.admits = []
    assert spec.metric_reader("mla_moe_prefill_mfu")(rec) is None


def test_format_gemm_bytes_are_the_weights_it_reads():
    """At 32 bits and no rows, the bytes of a decode step's format-GEMM
    products are exactly the weights those products read: every projection
    but the latent up-projection, the dense MLP, router, shared and every
    routed expert; the embedding, head, norms and wkv_b stay out."""
    hf, arch = _full_arch()
    ref = spec.reference(hf["reference"])
    shapes = jax.eval_shape(functools.partial(ref.init_weights, hf),
                            jnp.uint32(0), jnp.uint32(0))
    out = ("embed", "head", "final_norm", "ln1", "ln2", "kv_norm", "wkv_b")
    want = sum(math.prod(a.shape) * 4
               for path, a in jax.tree_util.tree_leaves_with_path(shapes)
               if not any(getattr(k, "key", None) in out for k in path))
    got = sum(b for _, b in work_mla_moe.decode_gemms(arch, None, 0))
    assert got == want
    # the routed experts are 84% of what a decode step streams (the head
    # is read whole every step, the embedding one row a token)
    routed = 3 * 64 * 2048 * 1408 * 4 * 4
    assert routed / (got + 102400 * 2048 * 4) > 0.84
