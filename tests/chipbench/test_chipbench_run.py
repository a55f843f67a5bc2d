"""Whole runs of the small cells on the CPU (the chip check skipped), the
refusals without a chip or a program, and the faults ``correct`` catches."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

import tiny
from chipbench import run

REPO = tiny.REPO


@pytest.fixture
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "use_cache", lambda root: None)
    return tiny.make_root(tmp_path)


def run_small(root, cell, seed=2 ** 31 + 11, seconds=0.5):
    r, bench_dir = root
    result, rec = run.run_cell(r, cell, seed, seconds, False,
                               bench_dir=bench_dir, require_tpu=False,
                               t0=time.perf_counter())
    return result, rec


@pytest.mark.parametrize("cell", ["f32-batch", "fmt-chat"])
def test_small_cell_runs_correct_and_reports_its_metrics(root, cell):
    result, rec = run_small(root, cell)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "compare"
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    want = {"f32-batch": {"out_tok_s", "setup_s"},
            "fmt-chat": {"itl_p95_ms", "ttft_p90_ms", "setup_s"}}[cell]
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    for c in result["compare"].values():
        assert c["value"] <= c["limit"]


def _broken(fault):
    def wrap(decode):
        def f(params, cache, tokens, offsets):
            nxt, rows, new = decode(params, cache, tokens, offsets)
            if fault == "state_unchanged":
                return nxt, rows, cache
            if fault == "half_batch":
                # odd lanes are left out and take their even neighbour's
                # result
                keep = jnp.arange(nxt.shape[0]) // 2 * 2
                nxt, rows = nxt[keep], rows[keep]
            if fault == "token_altered":
                hit = offsets % 16 == 5
                nxt = jnp.where(hit, (nxt + 1) % rows.shape[-1], nxt)
            return nxt, rows, new
        return jax.jit(f)
    return wrap


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
@pytest.mark.parametrize("cell", ["f32-batch", "fmt-chat"])
def test_broken_decode_is_not_correct(root, monkeypatch, cell, fault):
    from repro.launch import batching
    build = batching.ContinuousBatchingEngine._build_steps

    def broken(self):
        build(self)
        self._decode = _broken(fault)(self._decode)

    monkeypatch.setattr(batching.ContinuousBatchingEngine, "_build_steps",
                        broken)
    result, _ = run_small(root, cell)
    assert result["correct"] is False
    assert any(c["value"] > c["limit"] for c in result["compare"].values())


def _changed_engine(change):
    """An engine whose calls no longer have the shape the benchmark wraps:
    a decode with other arguments, or two prefills to one admission (as a
    chunked prefill would make)."""
    from repro.launch import batching
    eng = batching.ContinuousBatchingEngine
    if change == "decode_signature":
        build = eng._build_steps

        def changed(self):
            build(self)
            decode = self._decode
            self._decode = jax.jit(
                lambda params, cache, batch: decode(params, cache, *batch))
        return "_build_steps", changed
    admit = eng._admit

    def two_prefills(self):
        if self.queue and None in self.lanes:
            req = self.queue[0]
            toks = batching.page_padded(req.prompt, self.page_size,
                                        self.max_seq)
            self._prefill(self.params, jnp.asarray(toks),
                          jnp.asarray(len(req.prompt), jnp.int32))
        admit(self)
    return "_admit", two_prefills


@pytest.mark.parametrize("change, says", [
    ("decode_signature", "is not the call"),
    ("prefill_per_admission", "prefill calls in one step")])
def test_engine_calls_of_another_shape_fail_loudly(root, monkeypatch,
                                                   change, says):
    from repro.launch import batching
    name, fn = _changed_engine(change)
    monkeypatch.setattr(batching.ContinuousBatchingEngine, name, fn)
    with pytest.raises(RuntimeError, match=says):
        run_small(root, "f32-batch")


def test_no_tpu_is_refused():
    with pytest.raises(SystemExit, match="no TPU"):
        run.devices(1)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "fmt-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_chip_exits_nonzero_with_no_result(tmp_path):
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(REPO / "src", tmp_path / "src",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_cli_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for path in bench["paths"]:
        shutil.copytree(REPO / path, tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
