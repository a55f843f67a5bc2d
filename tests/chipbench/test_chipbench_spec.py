"""The harness is driven by data: BENCHMARK.json and files found by name."""
import json
import re
import shutil

import pytest

from chipbench import spec

REPO = spec.HERE.parent
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        assert cell.config["reference"]
        spec.reference(cell.config["reference"])
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(REPO, w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names


def test_names_units_and_bounds_keep_to_their_forms():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        conf = json.loads((REPO / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    """A later change adds files and entries and edits no file."""
    bench_dir = tmp_path / "chipbench"
    shutil.copytree(spec.HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    conf = json.loads((REPO / BENCH["configs"][0]["file"]).read_text())
    conf["engine"] = dict(conf["engine"], lanes=8)
    (bench_dir / "configs" / "new-conf.json").write_text(json.dumps(conf))
    mix = json.loads((bench_dir / "mixes" / "batch.json").read_text())
    mix["backlog"] = 8
    (bench_dir / "mixes" / "new-mix.json").write_text(json.dumps(mix))
    (bench_dir / "metrics" / "new_metric.py").write_text(
        "def read(rec):\n    return 42.0\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "new-conf", "source": "x",
                             "file": "chipbench/configs/new-conf.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-cell", "config": "new-conf",
                               "traffic": "new-mix", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "x", "moves": "out_tok_s",
                               "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(tmp_path, "new-cell", bench_dir)
    assert cell.config["engine"]["lanes"] == 8
    assert cell.traffic["backlog"] == 8
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert spec.metric_reader("new_metric", bench_dir)(None) == 42.0
    assert all(p.read_bytes() == b for p, b in before.items())


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell(REPO, "no-such-cell")
