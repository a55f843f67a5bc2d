"""The control comes out not correct under each configuration's limits,
where the program passes: the check of ``correct`` can fail."""
import pytest

import tiny
from chipbench import control, run, spec


@pytest.mark.parametrize("cell", ["f32-batch", "fmt-batch"])
def test_control_fails_where_the_program_passes(tmp_path, monkeypatch,
                                                cell):
    monkeypatch.setattr(run, "use_cache", lambda root: None)
    root, bench_dir = tiny.make_root(tmp_path)
    limits = spec.load_cell(root, cell, bench_dir).config["limits"]
    (got,) = control.readings(root, cell, [2 ** 31 + 5], {2 ** 31 + 5}, 0.5,
                              bench_dir=bench_dir, require_tpu=False)
    assert got["program"]["rows"] > 0
    assert all(got["program"][k] <= v for k, v in limits.items())
    assert any(got["control"][k] > v for k, v in limits.items())
