"""The check's control, at a size a test run holds: the reference in the
next lower precision put in the program's place must fail the check.

On the tiny RAG cell (cb_tiny.py), one process reads the program's
numbers and the control's on the same sample (chip_bench/calibrate.py):
the fp8 reference's choice of token against the float32 reference. The
program's reading stays within the tiny configuration's limit and the
control's exceeds it. The full-size readings, on the chip, are in
PERF.md.
"""
import pytest

import cb_tiny
from chip_bench import calibrate, spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cb_tiny.make_root(str(tmp_path_factory.mktemp("cbc")))


@pytest.fixture(autouse=True)
def _cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("cell,numbers", [
    ("tiny-rag", ("logit_gap",)),
])
def test_control_fails_where_the_program_passes(root, cell, numbers):
    c = spec.resolve(cell, root)
    out = calibrate.readings(c, 31337, 2.0, control=True, require_tpu=False)
    limits = c.config["correct"]
    for n in numbers:
        assert out["program"][n] <= limits[n], (n, out)
    assert any(out["control"][n] > limits[n] for n in numbers), out
