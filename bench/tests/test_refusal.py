"""The benchmark refuses to run without a TPU, beside no program, and with
a serving mesh that does not take the cell's chips."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.lib import cell as C

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "granite-8b-bf16.chat", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def _run(root: Path):
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          capture_output=True, text=True, timeout=300,
                          env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"})


def _bench_copy(root: Path) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))


def test_no_tpu_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_alone_no_result(tmp_path):
    _bench_copy(tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_mesh_not_chips_refused_before_setup(tmp_path):
    """A one-chip cell whose configuration names a 2 x 2 mesh: refused
    before JAX is asked for a chip."""
    _bench_copy(tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    path = tmp_path / "bench" / "configs" / "granite-8b-bf16.json"
    config = json.loads(path.read_text())
    config["engine"]["mesh"] = {"data": 2, "model": 2}
    path.write_text(json.dumps(config))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "engine.mesh" in r.stderr and "no TPU" not in r.stderr


@pytest.mark.parametrize("mesh,chips,shape", [
    (None, 1, None),
    ({"data": 2, "model": 2}, 4, (2, 2)),
    ({"data": 4, "model": 1}, 4, (4, 1)),
    ({"data": 2, "model": 2}, 1, ValueError),
    ({"data": 2, "model": 1}, 4, ValueError),
    ({"data": 4}, 4, ValueError),
    ({"data": 2, "model": 2, "pod": 1}, 4, ValueError),
    ({"data": 0, "model": 4}, 0, ValueError),
])
def test_mesh_shape(mesh, chips, shape):
    cell = C.load(ROOT, "granite-8b-bf16.chat")
    cell.chips = chips
    if mesh is not None:
        cell.config["engine"]["mesh"] = mesh
    if shape is ValueError:
        with pytest.raises(ValueError, match="engine.mesh"):
            cell.mesh_shape()
    else:
        assert cell.mesh_shape() == shape
