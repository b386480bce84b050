"""The benchmark's entry refuses what it cannot measure: a machine
without the cell's chips, and a directory without the program."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chipbench.run as R
from chipbench.spec import Cell

REPO = Path(__file__).resolve().parents[2]


def test_refuses_the_cpu_before_any_model_work(monkeypatch):
    monkeypatch.setattr(R, "Setup", None)     # any model work would fail
    with pytest.raises(SystemExit) as exc:
        R.main(["--workload", "phi3-mixed-poisson", "--seed", "1",
                "--seconds", "1", "--trace", "0"])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)


def test_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "phi3-mixed-poisson", "--seed", "1", "--seconds", "1", "--trace",
         "0"], cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "no program" in out.stderr


def test_every_cell_finds_its_pieces():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = Cell(w["name"])
        assert cell.driver().drive and cell.reference().logit_gaps
        assert cell.limits["max_logit_gap"] > 0
        names = [m["name"] for m in cell.end_to_end() + cell.per_layer()]
        assert "setup_s" in names and len(cell.per_layer()) >= 1
        for name in names:
            assert cell.reader_of(name).read
