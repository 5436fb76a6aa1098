"""CPU rehearsal of every traffic mix through the harness at a tiny size,
the refusal of a CPU, and discovery of new files by name.

    PYTHONPATH=src:. JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

ROOT = Path(harness.__file__).resolve().parent.parent
ONE_CHIP = ["transe-fb15k.train", "transe-fb15k.eval", "distmult-fb15k.serve"]


@pytest.mark.parametrize("name", ONE_CHIP)
def test_cell_runs_and_is_correct(name):
    out = tiny.run(name)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) >= {"setup_s"}
    # a cell named in BENCHMARK.json reports one metric beside setup_s
    assert len(out["metrics"]) == (1 if name in tiny.PENDING else 2)
    assert list(out)[-1] == "compared"


def test_eval_through_the_kernel_in_interpret_mode():
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        out = tiny.run("transe-fb15k.eval", seconds=0.2,
                       mix={"fused": True})
    assert out["correct"], out


def test_four_chip_cell_on_virtual_devices():
    code = ("from bench.tests import tiny; import json; "
            "print(json.dumps(tiny.run('transe-fb15k.train-4chip')))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=f"{ROOT / 'src'}:{ROOT}")
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["device"]["count"] == 4, out


def _run_py(cwd: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "transe-fb15k.train",
         "--seed", str(2**33 + 1), "--seconds", "1", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _json_lines(text: str) -> list:
    return [ln for ln in text.splitlines() if ln.lstrip().startswith("{")]


def test_refuses_a_cpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)
    assert "no TPU" in proc.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a traffic mix, limits and a per-layer metric added
    as files of their own run without an edit to any file there is."""
    bench = tmp_path / "bench"
    shutil.copytree(ROOT / "bench", bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    config = harness.load_json(bench / "configs" / "transe-fb15k.json")
    config.update(name="transe-small", dim=8)
    config["graph"].update(tiny.GRAPH)
    (bench / "configs" / "transe-small.json").write_text(json.dumps(config))
    mix = harness.load_json(bench / "traffic" / "train.json")
    mix.update(n_workers=2, batch_size=16)
    (bench / "traffic" / "train-small.json").write_text(json.dumps(mix))
    (bench / "limits" / "transe-small.train-small.json").write_text(
        json.dumps(tiny.LIMITS["fit"]))
    (bench / "metrics" / "blocks_traced.py").write_text(
        "def read(ctx):\n    return ctx['cell'].work.get('blocks')\n")
    import jax

    spec = tiny.spec()
    cell = {"name": "transe-small.train-small", "config": "transe-small",
            "traffic": "train-small", "chips": 1}
    spec["per_layer"].append({"name": "blocks_traced", "unit": "blocks"})
    out = harness.run_cell(
        spec, cell, harness.load_json(harness.find("configs", "transe-small",
                                                   bench)),
        harness.load_json(harness.find("traffic", "train-small", bench)),
        seed=5, seconds=0.3, trace=False, started_s=0.0,
        devices=jax.devices()[:1], clock=harness.CompileClock(), bench=bench)
    assert out["correct"], out

    run = harness.Cell(cell["name"], config, mix, 1, 5, jax.devices()[:1])
    run.work = {"blocks": 3}
    got = harness.read_per_layer(run, spec, summary=None, peaks={},
                                 bench=bench)
    assert got == {"blocks_traced": {"value": 3, "unit": "blocks"}}
