"""``run.py`` driven on the CPU at the program's ``.reduced()`` widths:
the request path, the metric arithmetic and the output check work; a
fault planted in the served answer makes ``correct`` false; and without
a TPU, or outside a checkout that holds the program, no result is
printed."""

import contextlib
import io
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

import run
import small
from conftest import CHIP, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SEED = 2 ** 33 + 17  # seeds may be wider than 32 bits


def drive(cell, **hook):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(SEED),
                       "--seconds", "1.5", "--trace", "0"],
                      require_chip=False, driver_hook=small.hook(**hook))
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_checks_on_cpu(cell):
    res = drive(cell)
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    m = res["metrics"]
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    assert list(res)[-1] == "check"


def test_altered_answer_is_not_correct(monkeypatch):
    from repro.core.collab import CollabRuntime

    served = CollabRuntime.cloud_step

    def altered(self, packet):  # every served logit moved one token on
        return jnp.roll(served(self, packet), 1, axis=-1)

    monkeypatch.setattr(CollabRuntime, "cloud_step", altered)
    res = drive(CELLS[0])
    assert res["correct"] is False
    assert res["check"]["logit_err"]["value"] \
        > res["check"]["logit_err"]["limit"]


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == run.NO_CHIP
    assert "{" not in p.stdout


def test_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); import run; "
            f"sys.exit(run.main(['--workload', {CELLS[0]!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0'], require_chip=False))")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout
