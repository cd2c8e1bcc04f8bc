"""Every cell end to end on the CPU at rehearsal size, and the refusals.

The rehearsal drives the whole run and prints no metric: for driver
``mine`` the node, host loop, Pallas kernel in the interpreter, the pod's
mesh on four virtual devices, push client, store and reference; for
driver ``replay`` the chain's build, the device scan on the CPU, the
seeded corruptions and the reference.  Without ``--rehearse`` the command
refuses a host with no TPU, and a checkout that holds only the benchmark.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
DRIVER = {w["name"]: json.loads((ROOT / "perfbench/traffic" / f"{w['traffic']}.json").read_text())
          ["driver"] for w in BENCH["workloads"]}


def _run(args, cwd=ROOT, platform="cpu"):
    env = dict(os.environ, JAX_PLATFORMS=platform)
    return subprocess.run([sys.executable, "-m", "perfbench.run", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=600)


def _last(stdout: str) -> dict:
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct_and_prints_no_metric(cell):
    for trace in ("0", "1"):
        proc = _run(["--workload", cell, "--seed", "3000000019", "--seconds", "2",
                     "--trace", trace, "--rehearse"])
        assert proc.returncode == 0, proc.stderr[-3000:]
        out = _last(proc.stdout)
        assert out["correct"] is True and out["attempted"] > 0 and out["failed"] == 0
        assert out["metrics"] == {} and out["rehearsal"] is True
        assert out["device"]["platform"] == "cpu"
        assert list(out)[-1] == "checks"
        assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
        assert out["facts"]["compiles_in_window"] == 0
        if DRIVER[cell] == "mine":
            assert "check not_first 0 limit 0" in proc.stderr
            # The harness's count of useful hashes, from the sealed
            # headers, agrees with the program's own.
            assert out["facts"]["hashes_done_gap"] == 0
        else:
            assert "check missed_corruption 0 limit 0" in proc.stderr
            # Every seeded flip read back at the reference's index.
            assert all(got == want for *_, got, want in out["facts"]["corruptions"])
            assert len(out["facts"]["corruptions"]) == 2


def test_every_seed_seals_the_same_chain():
    runs = [_last(_run(["--workload", "solo-d20", "--seed", seed, "--seconds", "1",
                        "--rehearse"]).stdout) for seed in ("77", "4000000077")]
    assert runs[0]["facts"]["first_nonces"] == runs[1]["facts"]["first_nonces"]


@pytest.mark.parametrize("cell", CELLS)
def test_refuses_without_a_tpu(cell):
    proc = _run(["--workload", cell, "--seed", "1", "--seconds", "1"])
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and proc.stdout.strip() == ""


def test_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "-m", "perfbench.run", "--workload", "solo-d28",
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_unknown_cell_is_refused():
    proc = _run(["--workload", "no-such-cell", "--seed", "1", "--seconds", "1"])
    assert proc.returncode != 0 and proc.stdout.strip() == ""
