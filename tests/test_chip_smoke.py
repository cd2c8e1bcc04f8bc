"""chip_smoke.py and the bring-up contract around it, without a chip.

- The script refuses a host with no TPU (exit non-zero, no result line),
  and a directory that holds nothing of the repo but the script.
- Every phase runs at ``TINY`` size with the Pallas kernel in the
  interpreter, so the script's control flow is under tier-1; the chip run
  is the same code at ``REAL`` size.
- The compile cache lands in one place; the device backends never fall
  back; ``p1 net`` refuses to put N processes on one chip; a node whose
  mining loop died exits non-zero.
"""

from __future__ import annotations

import asyncio
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run(args, cwd=REPO, env_extra=None, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, *args],
        cwd=cwd,
        env=env,
        capture_output=True,
        text=True,
        timeout=300,
    )


class TestRefusal:
    @pytest.mark.parametrize("chips", ["1", "4"])
    def test_no_tpu_exits_nonzero_and_names_the_platform(self, chips):
        proc = _run(
            ["chip_smoke.py", "--chips", chips], env_extra={"JAX_PLATFORMS": "cpu"}
        )
        assert proc.returncode != 0
        assert "'cpu'" in proc.stderr
        assert '"ok"' not in proc.stdout

    def test_script_alone_exits_nonzero(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        proc = _run(
            ["chip_smoke.py"],
            cwd=tmp_path,
            env_extra={"JAX_PLATFORMS": "cpu"},
            drop=("PYTHONPATH",),
        )
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout


@pytest.fixture(scope="module")
def meter():
    return chip_smoke.CompileMeter()


@pytest.mark.parametrize(
    "name", [name for name, _ in chip_smoke.ONE_CHIP + chip_smoke.FOUR_CHIPS]
)
def test_phase_at_tiny_size(name, meter, capsys):
    phases = dict(chip_smoke.ONE_CHIP + chip_smoke.FOUR_CHIPS)
    ok = chip_smoke.run_phases([(name, phases[name])], chip_smoke.TINY, 0, meter)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert ok, line
    assert line.startswith(f"{name}: PASS "), line


class TestCompileCache:
    CODE = (
        "import sys; sys.path.insert(0, {repo!r});"
        "from p1_tpu.hashx import {call};"
        "{call}({args});"
        "import jax; print(jax.config.jax_compilation_cache_dir)"
    )

    @pytest.mark.parametrize(
        "call,args",
        [("configure_compile_cache", ""), ("get_backend", "'jax'")],
    )
    def test_unset_goes_to_the_checkout(self, call, args, tmp_path):
        code = self.CODE.format(repo=REPO, call=call, args=args)
        proc = _run(["-c", code], cwd=tmp_path, drop=("JAX_COMPILATION_CACHE_DIR",))
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == os.path.join(REPO, ".jax_cache")

    def test_set_goes_only_there(self, tmp_path):
        cache = tmp_path / "cache"
        code = self.CODE.format(repo=REPO, call="configure_compile_cache", args="")
        code += (
            ";jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)"
            ";jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(8)).block_until_ready()"
        )
        proc = _run(
            ["-c", code],
            cwd=tmp_path,
            env_extra={"JAX_COMPILATION_CACHE_DIR": str(cache)},
        )
        assert proc.returncode == 0, proc.stderr
        assert proc.stdout.strip() == str(cache)
        assert any(cache.iterdir()), "the compile was not cached there"


@pytest.mark.parametrize("backend", ["tpu", "sharded", "jax"])
def test_net_refuses_device_backend_on_many_nodes(backend, monkeypatch, capsys):
    from p1_tpu import cli

    def no_spawn(*a, **k):
        raise AssertionError("p1 net spawned a child")

    monkeypatch.setattr(subprocess, "Popen", no_spawn)
    rc = cli.main(["net", "--backend", backend, "--nodes", "2", "--duration", "1"])
    assert rc == 2
    assert "ROADMAP R2" in capsys.readouterr().err


def test_serve_refuses_device_sigs_on_many_workers(monkeypatch, capsys, tmp_path):
    from p1_tpu import cli

    monkeypatch.setenv("P1_SIG_BACKEND", "device")
    monkeypatch.setattr(cli.asyncio, "run", lambda *a, **k: pytest.fail("served"))
    from p1_tpu.core import keys

    keys.set_sig_backend(None)  # re-resolve from the environment
    try:
        rc = cli.main(
            ["serve", "--store", str(tmp_path / "s"), "--workers", "2",
             "--port", "19999"]
        )
    finally:
        monkeypatch.delenv("P1_SIG_BACKEND")
        keys.set_sig_backend(None)
    assert rc == 2
    assert "ROADMAP R2" in capsys.readouterr().err


def test_node_exits_nonzero_when_mining_dies(tmp_path):
    from p1_tpu.cli import build_parser
    from p1_tpu.node.runner import run_node

    class DeadMiner:
        def search_nonce(self, header, abort=None):
            raise RuntimeError("device search failed")

    args = build_parser().parse_args(
        ["node", "--difficulty", "8", "--port", "0", "--duration", "30",
         "--store", str(tmp_path / "chain.dat")]
    )
    rc = asyncio.run(asyncio.wait_for(run_node(args, miner=DeadMiner()), 60))
    assert rc == 5


def test_device_sig_backend_raises_without_devices(monkeypatch):
    from p1_tpu.core import keys
    from p1_tpu.core.keys import Keypair
    from p1_tpu.hashx import ed25519_msm

    def no_mesh(n_devices=None):
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(ed25519_msm, "_default_mesh", no_mesh)
    kp = Keypair.from_seed_text("no-device")
    msgs = [b"m%d" % i for i in range(keys.BATCH_MIN)]
    triples = [(kp.pubkey, kp.sign(m), m) for m in msgs]
    keys.set_sig_backend("device")
    try:
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            keys.verify_batch(triples)
        assert keys.backend() == "device"  # still pinned: nothing degraded
    finally:
        keys.set_sig_backend(None)
