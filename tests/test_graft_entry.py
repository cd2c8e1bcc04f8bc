"""The driver-graded entry points must work — especially ``dryrun_multichip``.

Round 2's graded run failed (MULTICHIP_r02.json rc=1) because the dryrun
created example arrays on the default TPU platform before the CPU mesh, so
a transient TPU-client condition killed a CPU-only check.  The regression
test here runs the dryrun in a subprocess with the TPU platform
*deliberately available* (JAX_PLATFORMS scrubbed from the env, so JAX would
pick the installed TPU runtime) and asserts it still completes on CPU
without ever touching the TPU.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_entry(code: str, *, scrub_platform_env: bool) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    if scrub_platform_env:
        # Let JAX pick the platform — the dryrun itself must force CPU.
        env.pop("JAX_PLATFORMS", None)
    return subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=600,
    )


def test_dryrun_multichip_subprocess_with_tpu_available():
    proc = _run_entry(
        "import __graft_entry__; __graft_entry__.dryrun_multichip(8)",
        scrub_platform_env=True,
    )
    assert proc.returncode == 0, (
        f"dryrun_multichip(8) failed:\nstdout={proc.stdout}\nstderr={proc.stderr}"
    )
    assert "dryrun_multichip ok" in proc.stdout
    assert "platform=cpu" in proc.stdout


def test_entry_compiles_in_process():
    # entry() runs on whatever platform the test session uses (CPU here);
    # the driver separately compile-checks it on the real chip.
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__

        fn, args = __graft_entry__.entry()
        out = fn(*args)
        assert int(out) >= 0
    finally:
        sys.path.remove(REPO)
