"""Test environment: force JAX onto CPU with 8 virtual devices.

Multi-chip hardware is unavailable in CI; the sharded nonce-search path
(shard_map + pmin over a Mesh) is exercised on a virtual 8-device CPU mesh
instead (SURVEY.md §7 step 8).

``XLA_FLAGS`` must be in the environment before the first backend
initialization, so it is set at import time (conftest imports before any
test module).  The platform is pinned in the config as well as the
environment, which holds even if JAX was imported before this file ran.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_addoption(parser):
    # Validation fast lane: pin the Ed25519 batch-verification worker
    # count for the whole run.  Default 1 keeps tier-1 deterministic on
    # the 1-vCPU CI host (no thread-pool scheduling in the mix); the
    # slow soak set re-exercises workers>1 explicitly
    # (tests/test_sigbatch.py's pool lifecycle soak).
    parser.addoption(
        "--verify-workers",
        type=int,
        default=int(os.environ.get("P1_VERIFY_WORKERS", "1")),
        help="Ed25519 batch-verification worker threads for this run "
        "(env P1_VERIFY_WORKERS; default 1 for determinism)",
    )


def pytest_configure(config):
    # Tier-1 runs `-m 'not slow'` (ROADMAP.md): the marker must be
    # registered or every slow-marked soak raises an unknown-mark warning.
    config.addinivalue_line(
        "markers",
        "slow: long-running soaks excluded from the tier-1 `-m 'not slow'` run",
    )
    config.addinivalue_line(
        "markers",
        "sim: deterministic network-simulator scenarios (node/netsim.py) "
        "— virtual-time runs selectable with `-m sim`; tier-1 carries "
        "the quick set, the 1000-node acceptance runs are also `slow`",
    )
    config.addinivalue_line(
        "markers",
        "chaos: combined-fault schedules over the simulated mesh "
        "(node/chaos.py) — tier-1 carries the bounded ~30-schedule "
        "sweep, the ≥200-schedule sweep is also `slow`",
    )
    config.addinivalue_line(
        "markers",
        "staged: staged-pipeline coverage (node/pipeline.py, round 19) "
        "— lane offload, ordering/digest equivalence with staging on "
        "vs off, and worker-crash respawn; selectable with `-m staged`",
    )
    from p1_tpu.core import keys

    keys.set_verify_workers(config.getoption("--verify-workers"))


def pytest_sessionstart(session):
    devices = jax.devices()
    assert devices[0].platform == "cpu", f"tests must run on CPU, got {devices}"
    assert len(devices) == 8, f"expected 8 virtual CPU devices, got {len(devices)}"
