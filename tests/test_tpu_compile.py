"""The main path's device programs compile for a v5e chip, at real size.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2.3).  A compile that
passes is not a chip run: nothing here runs, so it says nothing about
results or times.  What it does catch, at no chip time, is what the chip's
compiler would refuse — a tile not aligned to the layout, too much fast
memory, a kernel that cannot be partitioned.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports every test file.  The persistent compile cache is off around these
compiles (an entry written for a described chip cannot be read back).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

U32 = jnp.uint32


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, U32, sharding=sharding) for s in shapes]


SEARCH_ARGS = ((8,), (3,), (8,), ())  # midstate, tail, target, nonce base


def test_pallas_search_step_one_chip(topo):
    from p1_tpu.hashx.pallas_backend import pallas_search_fn

    step = pallas_search_fn(1 << 27, sub=16)
    args = _shapes(SingleDeviceSharding(topo.devices[0]), *SEARCH_ARGS)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # The names the device trace shows: the module and the kernel.
    assert compiled.as_text().startswith("HloModule jit_pallas_search_step")
    assert "%sha256d_search" in compiled.as_text()


def test_sharded_pallas_step_four_chips(topo):
    from jax.sharding import Mesh
    import numpy as np

    from p1_tpu.hashx.sharded import AXIS, jit_sharded_step

    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    step = jit_sharded_step(mesh, 1 << 27, kernel="pallas")
    args = _shapes(NamedSharding(mesh, P()), *SEARCH_ARGS)
    text = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text  # the cross-chip pmin
    assert text.startswith("HloModule jit_sharded_search_step")
    assert "%sha256d_search" in text


@pytest.mark.parametrize("chips", [1, 4])
def test_chained_search_step(topo, chips):
    """The form the host loop launches: a fifth operand, the result of the
    launch before, and one result out; still one kernel op a launch."""
    from jax.sharding import Mesh
    import numpy as np

    from p1_tpu.hashx.pallas_backend import pallas_search_fn
    from p1_tpu.hashx.sharded import AXIS, jit_sharded_step

    if chips == 1:
        step = jax.jit(pallas_search_fn(1 << 27, sub=16))
        sharding = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
        step = jit_sharded_step(mesh, 1 << 27, kernel="pallas")
        sharding = NamedSharding(mesh, P())
    args = _shapes(sharding, *SEARCH_ARGS)
    prior = jax.ShapeDtypeStruct((2,), U32, sharding=sharding)
    text = step.lower(*args, prior).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "%sha256d_search" in text
    assert ("all-reduce" in text) == (chips == 4)


@pytest.mark.parametrize("difficulty", [20, 28])
def test_sharded_step_sized_from_difficulty(topo, difficulty):
    """The mesh steps a four-chip backend sizes from the difficulty (the
    floor at d20; pod-d28's step), in the chained form the host loop
    launches."""
    from jax.sharding import Mesh
    import numpy as np

    from p1_tpu.hashx.jax_backend import _RAMP_FLOOR
    from p1_tpu.hashx.pallas_backend import _DEFAULT_BATCH
    from p1_tpu.hashx.sharded import AXIS, jit_sharded_step, mesh_batch

    batch = mesh_batch(difficulty, 4, _RAMP_FLOOR, _DEFAULT_BATCH)
    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    sharding = NamedSharding(mesh, P())
    args = _shapes(sharding, *SEARCH_ARGS, (2,))
    text = jit_sharded_step(mesh, batch, kernel="pallas").lower(*args).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 1
    assert "all-reduce" in text


def test_replay_scan_config3(topo):
    from p1_tpu.hashx.jax_sha256 import default_unroll, verify_header_chain_segments

    fn = functools.partial(verify_header_chain_segments, unroll=default_unroll("tpu"))
    args = _shapes(SingleDeviceSharding(topo.devices[0]), (2, 8192, 20), (8,), ())
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_replay_staging_mainnet(topo):
    # The byte swap and join that put 870,000 uploaded headers (106 full
    # segments and a padded tail) into the scan's words: one fusion, no
    # loop, so the trace's only u32[107,8192,20] ``while`` stays the scan.
    from p1_tpu.chain.replay import _stage_words

    args = _shapes(SingleDeviceSharding(topo.devices[0]), (106, 8192, 20), (1, 8192, 20))
    compiled = jax.jit(_stage_words).lower(*args).compile()
    assert compiled.out_info.shape == (107, 8192, 20)
    assert "while" not in compiled.as_text()
