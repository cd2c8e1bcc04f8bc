"""Multi-chip sharded nonce search: ``shard_map`` + ``pmin`` over a Mesh.

Capability parity: the north star's pod-scale mode — "Nonce ranges shard
across chips with a ``pmin``-based first-hit reduction so a v5e-8 pod
presents as a single miner on the gossip network" (BASELINE.json:5; config 5
at BASELINE.json:11).  TPU-first design: the mesh is a 1-D
``jax.sharding.Mesh`` over all chips, each device scans a **contiguous,
disjoint** block of the step's nonce range, and one ``lax.pmin`` over the
per-device first-hit offsets (sentinel = whole span) rides the ICI to give
the deterministic global earliest nonce — 4 bytes cross the ICI per step,
nothing crosses per candidate.

Contiguous blocks (device d owns ``[base + d*batch, base + (d+1)*batch)``)
rather than interleaved strides keep the global offset a pure affine map of
the local one, so the ``pmin`` argument *is* the earliest-nonce order and
the result is bit-identical to a single-device scan of the same range —
the mesh-parity tests assert exactly that.

The mesh step follows the difficulty.  A chip with no hit scans its whole
block, so a step lasts a full per-chip scan whenever any chip misses, and
every nonce past the first hit counts for nothing.  A fixed 2^27 per chip
made a four-chip step 2^29 nonces against ~2^28 a d28 block, and the pod
got 1.69 chips' worth out of four (PERF.md §6).  Unless the caller fixes
the per-chip batch, ``mesh_batch`` sizes it per search so that a step is
at most 2^(d - MESH_STEP_SHIFT) nonces, within the kernel's own bounds.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from p1_tpu.hashx.backend import HashBackend, register
from p1_tpu.hashx.jax_backend import (
    _RAMP_FLOOR,
    PipelinedSearchMixin,
    StepFn,
    default_batch,
    is_tpu_platform,
)
from p1_tpu.hashx.jax_sha256 import default_unroll, search_step

_U32 = jnp.uint32
AXIS = "chips"

#: A mesh step at difficulty d covers at most 2^(d - MESH_STEP_SHIFT)
#: nonces, so a block of ~2^d hashes takes ~2^MESH_STEP_SHIFT steps and
#: overscans by at most one step.  Measured on four v5e chips at d28
#: (PERF.md §6): a shift of 3 gained 1.3% over 2 (2.643 G against 2.608 G
#: H/s) for 9.5 launches a block against 5.5, and each launch leaves the
#: device ~1 ms idle, so 2 keeps the host's headroom.
MESH_STEP_SHIFT = 2
#: The smallest per-device batch off a TPU mesh: a multiple of the Pallas
#: kernel's (16, 128) tile, and small enough that a CPU validation mesh
#: sizes its step from the difficulty at the difficulties tests mine.
_HOST_MESH_FLOOR = 1 << 12


def mesh_batch(difficulty: int, n_devices: int, floor: int, cap: int) -> int:
    """The per-device batch of a mesh step at ``difficulty``: the largest
    power of two ``b`` with ``n_devices * b <= 2**(difficulty -
    MESH_STEP_SHIFT)``, clamped to ``[floor, cap]``.  A pure function of
    its arguments, so every process of a multi-host pod picks the same."""
    shift = difficulty - MESH_STEP_SHIFT - (n_devices - 1).bit_length()
    return min(max(1 << shift if shift >= 0 else 0, floor), cap)


def make_mesh(
    n_devices: int | None = None, platform: str | None = None
) -> Mesh:
    """A 1-D mesh over the first ``n_devices`` devices (default: all)."""
    devices = jax.devices(platform) if platform else jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, only {len(devices)} available"
            )
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (AXIS,))


@functools.cache
def jit_sharded_step(
    mesh: Mesh,
    batch_per_device: int,
    unroll: int | None = None,
    kernel: str = "xla",
    interpret: bool = False,
) -> StepFn:
    """Jitted sharded step closed over mesh + per-device batch.

    Signature matches ``jit_search_step``: (midstate, tail, target,
    nonce_base) -> uint32 offset of the earliest hit in
    [nonce_base, nonce_base + n_devices*batch_per_device), or the span.
    All inputs are replicated (``P()``); the output is replicated too —
    ``pmin`` makes it device-invariant, so any shard can be read back.

    Chained form: called with a fifth, replicated operand ``prior`` (the
    result of the mesh step launched before) it returns its own result,
    ``(offset, found)`` in one array.  Each chip's body skips its hashing
    when the prior's ``found`` is set; ``found`` is derived from the offset
    after ``pmin``, so every chip holds the same result for the next step.

    ``kernel`` picks the per-device search body: ``"xla"`` (the jax_sha256
    formulation — also what CPU validation meshes run) or ``"pallas"``
    (the Mosaic kernel of pallas_backend inside shard_map, so every chip
    of a real TPU mesh runs the single-chip kernel; ``interpret=True``
    runs it in the Pallas interpreter on a CPU mesh).  Cached per batch:
    the backend's difficulty-sized steps each compile once.
    """
    n = mesh.devices.size
    span = n * batch_per_device
    if span >= 1 << 32:
        raise ValueError("step span must stay below uint32 nonce space")
    platform = mesh.devices.flat[0].platform
    if kernel == "pallas":
        from p1_tpu.hashx.pallas_backend import pallas_search_fn

        if not interpret and not is_tpu_platform(platform):
            raise RuntimeError(
                f"the pallas body needs a TPU mesh, and this mesh is "
                f"{platform!r}; pass interpret=True to run it in the "
                "Pallas interpreter"
            )
        device_search = pallas_search_fn(
            batch_per_device, interpret=interpret, unroll=unroll
        )
    elif kernel == "xla":
        if unroll is None:
            # Resolve against the mesh's platform, not the ambient default
            # backend: a CPU validation mesh on a TPU host must get the
            # trace-tiny body, and vice versa.
            unroll = default_unroll(platform)
        device_search = functools.partial(
            search_step, batch=batch_per_device, unroll=unroll
        )
    else:
        raise ValueError(f"unknown sharded kernel {kernel!r}")

    # The pallas body needs check_vma off: pallas' internal grid indexing
    # emits unvarying operands the varying-manual-axes checker rejects
    # (JAX's own suggested workaround).  The XLA body keeps the check and
    # the explicit pcast promotion it requires.
    check_vma = kernel != "pallas"

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P()),
        out_specs=P(),
        check_vma=check_vma,
    )
    def mesh_step(midstate, tail, target, nonce_base, prior):
        d = lax.axis_index(AXIS).astype(_U32)
        base = nonce_base + d * _U32(batch_per_device)
        skip = prior
        if check_vma:
            # ``base`` varies per device, so the whole hash dataflow is
            # varying over the mesh axis; promote the replicated inputs to
            # match, or the fori_loop carry in the compression rejects the
            # mixed types.
            midstate, tail, target, skip = (
                lax.pcast(x, AXIS, to="varying")
                for x in (midstate, tail, target, prior)
            )
        off = device_search(midstate, tail, target, base, skip)[0]
        hit = off < _U32(batch_per_device)
        global_off = jnp.where(hit, d * _U32(batch_per_device) + off, _U32(span))
        global_off = lax.pmin(global_off, AXIS)
        found = (prior[1] != 0) | (global_off < _U32(span))
        return jnp.stack([global_off, found.astype(_U32)])

    @jax.jit
    def sharded_search_step(midstate, tail, target, nonce_base, prior=None):
        # Named: the mesh program's module in the device trace.
        if prior is None:
            no_prior = jnp.zeros((2,), _U32)
            return mesh_step(midstate, tail, target, nonce_base, no_prior)[0]
        return mesh_step(midstate, tail, target, nonce_base, prior)

    return sharded_search_step


@register("sharded")
class ShardedBackend(PipelinedSearchMixin, HashBackend):
    """SHA-256d search sharded over every chip of a device mesh.

    ``batch`` fixes the per-device batch; one step then evaluates
    ``n_devices * batch`` nonces.  Left out, each search sizes it from its
    difficulty (``batch_for``, ``mesh_batch``) between a floor and the
    platform's default, which ``batch`` and ``step_span`` then hold: the
    largest step.  With one device this degrades gracefully to the
    single-chip search (the ``pmin`` is a no-op), so the same backend name
    works from a laptop CPU to a pod slice.
    """

    def __init__(
        self,
        batch: int | None = None,
        n_devices: int | None = None,
        platform: str | None = None,
        unroll: int | None = None,
        kernel: str | None = None,
        interpret: bool = False,
    ):
        self.mesh = make_mesh(n_devices, platform)
        mesh_platform = self.mesh.devices.flat[0].platform
        if kernel is None:
            # TPU meshes run the Mosaic kernel; CPU validation meshes run
            # the XLA body.  A choice made from the mesh's platform, not a
            # fallback: the Pallas body on a CPU mesh is asked for
            # explicitly (kernel="pallas", interpret=True).
            kernel = "pallas" if is_tpu_platform(mesh_platform) else "xla"
        #: The smallest per-device batch ``batch_for`` picks; None when
        #: the caller fixed the batch.
        self.batch_floor: int | None = None
        if batch is None:
            if kernel == "pallas" and is_tpu_platform(mesh_platform):
                # The kernel's own big dispatch-amortizing step, not the
                # XLA-carry-sized default.
                from p1_tpu.hashx.pallas_backend import _DEFAULT_BATCH

                batch = _DEFAULT_BATCH
            else:
                batch = default_batch(mesh_platform)
            self.batch_floor = (
                _RAMP_FLOOR if is_tpu_platform(mesh_platform) else _HOST_MESH_FLOOR
            )
        if batch <= 0 or batch & (batch - 1):
            raise ValueError(f"batch must be a power of two, got {batch}")
        if kernel == "pallas":
            # Mirror PallasTPUBackend's construction-time guards: the
            # kernel's first-hit min runs in int32 and nonces tile as
            # (sub, 128) blocks — fail here, not at the first search.
            from p1_tpu.hashx.pallas_backend import _DEFAULT_SUB

            block = _DEFAULT_SUB * 128
            if batch % block:
                raise ValueError(
                    f"per-device batch {batch} must be a multiple of {block} "
                    "for the pallas kernel"
                )
            if batch >= 1 << 31:
                raise ValueError(f"per-device batch {batch} must be < 2**31")
        self.n_devices = self.mesh.devices.size
        self.batch = batch
        self.kernel = kernel
        self.step_span = self.n_devices * batch
        if self.step_span >= 1 << 32:
            # jit_sharded_step would reject this at first search; fail at
            # construction instead (reachable: 32 devices x the 2**27
            # pallas default).
            raise ValueError(
                f"step span {self.step_span} (= {self.n_devices} devices x "
                f"batch {batch}) must stay below uint32 nonce space"
            )
        self.unroll = unroll
        self.interpret = interpret
        # No opening ramp: the step is sized from the difficulty instead.
        self.ramp_floor = None

    def batch_for(self, difficulty: int) -> int:
        """The per-device batch of a search at ``difficulty``."""
        if self.batch_floor is None:
            return self.batch
        return mesh_batch(difficulty, self.n_devices, self.batch_floor, self.batch)

    def _step_span_for(self, difficulty: int) -> int:
        return self.n_devices * self.batch_for(difficulty)

    def _make_step(self, span: int) -> StepFn:
        return jit_sharded_step(
            self.mesh, span // self.n_devices, self.unroll, self.kernel,
            self.interpret,
        )

    def _placement(self):
        return NamedSharding(self.mesh, P())
