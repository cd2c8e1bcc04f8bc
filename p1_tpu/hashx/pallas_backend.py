"""Pallas-TPU hash backend: the SHA-256d nonce search as a Mosaic kernel.

This is the literal north-star artifact (BASELINE.json:5 — the miner's inner
loop "becomes a vmapped Pallas SHA-256 kernel that evaluates millions of
candidate nonces per device step"), and it exists for a measured reason, not
ceremony: the pure-XLA formulation (jax_backend.py) is **HBM-bound**.  XLA
compiles the 64-round ``fori_loop`` with its ~25-array uint32 carry spilled
to HBM between (unrolled) round bodies, so at batch 2²⁴ every round group
streams gigabytes through HBM and the VPU idles.  The Pallas kernel instead
works one ``(sub, 128)`` uint32 tile of nonces per grid step with the entire
rolling window held in VMEM/vector registers — HBM traffic is ~zero (a few
SMEM scalars in, 4 bytes out) and the search becomes compute-bound on the
VPU, which is the best a hash search can do on this hardware.

Layout (SURVEY.md §7 step 3):

- Nonces across VPU lanes: grid step ``i`` evaluates flat lane indices
  ``[i·sub·128, (i+1)·sub·128)`` as a ``(sub, 128)`` uint32 tile — the
  native vreg shape for 32-bit data.
- Same round math as jax_sha256 (``_compress`` is reused verbatim inside
  the kernel body: midstate chunk-2 + second pass, schedule extension fused
  into the round loop), so the Pallas/XLA/NumPy formulations stay
  lane-exact by construction.
- Scalar plumbing in SMEM: midstate (8), chunk-2 tail words (3), target
  (8), nonce base (1), prior result (2).  Output is a single SMEM uint32 —
  ``min`` over the grid of the earliest hit's flat index (or ``batch``) —
  accumulated across sequential grid steps, exactly the contract of
  jax_sha256.search_step, so the pipelined host loop and the sharded pmin
  reduction compose unchanged.
- Chained steps: the prior result is the (offset, found) pair of the step
  launched before this one.  When its found flag is set, that step's hit
  ends the search, and this one skips every tile through the early exit
  (see ``_search_kernel``).
- ``interpret=True`` runs the identical kernel on CPU (tests; Mosaic needs
  real TPU hardware otherwise).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from p1_tpu.hashx.backend import HashBackend, register
from p1_tpu.hashx.jax_backend import _RAMP_FLOOR, PipelinedSearchMixin, StepFn
from p1_tpu.hashx.jax_sha256 import _compress, below_target
from p1_tpu.hashx.sha256_ref import IV, K

_U32 = jnp.uint32
# Round constants / IV enter the kernel as SMEM inputs: a Pallas kernel may
# not capture array-valued constants from its closure.
_K_WORDS = np.asarray(K, dtype=np.uint32)
_IV_WORDS = np.asarray(IV, dtype=np.uint32)

#: Rows of 128 lanes per grid step.  A sweep through the removed
#: remote-device runtime put sub=16 on top: 2048 nonces/step keeps the
#: full compression window (~30 live tile-arrays ≈ 0.25 MB) in VMEM;
#: larger tiles spill, smaller ones starve the VPU of independent work.
#: Not swept on a directly attached chip (ROADMAP S10), where the kernel
#: reads 62.9% of its op-count roofline on ``solo-d28`` (PERF.md §5).
_DEFAULT_SUB = 16

#: Device-step batch.  Unlike the XLA backend, the kernel materializes
#: nothing per nonce in HBM, so a big batch costs only abort granularity.
#: 2²⁷ was chosen by a sweep through a remote-device runtime whose
#: per-dispatch cost no longer applies; whether it is still right on a
#: directly attached chip is not measured yet (ROADMAP S10).
_DEFAULT_BATCH = 1 << 27


def _search_kernel(
    mid_ref,
    tail_ref,
    target_ref,
    base_ref,
    prior_ref,
    k_ref,
    iv_ref,
    out_ref,
    *,
    sub: int,
    batch: int,
    unroll: int,
):
    """One grid step: hash a (sub, 128) tile of nonces, fold in its first hit.

    TPU grid steps run sequentially on the core, so the min-accumulation
    into the single SMEM output cell is race-free by construction — and
    that same sequencing powers the **early exit**: once any step has
    recorded a hit, every later step sees it in SMEM and skips its whole
    tile (one scalar read + branch instead of 2·64 compression rounds).
    Exactness is free — grid steps ascend in flat nonce index, so a later
    step can never hold an earlier hit than one already recorded.  This is
    what closes the d28 abort-granularity gap (VERDICT r3 item 4): the
    step containing the hit used to grind out its remaining ~2²⁷ nonces
    (~0.12 s wasted per block at the north-star difficulty); now the
    remainder of the batch costs microseconds.

    The same exit skips a whole launch: with the prior's found flag set
    (``prior_ref[1]``), the output starts at ``batch + 1``, no grid step
    passes the check, and the wrapper reads the sentinel as a miss.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        out_ref[0] = jnp.where(
            prior_ref[1] != 0, jnp.int32(batch + 1), jnp.int32(batch)
        )

    @pl.when(out_ref[0] == jnp.int32(batch))  # no hit recorded yet
    def _():
        rows = jax.lax.broadcasted_iota(_U32, (sub, 128), 0)
        cols = jax.lax.broadcasted_iota(_U32, (sub, 128), 1)
        flat = i.astype(_U32) * _U32(sub * 128) + rows * _U32(128) + cols
        nonces = base_ref[0] + flat

        def bc(scalar):
            return jnp.full((sub, 128), scalar, dtype=_U32)

        zero = jnp.zeros((sub, 128), dtype=_U32)
        # Pass 1, chunk 2: tail words + nonce + pad(0x80) + bitlen 640.
        w = (bc(tail_ref[0]), bc(tail_ref[1]), bc(tail_ref[2]), nonces)
        w += (zero + _U32(0x80000000),) + (zero,) * 10 + (zero + _U32(640),)
        state1 = _compress(
            tuple(bc(mid_ref[k]) for k in range(8)), w, unroll=unroll, ks=k_ref
        )
        # Pass 2 over the 32-byte digest (bitlen 256).
        w2 = (
            state1 + (zero + _U32(0x80000000),) + (zero,) * 6 + (zero + _U32(256),)
        )
        iv = tuple(bc(iv_ref[k]) for k in range(8))
        digest = list(_compress(iv, w2, unroll=unroll, ks=k_ref))

        hits = below_target(digest, tuple(target_ref[k] for k in range(8)))
        # Mosaic has no unsigned-int reductions; flat indices are < 2³¹, so
        # the first-hit min runs in int32 and the wrapper casts back to
        # uint32.
        local = jnp.min(
            jnp.where(hits, flat.astype(jnp.int32), jnp.int32(batch))
        )
        out_ref[0] = jnp.minimum(out_ref[0], local)


def pallas_search_fn(
    batch: int,
    sub: int = _DEFAULT_SUB,
    interpret: bool = False,
    unroll: int | None = None,
):
    """The UNJITTED Pallas search step: (midstate(8,), tail(3,), target(8,),
    nonce_base) -> uint32 first-hit offset in [0, batch] (``batch`` = miss).

    Chained form: with a fifth operand ``prior``, the result of the step
    launched before, it returns its own result, uint32 ``(offset, found)``
    in one array, and skips its hashing when the prior's ``found`` is set.
    ``found`` is 1 when this step hit or skipped, so every launch chained
    behind a hit skips too.

    Composable into larger traced programs — the ``sharded`` backend calls
    it inside ``shard_map`` so each chip of a mesh runs the kernel on its
    own nonce block; ``jit_pallas_search_step`` is the single-device jitted
    form.
    """
    block = sub * 128
    if batch % block:
        raise ValueError(f"batch {batch} not a multiple of the {block} tile")
    if batch >= 1 << 31:
        # The kernel's first-hit min runs in int32 (Mosaic has no unsigned
        # reductions): a 2³¹ batch wraps the miss sentinel negative and
        # silently masks every hit.  Guard at the layer that owns the
        # constraint so every composer (backends, shard_map) inherits it.
        raise ValueError(f"batch {batch} must be < 2**31")
    if unroll is None:
        # Interpret mode lowers through XLA:CPU, where a fully-unrolled
        # 128-round trace compiles for minutes (the trap jax_sha256's
        # rolled loop exists to avoid); Mosaic on real TPU wants the
        # straight-line body.
        unroll = 1 if interpret else 64

    kernel = functools.partial(
        _search_kernel, sub=sub, batch=batch, unroll=unroll
    )
    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    call = pl.pallas_call(
        kernel,
        grid=(batch // block,),
        in_specs=[scalar_spec] * 7,
        out_specs=pl.BlockSpec(
            (1,), lambda i: (0,), memory_space=pltpu.SMEM
        ),
        # NOTE: composing this into shard_map requires check_vma=False on
        # the shard_map (the sharded backend does this): the pallas
        # machinery emits unvarying internal operands (grid indexing) that
        # the varying-manual-axes checker rejects.
        out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
        interpret=interpret,
        # The kernel's name in the compiled program and the device trace.
        name="sha256d_search",
    )

    def pallas_search_step(midstate, tail, target, nonce_base, prior=None):
        out = call(
            midstate,
            tail,
            target,
            jnp.reshape(nonce_base, (1,)),
            jnp.zeros((2,), _U32) if prior is None else prior,
            jnp.asarray(_K_WORDS),
            jnp.asarray(_IV_WORDS),
        )[0]
        offset = jnp.minimum(out, batch).astype(_U32)  # a skip reads as a miss
        if prior is None:
            return offset
        return jnp.stack([offset, (out != batch).astype(_U32)])

    return pallas_search_step


@functools.cache
def jit_pallas_search_step(
    batch: int,
    sub: int = _DEFAULT_SUB,
    platform: str | None = None,
    interpret: bool = False,
    unroll: int | None = None,
) -> StepFn:
    """Jitted single-device ``pallas_search_fn`` (jit_search_step's exact
    signature, the chained form included)."""
    step = pallas_search_fn(batch, sub, interpret, unroll)
    device = jax.devices(platform)[0] if platform else None
    return jax.jit(step, device=device)


@register("tpu")
class PallasTPUBackend(PipelinedSearchMixin, HashBackend):
    """SHA-256d nonce search as a Pallas TPU kernel (north star's ``tpu``).

    Compiled Mosaic on a TPU.  ``interpret=True`` runs the identical kernel
    in the Pallas interpreter (CPU tests); it is never chosen for the
    caller, so without it a host with no TPU raises here instead of mining
    on the interpreter.
    """

    def __init__(
        self,
        batch: int = _DEFAULT_BATCH,
        sub: int = _DEFAULT_SUB,
        platform: str | None = None,
        interpret: bool = False,
    ):
        if not interpret:
            found = platform or jax.default_backend()
            if found != "tpu":
                raise RuntimeError(
                    f"the tpu hash backend needs a TPU, and JAX found "
                    f"{found!r}; pass interpret=True to run the kernel in "
                    "the Pallas interpreter"
                )
        block = sub * 128
        if batch % block:
            raise ValueError(f"batch {batch} must be a multiple of {block}")
        if batch >= 1 << 31:
            # Same int32-sentinel bound pallas_search_fn enforces; checked
            # here too so misconfiguration fails at construction, not at
            # the first search's trace.
            raise ValueError(f"batch {batch} must be < 2**31")
        if _RAMP_FLOOR % block:
            # Ramp spans are powers of two; a tile that doesn't divide them
            # can't take part in the opening ramp.
            self.ramp_floor = None
        self.batch = batch
        self.sub = sub
        self.step_span = batch
        self.platform = platform
        self.interpret = interpret

    def _make_step(self, span: int) -> StepFn:
        return jit_pallas_search_step(
            span, self.sub, self.platform, self.interpret
        )
