"""Bring-up check: the proof-of-work node's main path on a TPU chip.

    python chip_smoke.py [--seed N]       # one chip: mine, parity, replay, sigs
    python chip_smoke.py --chips 4        # four chips: the pod search only

One process: this one is the only process that touches JAX, because a chip
belongs to one process at a time.  It refuses, printing what it found and
exiting non-zero, unless JAX's first device is a TPU; there is no CPU
fallback.  Each phase prints one line (name, PASS/FAIL, seconds, key
figures, compile seconds and persistent-cache hits); the last line is
``{"ok": true, "device": {...}}`` and only when every phase passed.

Phases on one chip, at the sizes users run:

- mine: ``p1 node --backend tpu`` (the kernel's production batch, 2**27,
  sub=16) at difficulty 28 mines into a ``--store``; a wallet client pays
  over the wire, a light client fetches the header chain and checks it on
  the host (PoW, linkage) and the payments' inclusion proofs against it.
- parity: the tpu backend's earliest hit equals the host scan's on seeded
  headers at d20-22 over [0, 2**24); a full step at an impossible
  difficulty finds nothing and counts every nonce.
- replay: 10,000 headers (config 3) verified by the device scan and the
  host agree, clean and with one seeded broken prev-hash.
- sigs: the device Ed25519 MSM's verdicts on 256 seeded signatures, clean
  and with one corrupted, equal the serial host verify.

With ``--chips 4``, only the pod phase: the sharded Pallas search over a
4-chip mesh against the single-chip backend at d28, a low-difficulty case
whose first hit lies on a chip other than 0, and each chip's nonce share.

The figures printed are for reading, not asserted: the benchmark owns them.
``tests/test_chip_smoke.py`` runs the same phases at ``TINY`` size in the
Pallas interpreter on CPU.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import io
import json
import os
import random
import sys
import tempfile
import time
import traceback


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that scales.  ``REAL`` is what the chip runs."""

    interpret: bool  # run the Pallas kernel in the interpreter (CPU tests)
    batch: int | None  # tpu backend step; None = the backend's default
    mine_difficulty: int
    mine_blocks: int
    parity_difficulties: tuple[int, ...]
    parity_range: int
    replay_headers: int
    replay_difficulty: int
    sigs: int
    pod_difficulty: int
    pod_range: int
    pod_batch: int | None  # per-chip step in the interpreter; chips: default
    pod_low_batch: int
    pod_low_difficulty: int
    timeout_s: float  # the mine phase's wallet and light-client budget


REAL = Sizes(
    interpret=False,
    batch=None,  # 2**27, the kernel's production batch
    mine_difficulty=28,  # config 5: ~2**28 hashes per block
    mine_blocks=5,
    parity_difficulties=(20, 21, 22),
    parity_range=1 << 24,
    replay_headers=10_000,
    replay_difficulty=8,
    sigs=256,
    pod_difficulty=28,
    pod_range=1 << 30,
    pod_batch=None,  # sized from the difficulty (sharded.mesh_batch)
    pod_low_batch=2048,
    pod_low_difficulty=11,
    timeout_s=600.0,
)

TINY = Sizes(
    interpret=True,
    batch=4096,
    mine_difficulty=8,
    mine_blocks=5,
    parity_difficulties=(6, 7, 8),
    parity_range=4096,
    replay_headers=300,
    replay_difficulty=4,
    sigs=8,
    pod_difficulty=9,
    pod_range=1 << 14,
    pod_batch=2048,
    pod_low_batch=2048,
    pod_low_difficulty=11,
    timeout_s=300.0,
)

#: Difficulty no hash can meet: the search scans its whole range.
IMPOSSIBLE = 255


class CompileMeter:
    """Seconds JAX spent compiling (or loading from the persistent cache)
    and persistent-cache hits, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[float, int]:
        return self.compile_s, self.cache_hits


def tpu_backend(sizes: Sizes):
    """The single-chip Pallas backend.  With no kwargs it is the very
    instance ``p1 node --backend tpu`` builds (``get_backend`` memoizes)."""
    from p1_tpu.hashx import get_backend

    kwargs = {"batch": sizes.batch} if sizes.batch else {}
    if sizes.interpret:
        kwargs["interpret"] = True
    return get_backend("tpu", **kwargs)


def host_backend():
    """The fastest host scan: the native engine, or hashlib without a
    toolchain."""
    from p1_tpu.hashx import get_backend
    from p1_tpu.hashx.native_build import NativeBuildError

    try:
        return get_backend("native")
    except (NativeBuildError, OSError):
        return get_backend("cpu")


def seeded_prefix(rng: random.Random, difficulty: int) -> bytes:
    from p1_tpu.core.header import BlockHeader

    return BlockHeader(
        1, rng.randbytes(32), rng.randbytes(32), 1_735_689_600, difficulty, 0
    ).mining_prefix()


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- mine ------------------------------------------------------------------


async def _drive_node(sizes: Sizes, store: str, seed: int) -> dict:
    """``p1 node`` in this process, plus a wallet and a light client on
    the wire.  The node's stdout is captured (ready line, final status)
    and its stdin is a pipe that carries the stop time."""
    from p1_tpu.cli import build_parser
    from p1_tpu.core.keys import Keypair
    from p1_tpu.miner import Miner
    from p1_tpu.node.runner import run_node

    d = sizes.mine_difficulty
    wallet = Keypair.from_seed_text(f"chip-smoke-wallet-{seed}")
    payee = Keypair.from_seed_text(f"chip-smoke-payee-{seed}")
    args = build_parser().parse_args(
        ["node", "--backend", "tpu", "--difficulty", str(d), "--port", "0",
         "--store", store, "--miner-id", wallet.account, "--deadline", "stdin"]
    )
    # The real path builds its backend from --backend; the interpreter is
    # only ever asked for explicitly, so the tiny run hands its miner in.
    miner = Miner(backend=tpu_backend(sizes)) if sizes.interpret else None

    out = io.StringIO()
    stop_r, stop_w = os.pipe()
    saved_stdin = sys.stdin
    sys.stdin = os.fdopen(stop_r)
    try:
        with contextlib.redirect_stdout(out):
            node = asyncio.ensure_future(run_node(args, miner=miner))
            try:
                figures = await asyncio.wait_for(
                    _wallet_and_light_client(
                        node, out, d, wallet, payee, sizes.mine_blocks
                    ),
                    sizes.timeout_s,
                )
            finally:
                os.write(stop_w, f"{time.time()!r}\n".encode())  # stop now
                rc = await asyncio.wait_for(node, 120)
    finally:
        os.close(stop_w)
        sys.stdin.close()
        sys.stdin = saved_stdin
    check(rc == 0, f"p1 node exited {rc}")
    status = [json.loads(x) for x in out.getvalue().splitlines() if '"height"' in x]
    check(bool(status), "p1 node printed no final status")
    final = status[-1]
    figures.update(
        blocks_mined=final["blocks_mined"],
        hashes_per_sec=final["hashes_per_sec"],
        time_to_block_s=final["time_to_block_s"],
    )
    return figures


async def _wallet_and_light_client(node, out, d, wallet, payee, want_blocks) -> dict:
    from p1_tpu.chain import replay_host
    from p1_tpu.chain.proof import verify_tx_proof
    from p1_tpu.core.genesis import genesis_hash
    from p1_tpu.core.tx import Transaction
    from p1_tpu.node import client

    host = "127.0.0.1"
    port = None
    while port is None:
        if node.done():
            raise RuntimeError(f"p1 node ended before it was ready: {node.result()}")
        for line in out.getvalue().splitlines():
            if line.startswith('{"ready"'):
                port = json.loads(line)["ready"]
        await asyncio.sleep(0.05)

    # Wallet: wait for the first coinbase, then pay three transfers.
    amount, fee, n_tx = 3, 1, 3
    while True:
        acct = await client.get_account(host, port, wallet.account, d)
        if acct.balance >= n_tx * (amount + fee):
            break
        await asyncio.sleep(0.1)
    tag = genesis_hash(d)
    txs = [
        Transaction.transfer(
            wallet, payee.account, amount, fee, acct.next_seq + i, chain=tag
        )
        for i in range(n_tx)
    ]
    for tx in txs:
        await client.send_tx(host, port, tx, d)

    # Wait until every transfer is mined and the chain is deep enough.
    pending = {tx.txid() for tx in txs}
    while pending:
        for txid in list(pending):
            if await client.get_proof(host, port, txid, d) is not None:
                pending.discard(txid)
        await asyncio.sleep(0.1)
    while (await client.get_status(host, port, d))["height"] < want_blocks:
        await asyncio.sleep(0.1)

    # Light client: fetch the header chain, verify it on the host, and
    # anchor every payment's proof to the verified chain.
    headers = await client.get_headers(host, port, d)
    report = replay_host(headers)
    check(report.valid, f"light client: header chain invalid at {report.first_invalid}")
    height = len(headers) - 1
    check(height >= want_blocks, f"light client: height {height} < {want_blocks}")
    tx_heights = set()
    for tx in txs:
        proof = await client.get_proof(host, port, tx.txid(), d)
        verify_tx_proof(proof, d, tag, txid=tx.txid())
        check(
            proof.height <= height
            and headers[proof.height].block_hash() == proof.header.block_hash(),
            "a payment's block is not on the light client's verified chain",
        )
        tx_heights.add(proof.height)
    payee_acct = await client.get_account(host, port, payee.account, d)
    check(payee_acct.balance == n_tx * amount, f"payee balance {payee_acct.balance}")
    return {
        "height": height,
        "tip": headers[-1].block_hash().hex()[:16],
        "transfers": n_tx,
        "tx_block_heights": sorted(tx_heights),
        "headers_verified": len(headers),
    }


def phase_mine(sizes: Sizes, seed: int) -> dict:
    from p1_tpu.chain.tooling import load_store

    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        store = os.path.join(tmp, "chain.dat")
        figures = asyncio.run(_drive_node(sizes, store, seed))
        # The store holds what the light client verified.
        _, chain = load_store(store, sizes.mine_difficulty)
        main = list(chain.main_chain())
        check(
            len(main) - 1 >= figures["height"],
            "store is shorter than the served chain",
        )
        check(
            main[figures["height"]].block_hash().hex()[:16] == figures["tip"],
            "store's chain differs from the served chain",
        )
        figures["store_height"] = len(main) - 1
    be = tpu_backend(sizes)
    figures.update(batch=be.batch, sub=be.sub)
    return figures


# -- parity ----------------------------------------------------------------


def phase_parity(sizes: Sizes, seed: int) -> dict:
    tpu, host = tpu_backend(sizes), host_backend()
    rng = random.Random(seed)
    nonces = []
    for d in sizes.parity_difficulties:
        prefix = seeded_prefix(rng, d)
        got = tpu.search(prefix, 0, sizes.parity_range, d)
        want = host.search(prefix, 0, sizes.parity_range, d)
        check(
            (got.nonce, got.hashes_done) == (want.nonce, want.hashes_done),
            f"d{d}: tpu found {got.nonce} after {got.hashes_done}, "
            f"{host.name} found {want.nonce} after {want.hashes_done}",
        )
        nonces.append(got.nonce)
    check(any(n is not None for n in nonces), "no parity case had a hit")
    miss = tpu.search(seeded_prefix(rng, IMPOSSIBLE), 0, tpu.batch, IMPOSSIBLE)
    check(
        miss.nonce is None and miss.hashes_done == tpu.batch,
        f"impossible difficulty: {miss}",
    )
    return {"host": host.name, "nonces": nonces, "full_step_miss": tpu.batch}


# -- replay ----------------------------------------------------------------


def phase_replay(sizes: Sizes, seed: int) -> dict:
    from p1_tpu.chain import generate_headers, replay_device, replay_host

    host = host_backend()
    t0 = time.perf_counter()
    headers = generate_headers(
        sizes.replay_headers, sizes.replay_difficulty, backend=host
    )
    gen_s = time.perf_counter() - t0
    clean_dev, clean_host = replay_device(headers), replay_host(headers)
    check(
        clean_dev.valid and clean_host.valid,
        f"clean chain: {clean_dev} {clean_host}",
    )
    bad_at = random.Random(seed).randrange(1, len(headers))
    broken = list(headers)
    broken[bad_at] = dataclasses.replace(headers[bad_at], prev_hash=bytes(32))
    bad_dev, bad_host = replay_device(broken), replay_host(broken)
    check(
        bad_dev.first_invalid == bad_host.first_invalid == bad_at,
        f"broken at {bad_at}: device says {bad_dev.first_invalid}, "
        f"host says {bad_host.first_invalid}",
    )
    return {
        "headers": len(headers),
        "sealed_by": host.name,
        "seal_s": round(gen_s, 3),
        "broken_at": bad_at,
        "device_hps": round(bad_dev.headers_per_sec),
        "host_hps": round(bad_host.headers_per_sec),
    }


# -- sigs ------------------------------------------------------------------


def phase_sigs(sizes: Sizes, seed: int) -> dict:
    from p1_tpu.core import keys
    from p1_tpu.core.keys import Keypair
    from p1_tpu.hashx import ed25519_msm

    rng = random.Random(seed)
    triples = []
    for i in range(sizes.sigs):
        kp = Keypair.from_seed_text(f"chip-smoke-sig-{seed}-{i}")
        msg = rng.randbytes(64)
        triples.append((kp.pubkey, kp.sign(msg), msg))
    bad = list(triples)
    at = rng.randrange(len(bad))
    pub, sig, msg = bad[at]
    bad[at] = (pub, sig, msg[:-1] + bytes([msg[-1] ^ 1]))

    verdicts = {}
    for name, batch in (("clean", triples), ("corrupted", bad)):
        serial = all(keys.verify(*t) for t in batch)
        device = ed25519_msm.verify_batch_device(batch, n_devices=1)
        check(device == serial, f"{name}: device {device}, serial host {serial}")
        verdicts[name] = device
    check(verdicts == {"clean": True, "corrupted": False}, f"verdicts {verdicts}")
    return {"signatures": len(triples), "corrupted_at": at, **verdicts}


# -- pod (four chips) ------------------------------------------------------


def phase_pod(sizes: Sizes, seed: int) -> dict:
    from p1_tpu.hashx import get_backend

    # On chips the backend picks the Pallas body and its batch itself, as
    # `p1 pod` does; the interpreter is asked for, with a small batch.
    kwargs = (
        {"kernel": "pallas", "interpret": True, "batch": sizes.pod_batch}
        if sizes.interpret
        else {}
    )
    sharded = get_backend("sharded", n_devices=4, **kwargs)
    devices = list(sharded.mesh.devices.flat)
    want_platform = "cpu" if sizes.interpret else "tpu"
    check(
        sharded.kernel == "pallas"
        and len({dev.id for dev in devices}) == 4
        and all(dev.platform == want_platform for dev in devices),
        f"mesh {devices} with kernel {sharded.kernel}",
    )
    single = tpu_backend(sizes)

    # High difficulty: a seeded range that holds a hit, found by the single
    # chip, then searched by the mesh.
    rng = random.Random(seed)
    d = sizes.pod_difficulty
    for _ in range(16):
        prefix = seeded_prefix(rng, d)
        want = single.search(prefix, 0, sizes.pod_range, d)
        if want.nonce is not None:
            break
    else:
        raise AssertionError(f"no seeded range of {sizes.pod_range} holds a d{d} hit")
    got = sharded.search(prefix, 0, sizes.pod_range, d)
    check(
        (got.nonce, got.hashes_done) == (want.nonce, want.hashes_done),
        f"d{d}: mesh found {got.nonce}, one chip found {want.nonce}",
    )
    batch = sharded.batch_for(d)
    span = 4 * batch
    share = [0] * 4  # nonces of [0, hit] each chip was given
    for k in range(4):
        full, rest = divmod(want.nonce + 1, span)
        share[k] = full * batch + min(max(rest - k * batch, 0), batch)

    # Low difficulty, small blocks: the host scan picks a header whose
    # first hit lies in a chip other than 0's block of the first step.
    low = get_backend(
        "sharded", n_devices=4, batch=sizes.pod_low_batch, kernel="pallas",
        interpret=sizes.interpret,
    )
    host = host_backend()
    dl = sizes.pod_low_difficulty
    for _ in range(64):
        low_prefix = seeded_prefix(rng, dl)
        truth = host.search(low_prefix, 0, low.step_span, dl)
        if truth.nonce is not None and truth.nonce >= low.batch:
            break
    else:
        raise AssertionError("no seeded header hits past chip 0's block")
    low_got = low.search(low_prefix, 0, low.step_span, dl)
    check(
        low_got.nonce == truth.nonce,
        f"low: mesh {low_got.nonce}, host {truth.nonce}",
    )
    return {
        "devices": [dev.id for dev in devices],
        "batch_per_chip": batch,
        "difficulty": d,
        "nonce": got.nonce,
        "nonce_share": [round(s / sum(share), 4) for s in share],
        "nonces_per_chip": share,
        "low_hit_chip": truth.nonce // low.batch,
        "low_nonce": truth.nonce,
    }


# -- driver ----------------------------------------------------------------

ONE_CHIP = (("mine", phase_mine), ("parity", phase_parity),
            ("replay", phase_replay), ("sigs", phase_sigs))
FOUR_CHIPS = (("pod", phase_pod),)


def run_phases(phases, sizes: Sizes, seed: int, meter: CompileMeter) -> bool:
    """Run every phase, print one line each; True when all passed."""
    ok = True
    for name, phase in phases:
        c0, h0 = meter.snapshot()
        t0 = time.perf_counter()
        try:
            figures = phase(sizes, seed)
            verdict = "PASS"
        except Exception as exc:  # a failed phase is reported, the rest still run
            traceback.print_exc()
            figures = {"error": f"{type(exc).__name__}: {exc}"[:500]}
            verdict = "FAIL"
            ok = False
        c1, h1 = meter.snapshot()
        figures.update(compile_s=round(c1 - c0, 3), cache_hits=h1 - h0)
        print(
            f"{name}: {verdict} {time.perf_counter() - t0:.3f} s "
            f"{json.dumps(figures)}",
            flush=True,
        )
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from p1_tpu.hashx import configure_compile_cache

    configure_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"chip_smoke: JAX found no devices: {exc}", file=sys.stderr)
        return 1
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < args.chips:
        print(
            f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
            f"{len(devices)} {platform!r} device(s)",
            file=sys.stderr,
        )
        return 1
    meter = CompileMeter()
    phases = FOUR_CHIPS if args.chips == 4 else ONE_CHIP
    if not run_phases(phases, REAL, args.seed, meter):
        return 1
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": platform,
                    "kind": devices[0].device_kind,
                    "count": args.chips,
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
