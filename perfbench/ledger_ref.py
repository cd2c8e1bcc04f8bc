"""The plain reference of a full node's blocks: transfers parsed from their
wire bytes, the selection rule, an account ledger and the merkle root.

It imports nothing of the program.  A transfer's wire layout is, in order:
a one-byte length and the UTF-8 sender, the same for the recipient,
``>QQQ`` amount, fee and seq, then a one-byte length and each of the chain
tag (the genesis header's SHA-256d), the Ed25519 public key and the
signature.  The signed message is every byte before the public key.  Its
txid is SHA-256d of all of its bytes.  A coinbase has sender
``"coinbase"``, amount the block subsidy, seq the block's height, and
empty tag, key and signature.

- ``select``: the transfers a miner puts in its next block.  From each
  sender, only its pending transfer whose seq is the sender's confirmed
  nonce is eligible; the eligible one with the highest fee goes next (the
  smallest txid among equal fees), and taking it makes the sender's next
  seq eligible.  A transfer its sender cannot pay for (its confirmed
  balance less what the block already takes from it) is passed over, and
  with it the sender's later seqs.  At most ``max_txs`` transfers.
- ``Ledger``: balances and nonces.  A block's coinbase credits its
  recipient the subsidy; each transfer, in block order, must carry its
  sender's nonce and not overdraw it, debits amount and fee, credits the
  recipient the amount; the block's fees go to the coinbase's recipient.
- ``merkle_root``: SHA-256d pairs, the last node of an odd level paired
  with itself; 32 zero bytes for no leaf.
"""

from __future__ import annotations

import dataclasses
import hashlib
import heapq
import struct

COINBASE = "coinbase"
SUBSIDY = 50
#: The node's pool file: this magic, a ``>I`` count, then per record a
#: ``>d`` age in seconds, a ``>I`` length and the transfer's wire bytes.
POOL_MAGIC = b"P1MP0001"
_NUMS = struct.Struct(">QQQ")


def sha256d(data: bytes) -> bytes:
    return hashlib.sha256(hashlib.sha256(data).digest()).digest()


def account_of(pubkey: bytes) -> str:
    """The account a 32-byte public key owns: ``p1`` and the first 16 hex
    digits of its SHA-256."""
    return "p1" + hashlib.sha256(pubkey).hexdigest()[:16]


@dataclasses.dataclass(frozen=True)
class Tx:
    sender: str
    recipient: str
    amount: int
    fee: int
    seq: int
    chain: bytes
    pubkey: bytes
    sig: bytes
    signed: bytes  # the message the signature covers
    txid: bytes

    @property
    def is_coinbase(self) -> bool:
        return self.sender == COINBASE


def parse_tx(buf: bytes, off: int = 0) -> tuple[Tx, int]:
    """The transfer at ``buf[off:]`` and the offset past it."""
    start = off

    def field():
        nonlocal off
        n = buf[off]
        out = bytes(buf[off + 1:off + 1 + n])
        if len(out) != n:
            raise ValueError("truncated transfer")
        off += 1 + n
        return out

    sender, recipient = field().decode(), field().decode()
    amount, fee, seq = _NUMS.unpack_from(buf, off)
    off += _NUMS.size
    chain = field()
    signed_end = off
    pubkey, sig = field(), field()
    return Tx(sender, recipient, amount, fee, seq, chain, pubkey, sig,
              bytes(buf[start:signed_end]), sha256d(bytes(buf[start:off]))), off


def parse_pool_file(data: bytes) -> list[Tx]:
    """Every record of a pool file, in file order."""
    if not data.startswith(POOL_MAGIC):
        raise ValueError("not a pool file")
    (count,) = struct.unpack_from(">I", data, len(POOL_MAGIC))
    off = len(POOL_MAGIC) + 4
    out = []
    for _ in range(count):
        _, n = struct.unpack_from(">dI", data, off)
        off += 12
        tx, end = parse_tx(data, off)
        if end != off + n:
            raise ValueError("record length disagrees with its transfer")
        out.append(tx)
        off = end
    return out


def merkle_root(txids: list[bytes]) -> bytes:
    if not txids:
        return bytes(32)
    level = list(txids)
    while len(level) > 1:
        if len(level) % 2:
            level.append(level[-1])
        level = [sha256d(level[i] + level[i + 1]) for i in range(0, len(level), 2)]
    return level[0]


class Ledger:
    def __init__(self):
        self.balances: dict[str, int] = {}
        self.nonces: dict[str, int] = {}

    def balance(self, account: str) -> int:
        return self.balances.get(account, 0)

    def nonce(self, account: str) -> int:
        return self.nonces.get(account, 0)

    def apply(self, txs: list[Tx], height: int) -> list[str]:
        """Apply one block's transactions, coinbase first; the rules each
        one broke, as text (the block is applied all the same, so that
        one fault is counted once)."""
        faults = []
        if not txs or not txs[0].is_coinbase:
            faults.append("no coinbase")
        fees = 0
        miner = None
        for i, tx in enumerate(txs):
            if tx.is_coinbase:
                if i or tx.amount != SUBSIDY or tx.seq != height or tx.fee:
                    faults.append(f"coinbase {i}: amount {tx.amount}, seq {tx.seq}")
                miner = tx.recipient
                self._credit(miner, tx.amount)
                continue
            if tx.seq != self.nonce(tx.sender):
                faults.append(f"{tx.txid.hex()}: seq {tx.seq}, nonce {self.nonce(tx.sender)}")
            if self.balance(tx.sender) < tx.amount + tx.fee:
                faults.append(f"{tx.txid.hex()}: overdraws {tx.sender}")
            self.nonces[tx.sender] = self.nonce(tx.sender) + 1
            self._credit(tx.sender, -(tx.amount + tx.fee))
            self._credit(tx.recipient, tx.amount)
            fees += tx.fee
        if miner is not None:
            self._credit(miner, fees)
        return faults

    def _credit(self, account: str, v: int) -> None:
        b = self.balance(account) + v
        if b:
            self.balances[account] = b
        else:
            self.balances.pop(account, None)


def pending_by_sender(txs) -> dict[str, dict[int, Tx]]:
    """``select``'s form of a pool: sender -> seq -> transfer."""
    out: dict[str, dict[int, Tx]] = {}
    for tx in txs:
        out.setdefault(tx.sender, {})[tx.seq] = tx
    return out


def select(pending: dict[str, dict[int, Tx]], nonce, balance, max_txs: int) -> list[Tx]:
    """The next block's transfers from ``pending`` (``pending_by_sender``),
    given each account's confirmed ``nonce(account)`` and
    ``balance(account)``."""
    heap = []
    for sender, seqs in pending.items():
        tx = seqs.get(nonce(sender))
        if tx is not None:
            heap.append((-tx.fee, tx.txid, tx))
    heapq.heapify(heap)
    picked: list[Tx] = []
    spent: dict[str, int] = {}
    while heap and len(picked) < max_txs:
        _, _, tx = heapq.heappop(heap)
        cost = tx.amount + tx.fee
        if balance(tx.sender) - spent.get(tx.sender, 0) < cost:
            continue
        spent[tx.sender] = spent.get(tx.sender, 0) + cost
        picked.append(tx)
        nxt = pending[tx.sender].get(tx.seq + 1)
        if nxt is not None:
            heapq.heappush(heap, (-nxt.fee, nxt.txid, nxt))
    return picked
