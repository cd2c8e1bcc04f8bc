"""Operations per SHA-256d mining candidate, and per header of a chain
verified, counted from FIPS 180-4.

The numerators of the kernel and scan rooflines.  It counts the work the
specification defines, whatever implements it: with the midstate
precomputed, a candidate is one compression of the header's second
chunk, one compression of the 32-byte digest, and a 256-bit compare with
the target.  A header of a chain verified is three compressions (its two
chunks, then its digest), the compare with the target, and the compare
of its previous hash with its parent's digest.  Every 32-bit add, and, xor, not, shift and rotate is one
operation (a rotate is one even where the hardware needs three).  No
implementation trick is counted: not the constant words of the padding,
not an early exit, not a skipped last round.  So a kernel that issues
fewer operations can raise its share only by running faster, and the
share never reaches 100% while the chip issues more than the spec
needs.  ``tests/test_opcount.py`` checks this against ``sha256.py``,
which counts the operations of a real digest.
"""

#: Message schedule, t = 16..63: sigma0 and sigma1 (2 rotates, 1 shift,
#: 2 xors each) and 3 adds.
SCHEDULE = 48 * (5 + 5 + 3)
#: One round: Sigma1 (3 rotates, 2 xors), Ch (2 ands, 1 not, 1 xor),
#: T1 (4 adds), Sigma0 (5), Maj (3 ands, 2 xors), T2 (1 add), e and a
#: (1 add each).
ROUNDS = 64 * (5 + 4 + 4 + 5 + 5 + 1 + 1 + 1)
#: The feed-forward: 8 adds.
COMPRESSION = SCHEDULE + ROUNDS + 8
#: ``digest < target`` over 8 limbs: a compare per limb, and for the 7
#: upper limbs an equality, an and and an or.
COMPARE = 8 + 7 * 3
#: The previous hash against the parent's digest: a compare per word and
#: 7 ands.
LINK = 8 + 7


def sha256d_candidate_ops() -> int:
    """32-bit operations the spec needs to test one nonce."""
    return 2 * COMPRESSION + COMPARE


def header_verify_ops() -> int:
    """32-bit operations the spec needs to verify one header of a chain."""
    return 3 * COMPRESSION + COMPARE + LINK
