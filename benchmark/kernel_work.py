"""The 32-bit integer multiply-adds ONE ed25519 verification needs,
counted from the algorithm's structure and not from whatever implements
it, so that a later kernel is read against the same work.

Verification (RFC 8032 5.1.7, cofactorless as the pool checks it):
decompress A, compute [s]B - [h]A, compress, compare with R.

  - field elements are 255-bit; the limb count is the one the kernel's
    own radix gives (ops/ed25519_pallas.py: radix 2^13, NLIMB = 20 —
    the widest limbs whose 20-term column sums stay inside int32)
  - a field multiplication is a schoolbook limb convolution: NLIMB^2
    products; a squaring needs NLIMB*(NLIMB+1)/2
  - decompression: one square root by the (p-5)/8 power chain: 252
    squarings and 11 multiplications, plus 8 to form u, v, v^3, v^7 and
    to check the root
  - double-scalar multiplication with 4-bit fixed windows (the least
    known for a batch that cannot branch per signature): 252 doublings
    (4 squarings + 4 multiplications each, dbl-2008-hwcd), 64 additions
    of the base-point table (precomputed niels form, 7 mult.), 64 of the
    table of A (8 mult.), 14 additions to build A's table (8 mult.)
  - compression: one inversion, 254 squarings and 11 multiplications,
    and 2 multiplications

Additions, carries, table look-ups and SHA-512 are left out: the count
is a floor, so a roofline share read against it is a ceiling on how
well the multiplier is used.
"""
NLIMB = 20

DOUBLINGS = 252
WINDOWS = 64
TABLE_BUILD_ADDS = 14


def field_mults() -> dict:
    mult = (8 + 11                      # decompression
            + DOUBLINGS * 4             # doublings
            + WINDOWS * 7 + WINDOWS * 8 + TABLE_BUILD_ADDS * 8
            + 11 + 2)                   # compression
    square = 252 + DOUBLINGS * 4 + 254
    return {"mult": mult, "square": square}


def ed25519_verify_madds(nlimb: int = NLIMB) -> int:
    fm = field_mults()
    return fm["mult"] * nlimb * nlimb \
        + fm["square"] * (nlimb * (nlimb + 1) // 2)
