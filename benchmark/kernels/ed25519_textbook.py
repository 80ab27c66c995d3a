"""Textbook cost of edwards25519 arithmetic, shared by the Ed25519 kernels'
work functions, as ``p256_textbook.py`` is by the ECDSA ones: the cost of
the algorithm as a textbook states it, not of any lowering.

A field element (mod 2^255 - 19) is counted as ``p256_textbook`` counts
one: 32 limbs of 8 bits, one modular multiplication a schoolbook product
and a reduction of the same size (``p256_textbook.OPS_PER_FIELD_MUL``).
Points are in extended coordinates (Hisil-Wong-Carter-Dawson 2008, a = -1).
"""

SCALAR_BITS = 253  # the group order L is just above 2^252: S, k and r are below it
FIELD_BITS = 255
DOUBLE = 8  # dbl-2008-hwcd: 4M + 4S
ADD = 8  # add-2008-hwcd-3, the unified addition: 8M
FERMAT_INVERSE = (FIELD_BITS - 1) + FIELD_BITS // 2  # square-and-multiply, x^(p-2)
