"""Textbook cost of P-256 arithmetic, shared by the ECDSA kernels' work
functions.  It is the cost of the algorithm as a textbook states it, not of
any lowering: a kernel that finds a cheaper way reads a higher share.

A field element is 256 bits, 32 limbs of 8 bits (the width of the chip's
int8 peak).  One modular multiplication is the schoolbook product (32 x 32
8-bit multiply-accumulates) and a Montgomery reduction of the same size; a
multiply-accumulate counts as two operations, as the chip's peak counts it.
"""

LIMBS = 32
MACS_PER_FIELD_MUL = 2 * LIMBS * LIMBS  # product + Montgomery reduction
OPS_PER_FIELD_MUL = 2 * MACS_PER_FIELD_MUL

BITS = 256
DOUBLE = 8  # Jacobian doubling, a = -3: 4M + 4S
MIXED_ADD = 11  # Jacobian + affine: 8M + 3S
FERMAT_INVERSE = (BITS - 1) + BITS // 2  # square-and-multiply, x^(p-2)
