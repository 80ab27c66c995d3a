"""Textbook cost of SHA-256 (FIPS 180-4), counted in 32-bit word operations
from the standard's own equations: an addition mod 2^32, a rotation, a
shift, an AND, an XOR, a NOT is one operation each.

One round (6.2.2 step 3): T1 = h + Sigma1(e) + Ch(e,f,g) + K + W (4
additions), Sigma1 and Sigma0 (4.1.2: three rotations, two XORs each), Ch
(two ANDs, a NOT, an XOR), Maj (three ANDs, two XORs), T2 (an addition),
e = d + T1 and a = T1 + T2 (an addition each).  One schedule word (6.2.2
step 1): sigma0 and sigma1 (two rotations, a shift, two XORs each) and
three additions.  One compression: 64 rounds, 48 schedule words (the
first 16 are the block), and the 8 additions into the hash value.
"""

SIGMA = 3 + 2  # each of Sigma0, Sigma1, sigma0, sigma1
CH = 2 + 1 + 1
MAJ = 3 + 2
ROUND = 4 + SIGMA + CH + 1 + SIGMA + MAJ + 1 + 1
SCHEDULE_WORD = 2 * SIGMA + 3
ROUNDS, SCHEDULE_WORDS, STATE_WORDS = 64, 48, 8
COMPRESSION = ROUNDS * ROUND + SCHEDULE_WORDS * SCHEDULE_WORD + STATE_WORDS

# The chip's peak is counted in 8-bit operations (benchmark/peaks.json,
# int8_ops_per_s): a 32-bit word operation counts as four.
INT8_OPS_PER_WORD_OP = 4
