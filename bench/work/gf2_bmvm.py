"""Least bytes a Williams GF(2) BMVM call must move, computed from shapes.

The LUT of an n x n matrix cut into k-bit tiles is (C, 2^k, R) uint32 words,
C = R = n/k (``repro.kernels.ref.gf2_preprocess``).  A call on M vectors
looks up, for every vector m and column tile c, the row ``v[m, c]`` of
LUT[c] and XORs its R words into output row m.  Whatever implements it has
to read each LUT row that some vector selects at least once, the M x C index
words, and write the M x R output words.  On uniform vectors the number of
distinct rows that M draws select out of P = 2^k is P (1 - (1 - 1/P)^M), in
expectation: all 2^k rows at M >> 2^k, one row at M = 1.  At M = 1024, C = 512
the realized count lies within 0.04% (one standard deviation) of it.

Split over ``chips`` that each own C/chips column tiles, every chip XORs a
partial (M, R) block and keeps 1/chips of its words: the rest, (chips - 1)
M R / chips words a chip, has to cross the interconnect.
"""
from __future__ import annotations

WORD = 4   # bytes in a uint32 LUT / index / output word


def lut_shape(n: int, k: int) -> tuple[int, int, int]:
    return n // k, 2 ** k, n // k


def lut_bytes(n: int, k: int) -> int:
    c, p, r = lut_shape(n, k)
    return c * p * r * WORD


def rows_selected(p: int, m: int) -> float:
    """Expected number of distinct rows out of ``p`` that ``m`` uniform draws hit."""
    return p * (1.0 - (1.0 - 1.0 / p) ** m)


def min_bytes(n: int, k: int, m: int) -> float:
    """Least HBM bytes of one product of M vectors: selected LUT rows, index
    words in, output words out."""
    c, p, r = lut_shape(n, k)
    return c * rows_selected(p, m) * r * WORD + m * c * WORD + m * r * WORD


def all_to_all_bytes(n: int, k: int, m: int, chips: int) -> int:
    """Bytes that leave their chip in one product, summed over the chips."""
    _, _, r = lut_shape(n, k)
    return (chips - 1) * m * r * WORD


def min_step_seconds(n: int, k: int, m: int, chips: int, peaks: dict) -> float:
    """Least time of one product on ``chips`` chips: the larger of the HBM
    term and the interconnect term, each spread evenly over the chips."""
    hbm = min_bytes(n, k, m) / (chips * peaks["hbm_bytes_per_s"])
    ici = all_to_all_bytes(n, k, m, chips) / (chips * peaks["ici_bytes_per_s"])
    return max(hbm, ici)
