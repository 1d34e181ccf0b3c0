"""A Mantel test's work by its definition, whatever computes it.

Each of the K draws is Pearson's r of the permuted condensed x against
the fixed condensed y: one multiply and one add per pair, so 2·m·K
operations. The least traffic reads the two condensed float32 operands
once and writes the K draws and the observed r.
"""


def ops(n: int, permutations: int) -> float:
    m = n * (n - 1) // 2
    return 2.0 * m * permutations


def bytes_moved(n: int, permutations: int) -> float:
    m = n * (n - 1) // 2
    return 2.0 * m * 4 + 4.0 * (permutations + 1)
