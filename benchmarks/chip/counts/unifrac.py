"""The tree hoist's least traffic, by its definition, whatever computes it.

The hoist turns an (n, T) float32 table into the (n, B) float32 branch
embedding: it has to read the table once and write the embedding once,
4·n·T + 4·n·B bytes. Its operations (a compare and a count per entry)
are far below the chip's peak, so bytes bound it.
"""


def hoist_bytes(n: int, tips: int, branches: int) -> float:
    return 4.0 * n * tips + 4.0 * n * branches
