"""Operations and bytes that the work itself needs, from its shapes: named for the work.

None of these knows which kernel, fusion or padding implements the work. Rows
are the rows the requests asked for, not the rows a block was padded to.
"""

import re


def ff_layer1_flops(rows: int, features: int, hidden: int) -> float:
    """The first layer's product, hidden x features by features x rows."""
    return 2.0 * rows * features * hidden


def ff_layer1_bytes(rows: int, features: int, hidden: int, requests: int = 1,
                    itemsize: int = 4) -> float:
    """Over ``requests`` requests of ``rows`` rows in all: w1 read once a request, the input
    rows read once, the hidden activations written once."""
    return float(itemsize) * (requests * hidden * features + rows * features + hidden * rows)


def ff_score_flops(rows: int, features: int, hidden: int, labels: int) -> float:
    """Both products of a score; bias, relu and softmax are not counted."""
    return 2.0 * rows * (features * hidden + hidden * labels)


_ARRAY = re.compile(r"[a-z]+[0-9]*\[([0-9,]+)\]")


def touches_features(hlo_text: str, features: int) -> bool:
    """Whether an HLO instruction reads or writes a feature-wide array: one with a dimension
    of at least ``features``, or, where the program keeps it in blocks, with at least
    ``features`` x 64 elements (only w1 and a batch of feature rows are that large)."""
    for dims in _ARRAY.findall(hlo_text):
        sizes = [int(d) for d in dims.split(",") if d]
        n = 1
        for d in sizes:
            n *= d
        if sizes and (max(sizes) >= features or n >= features * 64):
            return True
    return False


def fold_bytes(rows: int, columns: int, itemsize: int = 4) -> float:
    """A fold reads each of its columns once."""
    return float(itemsize) * rows * columns


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of compute and memory time."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
