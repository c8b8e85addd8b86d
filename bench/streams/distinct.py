"""DISTINCT over a stream: the set of values seen."""
import numpy as np

from bench.streams import BF16


def wrong(params, values, keep) -> int:
    want = np.unique(values)
    got = np.unique(values[keep])
    return int(np.setxor1d(want, got, assume_unique=True).size)


class Control:
    """Forward the first entry of each value not seen before, with the
    values held in bfloat16."""

    def __init__(self, params):
        self.seen = np.zeros(1 << 16, bool)

    def keep(self, values):
        code = values.astype(BF16).view(np.uint16)
        _, first = np.unique(code, return_index=True)
        keep = np.zeros(code.shape[0], bool)
        keep[first] = ~self.seen[code[first]]
        self.seen[code] = True
        return keep
