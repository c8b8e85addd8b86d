"""TOP-N over a stream: the N largest values, ties to the earlier entry."""
import numpy as np

from bench.streams import BF16


def top_n(values: np.ndarray, n: int) -> np.ndarray:
    """Indices of the n largest values, largest first, ties to the
    lower index."""
    n = min(n, values.shape[0])
    part = np.argpartition(-values, n - 1)[:n]
    cand = np.flatnonzero(values >= values[part].min())
    return cand[np.lexsort((cand, -values[cand]))][:n]


def wrong(params, values, keep) -> int:
    n = params["N"]
    want = top_n(values, n)
    kept = np.flatnonzero(keep)
    got = kept[top_n(values[kept], n)] if kept.size else kept
    m = min(want.size, got.size)
    return int((want[:m] != got[:m]).sum()) + abs(want.size - got.size)


class Control:
    """Forward an entry when it beats the N-th largest value forwarded
    so far, all in bfloat16."""

    def __init__(self, params):
        self.n = params["N"]
        self.top = np.zeros(0, np.float32)

    def keep(self, values):
        v = values.astype(BF16).astype(np.float32)
        thr = self.top[self.n - 1] if self.top.size >= self.n else -np.inf
        keep = v > thr
        self.top = np.sort(np.concatenate([self.top, v[keep]]))[::-1][:self.n]
        return keep
