"""Plain degree reference: each slot's count of edge endpoints.

It imports nothing of the program: two numpy bincounts over the edges,
so a self-loop counts 2 at its vertex and a slot no edge touched reads
0. Under the configuration ``twitter2010-degrees`` the check named
``label_mismatches`` counts the slots whose degree differs from these.
"""

from __future__ import annotations

import numpy as np


def labels(src, dst, n_v: int) -> np.ndarray:
    """i64[n_v] total degrees (out plus in) of the edges ``src``-``dst``."""
    return (np.bincount(np.asarray(src), minlength=n_v)
            + np.bincount(np.asarray(dst), minlength=n_v)).astype(np.int64)
