"""Plain connected-components reference: canonical labels (the smallest
slot of each component; -1 for a slot no edge touched).

It imports nothing of the program: scipy's breadth-first connected
components over the edges as an undirected graph, then each component's
smallest slot. The program's own numpy oracle (``cc_pairs_numpy`` /
``cc_labels_numpy`` in ``library/connected_components.py``), which PR 22
first copied here, propagates labels round by round: over the 41,652,230
touched slots of a cell it would run for minutes, longer than the window
(on a CPU at an eighth of the size: 12.6 s against scipy's 1.4 s).
"""

from __future__ import annotations

import numpy as np


def labels(src, dst, n_v: int) -> np.ndarray:
    """i32[n_v] canonical labels of the graph with edges ``src``-``dst``."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    g = coo_matrix((np.ones(src.size, np.int8), (src, dst)),
                   shape=(n_v, n_v)).tocsr()
    k, comp = connected_components(g, directed=False)
    del g
    first = np.full(k, n_v, np.int64)
    np.minimum.at(first, comp, np.arange(n_v))
    lab = first[comp].astype(np.int32)
    touched = np.zeros(n_v, bool)
    touched[src] = True
    touched[dst] = True
    lab[~touched] = -1
    return lab
