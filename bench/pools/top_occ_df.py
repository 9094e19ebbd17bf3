"""Pattern pool of the paper's Sec. 6.1.2: extract random substrings of one
length, drop duplicates and those that cross a document boundary, and keep
the ones with the highest occ/df (occurrences per document that holds
them), scored by the plain reference."""

from __future__ import annotations

import numpy as np


def make(ref, params: dict, rng: np.random.Generator) -> list[np.ndarray]:
    length = int(params["length"])
    cands = set()
    for _ in range(int(params["extract"])):
        p = int(rng.integers(0, max(1, ref.n - length)))
        sub = ref.text[p: p + length]
        if (sub == ref.base - 1).any():        # crosses a separator
            continue
        cands.add(tuple(int(x) for x in sub))
    scored = []
    for c in sorted(cands):
        occ, df = ref.occ(c), ref.count(c)
        scored.append((occ / df, c))
    scored.sort(key=lambda t: -t[0])
    return [np.asarray(c, np.int32) for _, c in scored[: int(params["keep"])]]
