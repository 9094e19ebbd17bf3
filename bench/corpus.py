"""Raw symbols and the program's symbol space.

A configuration's collection comes from its corpus plug-in
(``corpora/<corpus>.py``) as raw symbol arrays in ``[0, alphabet_size)``.
``repro.core.suffix.concat_documents`` shifts every document by one when
the program indexes it (0 is its document terminator), whatever the corpus,
and ``served_pattern`` applies the same shift to a query.
"""

from __future__ import annotations

import numpy as np


def served_pattern(raw: np.ndarray) -> np.ndarray:
    """A raw pattern in the program's symbol space (``concat_documents``
    shifts integer documents by one)."""
    return np.asarray(raw, np.int32) + 1
