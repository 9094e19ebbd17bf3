"""Document collections of the paper's Sec. 6.1.1 synthetic families.

A copy of the repository's generator (``repro.data.collections.generate``),
kept with the benchmark so that the data every cell runs on cannot change
when the program does.  Documents come back as raw symbol arrays in
``[0, len(alphabet))``.

* ``version``: every variant is its own document (a versioned store);
* ``concat``: all variants of one base are one document (page-level
  revision concatenation);
* ``dna``: like ``version``, the collection of one genome's variants.

Base documents are mutations (rate 10p) of one seed sequence; each base has
``n_variants`` variants at rate p.
"""

from __future__ import annotations

import numpy as np


def alphabet_size(config: dict) -> int:
    return len(config["alphabet"])


def _mutate(rng, doc: np.ndarray, rate: float, alphabet_size: int) -> np.ndarray:
    out = doc.copy()
    mask = rng.random(len(doc)) < rate
    out[mask] = rng.integers(0, alphabet_size, mask.sum())
    return out


def generate(config: dict, rng: np.random.Generator) -> list[np.ndarray]:
    """The documents of ``config`` (keys ``family``, ``n_base``,
    ``n_variants``, ``base_len``, ``mutation_rate``, ``alphabet``)."""
    sigma = alphabet_size(config)
    rate = config["mutation_rate"]
    seed_seq = rng.integers(0, sigma, config["base_len"])
    bases = [_mutate(rng, seed_seq, 10 * rate, sigma)
             for _ in range(config["n_base"])]
    variants = [[_mutate(rng, base, rate, sigma)
                 for _ in range(config["n_variants"])] for base in bases]
    if config["family"] == "concat":
        return [np.concatenate(vs) for vs in variants]
    return [v for vs in variants for v in vs]
