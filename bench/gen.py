"""Traffic generators: one per traffic kind, driven by a traffic file.

``tokens`` copies the repository's synthetic training data
(``repro.data.synthetic.make_batch``): Zipf(1.3) ids shifted by a
per-row offset, so that loss falls as in a real corpus.  Every row of
every step differs; the same seed gives the same batches.
"""
from __future__ import annotations

import numpy as np

from bench.seeds import seed_words


def tokens(traffic: dict, vocab: int, seed: int, step: int) -> dict:
    b, s = traffic["global_batch"], traffic["seq"]
    rng = np.random.default_rng((*seed_words(seed), step))
    base = rng.zipf(traffic.get("zipf_a", 1.3), size=(b, s)).astype(np.int64)
    offs = rng.integers(0, 97, size=(b, 1))
    toks = ((base + offs) % vocab).astype(np.int32)
    return {"tokens": toks, "labels": toks.copy()}
