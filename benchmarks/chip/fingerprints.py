"""Count fingerprints and binding scores from the run's seed, at DOCKSTRING's
shape (1,024-dim count Morgan fingerprints; García-Ortegón et al., J. Chem.
Inf. Model. 2022).

The law is the configuration's: each of the d bits is set with chance
``density``; a set bit's count is 1, 2, ... with chances falling off
geometrically by ``count_decay`` and capped at ``max_count``. Scores follow
the repository's molecule stand-in (``data/pipeline.py:molecule_fingerprints``):
a weighted sum of the overlaps of each molecule's counts with 8 random motifs
(8% of the bits each), plus noise 0.05, clipped at the 95th percentile (the
paper clips docking scores) and standardised. Everything is made with NumPy
on the host from ``seed`` alone, and kept here so that the inputs cannot
change with the system under test.
"""
from __future__ import annotations

import numpy as np

MOTIFS = 8
MOTIF_DENSITY = 0.08


def fingerprints(rng, rows: int, cfg: dict) -> np.ndarray:
    """(rows, d) float32 counts 0..max_count from the configuration's law."""
    d = int(cfg["d"])
    on = rng.random((rows, d), dtype=np.float32) < cfg["density"]
    extra = rng.geometric(1.0 - cfg["count_decay"], size=(rows, d)).astype(np.float32)
    return on * np.minimum(extra, float(cfg["max_count"]))


def screening_data(cfg: dict, seed: int):
    """``(x, y)``: (n, d) float32 training fingerprints and (n,) standardised
    float32 scores."""
    rng = np.random.default_rng([seed, 11])
    n, d = int(cfg["n"]), int(cfg["d"])
    x = fingerprints(rng, n, cfg)
    motifs = (rng.random((MOTIFS, d)) < MOTIF_DENSITY).astype(np.float32)
    weights = rng.normal(size=MOTIFS)
    overlap = (x @ motifs.T) / motifs.sum(axis=1)
    y = overlap @ weights + 0.05 * rng.normal(size=n)
    y = np.minimum(y, np.quantile(y, 0.95))
    y = (y - y.mean()) / (y.std() + 1e-12)
    return x, y.astype(np.float32)
