"""Seeded data for the chip benchmark.

Everything a run feeds the system comes from here, from ``--seed`` alone:
the point table of a configuration.  Only numpy is used, so the same seed
gives the same inputs on any machine.  Every seed gives a table of the
same size, components and separation, so runs on different seeds do the
same amount of work.
"""
from __future__ import annotations

import numpy as np


def mixture(n: int, d: int, seed: int, *, components: int = 8,
            sep: float = 3.0) -> np.ndarray:
    """Seeded K-component Gaussian mixture (d >= K), rows in random order.

    Unit-variance components around mutually orthogonal centres of norm
    sep * sqrt(d): every pair of components is equally far apart in
    euclidean and in cosine geometry.  Labels are drawn per row, so the
    table is not sorted by cluster.
    """
    rng = np.random.default_rng(seed)
    q, _ = np.linalg.qr(rng.normal(size=(d, components)))
    centers = q.T * (sep * np.sqrt(d))
    labels = rng.integers(0, components, size=n)
    X = centers[labels]
    X += rng.standard_normal(size=(n, d))
    return X


def table(config: dict, seed: int) -> np.ndarray:
    """The configuration's point table as float32, (rows, fields)."""
    a = config["assumed"]
    X = mixture(config["rows"], config["fields"], seed,
                components=int(a["components"]), sep=float(a["separation"]))
    if config.get("standardize"):
        X = (X - X.mean(axis=0)) / X.std(axis=0)
    return np.ascontiguousarray(X, np.float32)
