"""Comparison helpers shared by the configurations' output checks.

Written for the benchmark, independent of the program: the scaled error
measure, the choice of checked lanes and the test that a compiled program
holds a TPU (Mosaic) kernel.
"""
from __future__ import annotations

import numpy as np

# One checked trajectory per block of this many lanes: every block of the
# lane axis (and so every kernel tile and, sharded, every device) is read.
SAMPLE_BLOCK = 4096
# Small ensembles are checked whole up to this many trajectories.
SAMPLE_MIN = 256


def scaled_err(got, ref) -> float:
    """max |got - ref| / (1 + |ref|): absolute near 0, relative for large
    states.  NaN anywhere reads as infinitely wrong."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    err = np.abs(got - ref) / (1.0 + np.abs(ref))
    return float(np.inf) if not np.all(np.isfinite(err)) else float(err.max())


def rel_err(got, ref) -> float:
    """max |got - ref| / |ref| over states bounded away from 0."""
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    err = np.abs(got - ref) / np.abs(ref)
    return float(np.inf) if not np.all(np.isfinite(err)) else float(err.max())


def sample_lanes(n: int, seed: int) -> np.ndarray:
    """Trajectory indices to check, drawn from the seed: one uniformly
    chosen lane in every block of `SAMPLE_BLOCK`, or every lane of a small
    ensemble.  Sorted, unique."""
    if n <= SAMPLE_MIN:
        return np.arange(n)
    rng = np.random.default_rng([seed, 0x5A3])
    blocks = max(SAMPLE_MIN, n // SAMPLE_BLOCK)
    edges = np.linspace(0, n, blocks + 1).astype(np.int64)
    width = np.diff(edges)
    return np.unique(edges[:-1] + rng.integers(0, width))


def holds_mosaic_kernel(compiled_text: str) -> bool:
    """Whether a compiled program's HLO holds a TPU kernel custom call."""
    return "tpu_custom_call" in compiled_text
