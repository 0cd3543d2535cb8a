# Frozen copy of src/repro/core/pareto.py (commit eba02b4), part of the
# benchmark's plain reference: it imports nothing of the program, so a
# change to the program cannot move the yardstick. Edits from the
# original are marked "bench reference:".
"""Pareto utilities + hypervolume for the 2-objective (maximize throughput,
minimize power) setting. Internally we work in 'maximize both' space by
negating power.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np


def pareto_mask(points: np.ndarray) -> np.ndarray:
    """points (N, 2) in maximize-maximize space -> boolean mask of the front."""
    n = len(points)
    mask = np.ones(n, bool)
    for i in range(n):
        if not mask[i]:
            continue
        dominated = np.all(points >= points[i], axis=1) & np.any(
            points > points[i], axis=1)
        if dominated.any():
            mask[i] = False
            continue
        dominates = np.all(points[i] >= points, axis=1) & np.any(
            points[i] > points, axis=1)
        mask[dominates] = False
        mask[i] = True
    return mask


def pareto_front(points: np.ndarray) -> np.ndarray:
    pts = np.asarray(points, float)
    return pts[pareto_mask(pts)]


