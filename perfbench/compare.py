"""The comparison that decides ``correct`` for a training cell.

What the timed path produced over the window's first steps — each step's
loss, the first gradient as the optimizer got it, the parameters' change
after the followed steps, and what the input program made of the rows it was
fed — against the plain reference run from the same weights and the same
feeds. Every number has its own limit (``limits/<cell>.json``); ``correct``
is all of them holding.

Norms are compared by the worst leaf: the gap between the program's norm and
the reference's (not the norm of their difference), against the reference's
norm of that leaf or of the median leaf, whichever is larger. Leaves whose
reference gradient is under a thousandth of the median leaf's are left out of
the change: under Adam they move by round-off alone. Beside the worst leaf the
median leaf's gap and the gap of the whole gradient's norm are read: they are
steadier where one small leaf is noise (PERF.md says which are compared).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

# a leaf whose first reference gradient is below this share of the median
# leaf's does not count in the parameters' change
DEAD_GRADIENT_SHARE = 1e-3


def _norms(tree: Dict[str, Any]) -> Dict[str, float]:
    return {k: float(np.linalg.norm(np.asarray(v, np.float64))) for k, v in tree.items()}


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              leaves=None) -> Dict[str, float]:
    """Per leaf: |‖program‖ − ‖reference‖| / max(‖reference‖, the median leaf's)."""
    leaves = sorted(reference) if leaves is None else sorted(leaves)
    median = float(np.median([reference[k] for k in leaves])) or 1e-30
    return {k: abs(program[k] - reference[k]) / max(reference[k], median) for k in leaves}


def worst_leaf_gap(program: Dict[str, float], reference: Dict[str, float],
                   leaves=None) -> Tuple[float, str]:
    gaps = leaf_gaps(program, reference, leaves)
    where = max(gaps, key=lambda k: gaps[k] if np.isfinite(gaps[k]) else float("inf"))
    return (gaps[where] if np.isfinite(gaps[where]) else float("inf")), where


def training_numbers(
    observed: Dict[str, Any],
    reference: Dict[str, Any],
    params0: Dict[str, Any],
    where: Optional[Dict[str, str]] = None,
    head=(),
) -> Dict[str, float]:
    """``observed``/``reference``: each step's loss (``losses``), the first
    gradient as the optimizer got it (``grad1``, worked out from its state
    after one update) and the parameters after the followed steps
    (``params``). ``head`` names the output layer's leaves: their first
    gradient has no backward chain behind it, so it is the one part of the
    gradient that rounding does not scramble (PERF.md, PR 23), and is compared
    element by element (``grad1_head_diff``)."""
    numbers: Dict[str, float] = {}
    where = {} if where is None else where  # the worst leaf of each norm, for the log
    for i, (lp, lr) in enumerate(zip(observed["losses"], reference["losses"])):
        numbers[f"loss{i + 1}_gap"] = abs(lp - lr) / max(abs(lr), 1e-30)
    if "stats1" in observed and reference.get("stats1"):
        # the root BatchNorms' batch statistics of the first step, worst layer:
        # variances against the reference's, means in units of its spread
        var_gap = mean_gap = 0.0
        for name, want in reference["stats1"].items():
            got = np.asarray(observed["stats1"][name], np.float64)
            want = np.asarray(want, np.float64)
            if name.endswith("/var"):
                var_gap = max(var_gap, float(np.linalg.norm(got - want) / np.linalg.norm(want)))
            else:
                spread = np.sqrt(np.asarray(reference["stats1"][name[: -len("mean")] + "var"],
                                            np.float64))
                mean_gap = max(mean_gap, float(np.linalg.norm(got - want) / np.linalg.norm(spread)))
        numbers["root_norm_var_gap"], numbers["root_norm_mean_gap"] = var_gap, mean_gap
    g_prog = _norms(observed["grad1"])
    g_ref = _norms(reference["grad1"])
    numbers["grad1_gap"], where["grad1_gap"] = worst_leaf_gap(g_prog, g_ref)
    numbers["grad1_median_gap"] = float(np.median(list(leaf_gaps(g_prog, g_ref).values())))
    total_p = float(np.sqrt(sum(v * v for v in g_prog.values())))
    total_r = float(np.sqrt(sum(v * v for v in g_ref.values())))
    numbers["grad1_total_gap"] = abs(total_p - total_r) / max(total_r, 1e-30)
    if head:
        diff = sum(
            float(np.sum(np.square(np.asarray(observed["grad1"][k], np.float64)
                                   - np.asarray(reference["grad1"][k], np.float64))))
            for k in head
        )
        numbers["grad1_head_diff"] = float(np.sqrt(diff)) / max(
            float(np.sqrt(sum(g_ref[k] ** 2 for k in head))), 1e-30
        )
    median_g = float(np.median(list(g_ref.values())))
    alive = [k for k, v in g_ref.items() if v >= DEAD_GRADIENT_SHARE * median_g]
    p0 = {k: np.asarray(v, np.float64) for k, v in params0.items()}
    d_prog = _norms({k: np.asarray(observed["params"][k], np.float64) - p0[k] for k in p0})
    d_ref = _norms({k: np.asarray(reference["params"][k], np.float64) - p0[k] for k in p0})
    numbers["delta_gap"], where["delta_gap"] = worst_leaf_gap(d_prog, d_ref, alive)
    numbers["delta_median_gap"] = float(np.median(list(leaf_gaps(d_prog, d_ref, alive).values())))
    return numbers


def _block_means(x: np.ndarray, block: int = 8) -> np.ndarray:
    """[B, H, W] -> means over block x block tiles (the ragged edge is cut)."""
    b, h, w = x.shape
    h, w = h - h % block, w - w % block
    return x[:, :h, :w].reshape(b, h // block, block, w // block, block).mean(axis=(2, 4))


def seg_prepare_numbers(fed, expected, laplace) -> Dict[str, float]:
    """The segmentation input program's output against the reference's, step
    by step; the widest of the followed steps counts.

    - ``prep_mask_gap``: the share of label pixels that differ;
    - ``prep_image_gap``: the image channel, as means over 8x8 tiles: mean
      absolute gap in units of the reference's spread. Tiles, because the
      rows are pixel noise: a warp a fraction of a pixel off changes every
      single pixel and hardly any tile;
    - ``prep_laplace_gap``: the second channel against ``laplace`` (the
      reference's Laplacian) of the program's own first channel."""
    image_gap = mask_gap = laplace_gap = 0.0
    for got, want in zip(fed, expected):
        gi = np.asarray(got["images"], np.float64)
        wi = np.asarray(want["images"], np.float64)
        tiles_got, tiles_want = _block_means(gi[..., 0]), _block_means(wi[..., 0])
        spread = float(np.std(tiles_want)) or 1e-30
        image_gap = max(image_gap, float(np.mean(np.abs(tiles_got - tiles_want))) / spread)
        mask_gap = max(
            mask_gap, float(np.mean(np.asarray(got["labels"]) != np.asarray(want["labels"])))
        )
        lap = np.asarray(laplace(np.asarray(got["images"])[..., :1]), np.float64)[..., 1]
        spread = float(np.std(lap)) or 1e-30
        laplace_gap = max(laplace_gap, float(np.mean(np.abs(gi[..., 1] - lap))) / spread)
    return {"prep_image_gap": image_gap, "prep_mask_gap": mask_gap,
            "prep_laplace_gap": laplace_gap}


def flip_crop_numbers(matches, first_index) -> Dict[str, float]:
    """The classification input program: rows that are no mirror-and-crop of
    the row they were made from (exact, so the limit is 0), and the share of
    rows that took the commonest (mirror, y, x) — near 1 where nothing is
    drawn per row."""
    matches = np.concatenate([np.asarray(m) for m in matches])
    first = np.concatenate([np.asarray(i) for i in first_index])
    unmatched = float(np.sum(matches == 0))
    counts = np.bincount(first[matches > 0]) if np.any(matches > 0) else np.zeros(1)
    return {
        "prep_rows_unmatched": unmatched,
        "prep_modal_offset_share": float(counts.max()) / max(len(first), 1),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]):
    """(checks, correct): each number the cell's limits file names, beside its
    limit. The file decides what is compared (PERF.md gives the readings each
    limit was set from, and why a number that is read is not compared); a
    limit for a number nobody reads is an error of the benchmark."""
    checks = {}
    for name, limit in limits.items():
        if name not in numbers:
            raise KeyError(f"the cell's limits file names {name!r}, which this run did not read")
        checks[name] = (float(numbers[name]), float(limit))
    correct = all(np.isfinite(v) and v <= lim for v, lim in checks.values())
    return checks, bool(correct)
