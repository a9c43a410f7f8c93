"""The salt-layout data set the K-fold cell trains on: 101x101 8-bit PNG
pairs under ``images/`` and ``masks/``, each image noise over a few bright
discs and its mask the discs. (A copy of ``chip_smoke.py:write_dataset``, with
the size as a parameter.) Written once per checkout and reused: the rows are
the same for every ``--seed``, which decides the folds, the order and the
augmentation."""

from __future__ import annotations

import os
from typing import List

import numpy as np

IMAGE_HW = 101


def ids_of(n_images: int) -> List[str]:
    return [f"s{i:05d}" for i in range(n_images)]


def ensure_dataset(data_dir: str, n_images: int, data_seed: int) -> List[str]:
    """Write the set unless a complete one is there; returns the ids."""
    marker = os.path.join(data_dir, f"complete_{n_images}_{data_seed}")
    if os.path.exists(marker):
        return ids_of(n_images)
    from PIL import Image

    rng = np.random.default_rng(data_seed)
    yy, xx = np.mgrid[:IMAGE_HW, :IMAGE_HW]
    for sub in ("images", "masks"):
        os.makedirs(os.path.join(data_dir, sub), exist_ok=True)
    for name in ids_of(n_images):
        mask = np.zeros((IMAGE_HW, IMAGE_HW), bool)
        for _ in range(int(rng.integers(0, 4))):
            cy, cx = rng.uniform(10, IMAGE_HW - 10, 2)
            r = rng.uniform(8, 30)
            mask |= (yy - cy) ** 2 + (xx - cx) ** 2 < r * r
        image = rng.normal(90, 25, mask.shape) + 70 * mask
        Image.fromarray(np.clip(image, 0, 255).astype(np.uint8)).save(
            os.path.join(data_dir, "images", f"{name}.png"), compress_level=1
        )
        Image.fromarray((mask * 255).astype(np.uint8)).save(
            os.path.join(data_dir, "masks", f"{name}.png"), compress_level=1
        )
    with open(marker, "w", encoding="utf-8") as f:
        f.write("ok\n")
    return ids_of(n_images)
