"""The one traffic generator. A traffic mix is a data file
(``benchmarks/workloads/<cell>.json``, key ``traffic``); this reads its
parameters and makes the cell's inputs from ``--seed``. Nothing here knows a
cell by name.

Offline mixes (``kind: "image_batches"``): a pool of ``pool_batches`` batches
of ``batch`` images x 1 exemplar, which the driver cycles through.

Every seed gets the same set of exemplar sizes, in another order: the sides
are the quantiles of a log-uniform law between ``exemplar_side_px``'s ends,
in ascending order, either cut into the pool's batches (``group: "sorted"``:
a loader that buckets by exemplar size) or dealt out to them one at a time
(``group: "dealt"``: a loader that does not, so every batch spans the whole
range and takes the capacity of its largest). Either way each batch's
template capacity is fixed by the file, not by the draw, and the seed
shuffles the batches, the rows inside a batch, the positions and the pixels. Boxes start on multiples of
``align_px``, so that a side spans the same number of feature cells wherever
it lies.
"""

from __future__ import annotations

import zlib

import numpy as np


def rng_for(seed: int, salt: str) -> np.random.Generator:
    return np.random.default_rng([int(seed), zlib.crc32(salt.encode())])


def exemplar_sides(lo: float, hi: float, n: int) -> np.ndarray:
    """n whole-pixel sides: the (i + 0.5) / n quantiles of log-uniform."""
    q = (np.arange(n) + 0.5) / n
    return np.round(lo * (hi / lo) ** q).astype(np.int64)


def planted_image(rng: np.random.Generator, size: int, side: int,
                  align: int, image: dict):
    """(size, size, 3) float32, roughly normalized: dim noise with one bright
    textured patch of ``side`` pixels pasted at ``copies`` places, and the
    normalized xyxy box of the first (after the program's ``chip_smoke.py:
    planted_image``, with the place and the size drawn)."""
    img = rng.standard_normal((size, size, 3), np.float32)
    img *= np.float32(image["noise_std"])
    patch = rng.standard_normal((side, side, 3), np.float32)
    patch = patch * np.float32(image["patch_std"]) + np.float32(image["patch_mean"])
    slots = (size - side) // align + 1
    box = None
    for _ in range(int(image["copies"])):
        y, x = (int(v) * align for v in rng.integers(0, slots, 2))
        img[y:y + side, x:x + side] = patch
        if box is None:
            box = np.asarray([x, y, x + side, y + side], np.float32) / size
    return img, box


def image_batches(traffic: dict, image_size: int, seed: int, salt: str):
    """Returns ``images`` (P, B, S, S, 3) float32 and ``exemplars``
    (P, B, 1, 4) float32 normalized xyxy."""
    rng = rng_for(seed, salt)
    n_pool, batch = int(traffic["pool_batches"]), int(traffic["batch"])
    lo, hi = traffic["exemplar_side_px"]
    scale = image_size / float(traffic.get("side_px_at", image_size))
    sides = np.maximum(
        np.round(exemplar_sides(lo, hi, n_pool * batch) * scale), 2
    ).astype(np.int64)
    if traffic["group"] == "sorted":
        sides = sides.reshape(n_pool, batch)
    elif traffic["group"] == "dealt":
        sides = sides.reshape(batch, n_pool).T
    else:
        raise ValueError(f"unknown grouping {traffic['group']!r}")
    align = max(int(round(int(traffic["align_px"]) * scale)), 1)
    images = np.empty((n_pool, batch, image_size, image_size, 3), np.float32)
    exemplars = np.empty((n_pool, batch, 1, 4), np.float32)
    for p, group in enumerate(rng.permutation(n_pool)):
        for b, side in enumerate(rng.permutation(sides[group])):
            images[p, b], exemplars[p, b, 0] = planted_image(
                rng, image_size, int(side), align, traffic["image"])
    return images, exemplars


GENERATORS = {"image_batches": image_batches}


def generate(traffic: dict, image_size: int, seed: int, salt: str):
    kind = traffic["kind"]
    if kind not in GENERATORS:
        raise KeyError(f"unknown traffic kind {kind!r}")
    return GENERATORS[kind](traffic, image_size, seed, salt)
