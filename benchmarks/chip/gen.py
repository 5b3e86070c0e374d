"""Traffic generation: every input a cell uses, made from ``--seed``.

``cxr_clients`` is the synthetic multi-hospital chest-X-ray task the
program's own examples train on, kept here so that no change to the
program can change what the benchmark feeds it: positives carry bright or
dark nodular blobs on a smooth background, each hospital has its own
scanner shift, train prevalence is 50% and test prevalence 10%.  Every
array is float32 and carries the segmentation ``mask`` the generator
draws beside the image, as the program's data does.
"""

from __future__ import annotations

import numpy as np


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """The generator of one named stream of a run's seed (any size)."""
    return np.random.default_rng([int(seed) % 2**63, *stream])


def _smooth_noise(rng, n, size, sigma):
    low = rng.normal(0, 1, (n, size // 8, size // 8)).astype(np.float32)
    img = np.kron(low, np.ones((8, 8), np.float32))
    img += rng.normal(0, sigma, (n, size, size)).astype(np.float32)
    return img


def _add_blobs(rng, img, mask, intensity, center, n_blobs=(1, 4)):
    n, size, _ = img.shape
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32)
    for i in range(n):
        for _ in range(rng.integers(n_blobs[0], n_blobs[1] + 1)):
            cx = np.clip(rng.normal(center[0], 0.2), 0.1, 0.9) * size
            cy = np.clip(rng.normal(center[1], 0.2), 0.1, 0.9) * size
            r = rng.uniform(size * 0.08, size * 0.18)
            blob = np.exp(-(((xx - cx) ** 2 + (yy - cy) ** 2) / (2 * r * r)))
            img[i] += intensity * blob
            mask[i] |= blob > 0.4
    return img, mask


def _split(rng, n, size, prevalence, shift):
    labels = (rng.uniform(0, 1, n) < prevalence).astype(np.float32)
    img = _smooth_noise(rng, n, size, shift["noise"])
    mask = np.zeros((n, size, size), bool)
    pos = labels > 0.5
    if pos.any():
        img[pos], mask[pos] = _add_blobs(rng, img[pos], mask[pos],
                                         shift["intensity"], shift["center"])
    img = np.tanh(shift["gain"] * img + shift["offset"]).astype(np.float32)
    return {"image": img[..., None], "label": labels,
            "mask": mask[..., None].astype(np.float32)}


def cxr_clients(seed: int, train_per_client, image_size: int,
                test_per_client: int = 0) -> list[dict]:
    """One ``{"train": {...}, "test": {...}}`` per hospital; hospital
    ``c`` holds ``train_per_client[c]`` train images.  Even hospitals see
    bright lesions and odd ones dark, on different backgrounds."""
    rng = seed_rng(seed, 0)
    clients = []
    for c, n_tr in enumerate(train_per_client):
        polarity = 1.0 if c % 2 == 0 else -1.0
        shift = {"noise": rng.uniform(0.08, 0.3),
                 "gain": rng.uniform(0.5, 1.5),
                 "offset": rng.uniform(-0.4, 0.4),
                 "intensity": polarity * rng.uniform(2.0, 3.5),
                 "center": (rng.uniform(0.25, 0.75), rng.uniform(0.25, 0.75))}
        clients.append({
            "train": _split(rng, int(n_tr), image_size, 0.5, shift),
            "test": _split(rng, int(test_per_client), image_size, 0.1,
                           shift)})
    return clients

