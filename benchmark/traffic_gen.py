"""The one generator of inputs: it reads a traffic file's parameters and
makes, from ``--seed``, what a runner sends.  numpy only.

A traffic file names its ``kind`` (which runner drives it) and holds
parameters; a new mix is a new data file, never new code here.

- ``train-fed``: a pool of ``pool_batches`` global batches of ``uint8``
  images and ``int32`` labels, every row different, made before the window
  and cycled in it (no RNG in the window).
- ``serve-closed``: ``clients`` callers; caller *i* sends entries
  *i*, *i* + clients, *i* + 2·clients ... of the file's literal ``requests``
  list of (prompt tokens, output tokens), wrapping at its end.  The lengths
  are the cell's, not the seed's: the seed makes every prompt's token ids
  (and the weights), so the schedule of prefills against decode steps is
  the same in every run.
"""

from __future__ import annotations

import numpy as np


def train_batch(seed: int, index: int, traffic: dict, cfg: dict, chips: int):
    """Global batch ``index`` of the pool: ``(images [B, H, W, 3] uint8,
    labels [B] int32)``; every row is different."""
    batch = int(traffic["batch_per_chip"]) * int(chips)
    size = int(cfg["image_size"])
    rng = np.random.default_rng([int(seed), int(index)])
    images = rng.integers(0, 256, (batch, size, size, 3), dtype=np.uint8)
    labels = rng.integers(0, int(cfg["num_classes"]), (batch,)).astype(np.int32)
    return images, labels


def train_pool(seed: int, traffic: dict, cfg: dict, chips: int) -> list:
    """The pool as the rows the driver feeds: ``pool_batches`` global
    batches of ``(image, label)`` rows, in order."""
    rows = []
    for j in range(int(traffic["pool_batches"])):
        images, labels = train_batch(seed, j, traffic, cfg, chips)
        rows.extend(zip(images, labels))
    return rows


def client_entries(traffic: dict, client: int) -> list[tuple[int, int]]:
    """The (prompt tokens, output tokens) pairs of caller ``client``, in
    the order it sends them; it wraps around when it reaches the end."""
    reqs = traffic["requests"]
    return [tuple(reqs[i]) for i in range(client, len(reqs),
                                          int(traffic["clients"]))]


def prompt_ids(seed: int, client: int, index: int, length: int,
               vocab_size: int) -> np.ndarray:
    """The token ids of caller ``client``'s ``index``-th request."""
    rng = np.random.default_rng([int(seed), int(client), int(index)])
    return rng.integers(0, int(vocab_size), (int(length),)).astype(np.int32)
