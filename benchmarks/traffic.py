"""One general generator of traffic, driven by a cell's data file.

A cell's `traffic` gives `batch` (rows a step or call), `pool` (seeded
samples held on the host), `slots` (the columns of a sample, in feed
order) and `lengths` (named groups of sequence lengths, [low, high]):

    {"name": "image", "type": "dense",   "dim": 150528}
    {"name": "label", "type": "ids",     "vocab": 1000}
    {"name": "src",   "type": "ids_seq", "vocab": 30000, "min_id": 2,
     "len": "src"}

Every seed gives the same set of sizes in another order: the lengths of a
group are the fixed multiset low + i mod (high - low + 1) over the pool,
dealt to the samples by the seed. Batches walk the pool epoch by epoch,
each epoch a fresh seeded shuffle cut into `pool / batch` batches, so the
rows of a batch all differ and every epoch trains the same rows. The first
row of every batch is an anchor sample of full length in every group, so
the padded shape is one shape.
"""

from __future__ import annotations

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (2 ** 63), stream])


class Pool:
    def __init__(self, traffic: dict, seed: int):
        self.batch = int(traffic["batch"])
        self.size = int(traffic["pool"])
        if self.size % self.batch:
            raise ValueError(f"pool {self.size} is not a whole number of "
                             f"batches of {self.batch}")
        self.slots = list(traffic["slots"])
        self.seed = seed
        self.per_epoch = self.size // self.batch
        rng = _rng(seed, 0)
        self.lengths = {}
        for group, (lo, hi) in sorted(traffic.get("lengths", {}).items()):
            lens = lo + np.arange(self.size) % (hi - lo + 1)
            lens = rng.permutation(lens)
            for j in range(self.per_epoch):        # the anchors: swapped
                if lens[j] != hi:                  # in, so the multiset
                    k = j + 1 + int(np.argmax(lens[j + 1:] == hi))  # stays
                    lens[j], lens[k] = lens[k], lens[j]
            self.lengths[group] = lens.astype(np.int32)
        self.columns = [self._column(s, rng) for s in self.slots]

    def _column(self, slot, rng):
        kind = slot["type"]
        if kind == "dense":
            return rng.standard_normal((self.size, slot["dim"]),
                                       dtype=np.float32)
        lo = slot.get("min_id", 0)
        if kind == "ids":
            return rng.integers(lo, slot["vocab"], self.size).astype(np.int32)
        if kind == "ids_seq":
            lens = self.lengths[slot["len"]]
            flat = rng.integers(lo, slot["vocab"],
                                (self.size, int(lens.max()))).astype(np.int32)
            return [flat[i, :n] for i, n in enumerate(lens)]
        raise ValueError(f"unknown slot type {kind!r}")

    def sample(self, i: int) -> tuple:
        return tuple(col[i] for col in self.columns)

    def count(self, rows, unit: dict) -> int:
        """What a batch of pool rows adds to the rate: its rows, or its
        real (unpadded) steps of one length group."""
        if "length_group" in unit:
            return int(self.lengths[unit["length_group"]][rows].sum())
        return len(rows)

    def batches(self, stream: int):
        """Endless: lists of pool row indices, `batch` long."""
        rng = _rng(self.seed, 1 + stream)
        n = self.per_epoch
        while True:
            rest = n + rng.permutation(self.size - n)
            for j in range(n):
                body = rest[j * (self.batch - 1):(j + 1) * (self.batch - 1)]
                yield np.concatenate([[j], body])

    def arrays(self, rows) -> dict:
        """A batch as plain arrays, for the reference: a dense or ids slot
        under its name, a sequence slot padded to the batch's longest under
        its name with `<name>_lens` beside it."""
        out = {}
        for slot, col in zip(self.slots, self.columns):
            name = slot["name"]
            if slot["type"] == "ids_seq":
                lens = self.lengths[slot["len"]][rows]
                ids = np.zeros((len(rows), int(lens.max())), np.int32)
                for r, i in enumerate(rows):
                    ids[r, : lens[r]] = col[i]
                out[name], out[name + "_lens"] = ids, lens
            else:
                out[name] = col[rows]
        return out
