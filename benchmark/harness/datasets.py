"""The one general generator of training samples: a traffic file lists the
fields of a sample, and sample ``i`` of a run is drawn from
``numpy.random.default_rng([seed, i])``, so the same seed gives the same
batches in any worker and any order. numpy only: DataLoader workers fork
from the process that holds the chip and must never touch jax.

A field is ``{"name", "shape", "dtype", "draw", ...}`` with ``draw`` one of

  uniform_int        integers in [low, high); ``low``/``high`` are numbers
                     or the name of a key of the configuration's sizes
  distinct_positions ``shape[0]`` distinct integers of range(``of``)

``pack`` names the fields concatenated (in order, on the last axis) into
the step's one input array; ``label`` names the field that is its label.
"""
import numpy as np


def bound(value, sizes):
    """A number, or the configuration key that holds it."""
    return int(sizes[value]) if isinstance(value, str) else int(value)


class SeededDataset:
    """Map-style dataset for ``paddle_tpu.io.DataLoader``: returns
    ``(input, label)`` numpy arrays for index ``i``."""

    def __init__(self, traffic, sizes, seed, length):
        self.fields = traffic["fields"]
        self.pack = traffic["pack"]
        self.label = traffic["label"]
        self.sizes = sizes
        self.seed = int(seed)
        self.length = int(length)

    def __len__(self):
        return self.length

    def draw(self, i):
        rng = np.random.default_rng([self.seed, int(i)])
        out = {}
        for f in self.fields:
            shape, dtype = tuple(f["shape"]), np.dtype(f["dtype"])
            if f["draw"] == "uniform_int":
                out[f["name"]] = rng.integers(
                    bound(f["low"], self.sizes), bound(f["high"], self.sizes),
                    size=shape, dtype=dtype)
            elif f["draw"] == "distinct_positions":
                out[f["name"]] = np.sort(rng.choice(
                    bound(f["of"], self.sizes), shape[0],
                    replace=False)).astype(dtype)
            else:
                raise ValueError(f"unknown draw {f['draw']!r}")
        return out

    def __getitem__(self, i):
        fields = self.draw(i)
        parts = [fields[n] for n in self.pack]
        x = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=-1)
        return x, fields[self.label]


def field_shapes(traffic):
    return {f["name"]: tuple(f["shape"]) for f in traffic["fields"]}
