"""The collection and the request stream, both made from seeds.

The collection is a copy of ``repro.data.pipeline.vector_dataset``'s
generator (one attribute, no attribute-vector correlation), kept here so
that no change to the program can move the data a cell measures. It draws,
in this order from ``data_seed``: the cluster centres, each object's
cluster, its vector (centre + unit Gaussian noise) and its attribute.

Requests come from ``--seed``. Each one has a query vector (a cluster
centre of the collection plus unit Gaussian noise, as the paper's held-out
queries come from the collection's own distribution), an inclusive range of
attribute ranks and k. The range widths are stratified: every consecutive
block of ``len(classes)`` requests holds each width class once, in an order
drawn from the seed, so every seed sends the same mix of work. A range
starts anywhere it fits, uniformly.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ATTR_KINDS = ("uniform", "clustered")


def centres(cfg: dict) -> np.ndarray:
    """The collection's cluster centres: the generator's first draw."""
    rng = np.random.default_rng(cfg["data_seed"])
    return rng.standard_normal(
        (cfg["n_clusters"], cfg["dim"])).astype(np.float32) * 2.0


def collection(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    """(vectors f32[n, dim], attribute f64[n]) in original-id order."""
    n, dim, nc = cfg["n"], cfg["dim"], cfg["n_clusters"]
    if cfg["attribute"] not in ATTR_KINDS:
        raise ValueError(f"attribute {cfg['attribute']!r} not in "
                         f"{ATTR_KINDS}")
    rng = np.random.default_rng(cfg["data_seed"])
    c = rng.standard_normal((nc, dim)).astype(np.float32) * 2.0
    assign = rng.integers(0, nc, n)
    vectors = c[assign] + rng.standard_normal((n, dim)).astype(np.float32)
    if cfg["attribute"] == "uniform":
        attr = rng.uniform(0, 1e6, n)
    else:   # the attribute follows the vector's cluster
        attr = assign * 1000 + rng.uniform(0, 1000, n)
    return vectors, attr.astype(np.float64)


@dataclasses.dataclass
class Requests:
    """A request stream, in send order. ``L``/``R`` are inclusive ranks of
    the attribute; ``lo``/``hi`` the attribute values at those ranks, which
    is what a request carries."""
    vectors: np.ndarray     # f32[N, dim]
    L: np.ndarray           # int64[N]
    R: np.ndarray           # int64[N]
    lo: np.ndarray          # f64[N]
    hi: np.ndarray          # f64[N]
    k: int

    def __len__(self):
        return len(self.L)


def span_classes(ranges: dict, n: int) -> np.ndarray:
    """Objects per range for each width class of a mix: n * 2^-i for each i
    of ``log2_fractions``, at least 8."""
    return np.array([max(n >> int(i), 8) for i in ranges["log2_fractions"]],
                    np.int64)


def requests(cfg: dict, traffic: dict, seed: int, count: int,
             sorted_attr: np.ndarray, c: np.ndarray) -> Requests:
    """``count`` requests of the mix ``traffic`` over the collection of
    ``cfg`` (``sorted_attr``: its attribute values in rank order; ``c``:
    its cluster centres)."""
    n = cfg["n"]
    rng = np.random.default_rng([seed, 1])
    classes = span_classes(traffic["ranges"], n)
    nb = -(-count // len(classes))
    order = np.argsort(rng.random((nb, len(classes))), axis=1).reshape(-1)
    spans = classes[order[:count]]
    L = (rng.random(count) * (n - spans + 1)).astype(np.int64)
    R = L + spans - 1
    cl = rng.integers(0, len(c), count)
    noise = float(traffic["queries"]["noise"])
    vectors = c[cl] + np.float32(noise) * rng.standard_normal(
        (count, c.shape[1]), dtype=np.float32)
    return Requests(vectors, L, R, sorted_attr[L], sorted_attr[R],
                    int(traffic["k"]))
