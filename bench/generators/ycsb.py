"""The YCSB core-workload generator, for mix files with
``"generator": "ycsb"``::

    {"generator": "ycsb", "request_distribution": "scrambled_zipfian",
     "zipfian_constant": 0.99, "read_proportion": 0.5,
     "update_proportion": 0.5, "loop": "closed"}

:class:`Traffic` is an ``EpochDriver`` scenario:

* the record set: ``n_records`` distinct 32-bit keys drawn from the seed,
  sorted, with float32 values of ``value_dim`` words drawn from the seed;
* each epoch: ``epoch_ops`` operations; each picks a zipfian rank by the
  inverse CDF (computed once, at construction), scrambles it to a record
  with YCSB's FNV-1a 64 hash (``ScrambledZipfianGenerator``), and is a
  GET with probability ``read_proportion``, else an update (a PUT of an
  existing record with a fresh value drawn from the seed).

Every draw comes from ``numpy.random.default_rng((seed, stream, epoch))``,
so the same seed gives the same load and the same op stream, and any
epoch can be regenerated alone (the reference replays the stream that
way).  Every seed gives the same sizes: only which keys and values.
"""

from __future__ import annotations

import numpy as np

from workload import MAX_RECORD_KEY, OP_GET, OP_PUT, TrafficShape

FNV_OFFSET_BASIS_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(1099511628211)

_SUPPORTED = {"read_proportion", "update_proportion"}
_STREAM_KEYS, _STREAM_VALUES, _STREAM_OPS = 0, 1, 2


def fnvhash64(x: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` over int64 values: FNV-1a over the eight
    little-endian octets, then the absolute value of the signed result."""
    val = np.asarray(x, np.uint64).copy()
    h = np.full(val.shape, FNV_OFFSET_BASIS_64, np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= val & np.uint64(0xFF)
            val >>= np.uint64(8)
            h *= FNV_PRIME_64
    return np.abs(h.view(np.int64)).astype(np.uint64)


def zipfian_cdf(n: int, theta: float) -> np.ndarray:
    """Cumulative probabilities of ranks 0..n-1 under P(r) ~ (r+1)^-theta."""
    p = np.arange(1, n + 1, dtype=np.float64) ** -theta
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def validate(mix: dict, path) -> None:
    """Refuse a mix that asks for what this generator does not issue."""
    if mix.get("request_distribution") != "scrambled_zipfian":
        raise ValueError(f"{path}: only scrambled_zipfian is generated")
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: only closed-loop traffic is generated")
    props = {k: float(v) for k, v in mix.items() if k.endswith("_proportion")}
    extra = {k for k, v in props.items() if v > 0} - _SUPPORTED
    if extra:
        raise ValueError(f"{path}: the driver issues no {sorted(extra)}")
    if abs(sum(props.values()) - 1.0) > 1e-9:
        raise ValueError(f"{path}: proportions sum to {sum(props.values())}")


class Traffic:
    """A YCSB mix over a seeded record set, as an ``EpochDriver`` scenario."""

    def __init__(self, mix: dict, *, n_records: int, value_dim: int,
                 epoch_ops: int, n_epochs: int, seed: int):
        self.name = f"ycsb:{mix.get('name', 'mix')}"
        self.cfg = TrafficShape(n_epochs=n_epochs, epoch_ops=epoch_ops,
                                n_records=n_records, value_dim=value_dim,
                                seed=seed)
        self.read_proportion = float(mix["read_proportion"])
        self.seed = int(seed)
        self.record_keys = self._record_keys()
        self.cdf = zipfian_cdf(n_records, float(mix["zipfian_constant"]))
        # rank -> record index, fixed (YCSB hashes the rank, not the seed)
        self.scramble = (fnvhash64(np.arange(n_records, dtype=np.uint64))
                         % np.uint64(n_records)).astype(np.int64)

    def _rng(self, stream: int, epoch: int = 0) -> np.random.Generator:
        return np.random.default_rng((self.seed, stream, epoch))

    def _record_keys(self) -> np.ndarray:
        n = self.cfg.n_records
        rng = self._rng(_STREAM_KEYS)
        keys = np.empty(0, np.uint64)
        while keys.size < n:
            more = rng.integers(0, MAX_RECORD_KEY, size=n + n // 64 + 64,
                                dtype=np.uint64)
            keys = np.unique(np.concatenate([keys, more]))
        keys = keys[np.sort(rng.choice(keys.size, size=n, replace=False))]
        return keys.astype(np.uint32)

    def events(self, epoch: int) -> list:
        return []

    def load(self):
        """(keys, values) PUT before epoch 0 (the YCSB load phase)."""
        rng = self._rng(_STREAM_VALUES)
        vals = rng.standard_normal((self.cfg.n_records, self.cfg.value_dim),
                                   dtype=np.float32)
        return self.record_keys, vals

    def _draw(self, epoch: int):
        rng = self._rng(_STREAM_OPS, epoch)
        r = np.searchsorted(self.cdf, rng.random(self.cfg.epoch_ops),
                            side="right")
        return rng, np.minimum(r, self.cfg.n_records - 1)

    def ranks(self, epoch: int) -> np.ndarray:
        """The epoch's zipfian ranks (0 = hottest), before scrambling."""
        return self._draw(epoch)[1]

    def epoch(self, e: int):
        """One epoch's ops: (opcodes, keys, end_keys, values)."""
        B, V = self.cfg.epoch_ops, self.cfg.value_dim
        rng, r = self._draw(e)
        keys = self.record_keys[self.scramble[r]]
        opcodes = np.where(rng.random(B) < self.read_proportion, OP_GET,
                           OP_PUT).astype(np.int32)
        values = rng.standard_normal((B, V), dtype=np.float32)
        return opcodes, keys, np.zeros(B, np.uint32), values
