"""The plain reference and the comparison that decides ``correct``.

:class:`DictStore` is a dict from key to value row with the store's
batch semantics; :func:`replay` runs the load phase and an op stream
through it.  :func:`check_slabs` holds the store that the timed path
left behind to the reference on every copy of every record, and
:func:`check_gets` holds GET replies read from that store to it.
Both compare bit for bit: an exact comparison, every limit 0.

Nothing here imports the program: the slab arrays and GET replies come
in as numpy arrays, and the opcodes are the query interface's own.
"""

from __future__ import annotations

from collections import Counter

import numpy as np

from workload import OP_GET, OP_PUT

EMPTY_KEY = np.uint32(0xFFFFFFFF)


class DictStore:
    """Plain reference store with the store's batch semantics for the ops
    the traffic issues: GETs see the state before the batch, then the
    PUTs apply, the last PUT of a key in batch order winning."""

    def __init__(self, keys: np.ndarray, values: np.ndarray):
        self.d = dict(zip(keys.tolist(), values))
        self.writes: Counter = Counter()
        self.accesses: Counter = Counter()

    def get(self, keys, value_dim: int):
        """(values (B, V), found (B,)) of ``keys`` in the current state;
        zeros where not found."""
        out = np.zeros((len(keys), value_dim), np.float32)
        found = np.zeros((len(keys),), bool)
        for i, k in enumerate(keys.tolist()):
            if k in self.d:
                found[i] = True
                out[i] = self.d[k]
        return out, found

    def apply(self, opcodes, keys, values):
        """Apply one batch; returns (values (B, V), found (B,)) of its
        GETs against the pre-batch state."""
        out, found = self.get(keys, values.shape[1])
        is_get = opcodes == OP_GET
        out[~is_get] = 0.0
        found &= is_get
        klist = keys.tolist()
        self.accesses.update(klist)
        for i, (op, k) in enumerate(zip(opcodes.tolist(), klist)):
            if op == OP_PUT:
                self.d[k] = values[i]
                self.writes[k] += 1
        return out, found


def replay(traffic, n_epochs: int) -> DictStore:
    """The reference after the load phase and epochs ``0..n_epochs-1`` of
    the traffic's op stream (regenerated from its seed)."""
    ref = DictStore(*traffic.load())
    for e in range(n_epochs):
        opcodes, keys, _end, values = traffic.epoch(e)
        ref.apply(opcodes, keys, values)
    return ref


def pick_sample(ref: DictStore, record_keys: np.ndarray, n: int,
                seed: int) -> np.ndarray:
    """``n`` distinct record keys: up to half of them the most written
    (then the most read) while serving, the rest cold records drawn from
    the seed."""
    written = sorted(ref.writes, key=lambda k: (-ref.writes[k], k))
    read_hot = [k for k, _ in ref.accesses.most_common()
                if k not in ref.writes]
    hot = (written + read_hot)[: n // 2]
    taken = set(hot)
    rng = np.random.default_rng((seed, 7))
    cold = [k for k in rng.permutation(record_keys).tolist()
            if k not in taken][: n - len(hot)]
    return np.asarray(hot + cold, np.uint32)


def _bits(x: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(x, np.float32).view(np.uint32)


def check_slabs(slab_keys: np.ndarray, slab_vals: np.ndarray,
                ref: DictStore, replication: int) -> dict:
    """Every live slab entry against the reference, and every record's
    copies counted across the nodes.

    ``wrong_values``: live entries whose key is no record of the
    reference or whose value differs from the reference's in any bit.
    ``under_replicated``: records held by fewer than ``replication``
    nodes (each acknowledged write must sit on every chain member)."""
    rec = np.fromiter(ref.d.keys(), np.uint32, len(ref.d))
    order = np.argsort(rec)
    rec = rec[order]
    keys_list = rec.tolist()
    want = np.stack([ref.d[k] for k in keys_list]) if keys_list else (
        np.zeros((0, slab_vals.shape[-1]), np.float32))
    live = slab_keys != EMPTY_KEY
    k = slab_keys[live]
    v = slab_vals[live]
    pos = np.minimum(np.searchsorted(rec, k), max(len(rec) - 1, 0))
    known = (rec[pos] == k) if len(rec) else np.zeros(k.shape, bool)
    same = np.zeros(k.shape, bool)
    same[known] = (_bits(v[known]) == _bits(want[pos[known]])).all(axis=1)
    copies = np.bincount(pos[known], minlength=len(rec))
    return {
        "wrong_values": int((~same).sum()),
        "under_replicated": int((copies < replication).sum()),
        "entries_checked": int(k.size),
        "records": int(len(rec)),
    }


def check_gets(sample: np.ndarray, values: np.ndarray, found: np.ndarray,
               ref: DictStore) -> dict:
    """GET replies for ``sample`` against the reference, bit for bit:
    ``get_mismatches`` counts replies not found or not equal."""
    want, want_found = ref.get(sample, values.shape[1])
    ok = (found.astype(bool) == want_found) & (
        _bits(values) == _bits(want)).all(axis=1)
    return {"get_mismatches": int((~ok).sum()),
            "gets_checked": int(len(sample))}
