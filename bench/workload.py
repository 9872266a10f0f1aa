"""Traffic for the benchmark: the mix files in ``bench/traffic/`` and the
generators in ``bench/generators/`` that read them.

A mix file is JSON data.  Its ``generator`` names the module
``bench/generators/<generator>.py`` that turns it into an ``EpochDriver``
scenario, found by that name alone: a new mix of an existing kind is a
new mix file, and a new kind of traffic a new generator file beside it.

A generator module defines

* ``validate(mix, path)``, which raises ``ValueError`` where the mix asks
  for what the generator or the driver does not issue;
* ``Traffic(mix, *, n_records, value_dim, epoch_ops, n_epochs, seed)``,
  what ``EpochDriver`` reads from a scenario (``name``, ``cfg``,
  ``load()``, ``epoch(e)``, ``events(e)``), with the ``record_keys`` and
  ``seed`` that the check reads.  The same seed gives the same traffic.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path

# The query interface's opcodes and key space (the program's wire format:
# an op is (opcode, 32-bit key, end key, value row)).
OP_GET = 0
OP_PUT = 1
KEY_SPACE = 1 << 32
# record keys lie in [0, KEY_SPACE - 2); 0xFFFFFFFF is the store's
# empty-slot sentinel and 0xFFFFFFFE is never a record
MAX_RECORD_KEY = KEY_SPACE - 2

GENERATORS = Path(__file__).resolve().parent / "generators"
_GENERATOR_NAME = re.compile(r"^[A-Za-z0-9_]{1,64}$")


@dataclasses.dataclass(frozen=True)
class TrafficShape:
    """What the epoch driver reads from ``scenario.cfg``."""

    n_epochs: int
    epoch_ops: int
    n_records: int
    value_dim: int
    seed: int


def generator(name: str):
    """The module ``bench/generators/<name>.py``, loaded once."""
    if not isinstance(name, str) or not _GENERATOR_NAME.match(name):
        raise ValueError(f"bad generator name {name!r}")
    modname = f"bench_generator_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    path = GENERATORS / f"{name}.py"
    if not path.exists():
        raise ValueError(f"unknown generator {name!r} (no {path})")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_mix(path: Path) -> dict:
    """A traffic mix file, validated by the generator it names."""
    mix = json.loads(Path(path).read_text())
    try:
        gen = generator(mix.get("generator"))
    except ValueError as e:
        raise ValueError(f"{path}: {e}") from None
    gen.validate(mix, path)
    return mix


def make_traffic(mix: dict, **shape):
    """The scenario of ``mix``, built by its generator from ``shape``
    (``n_records``, ``value_dim``, ``epoch_ops``, ``n_epochs``, ``seed``)."""
    return generator(mix["generator"]).Traffic(mix, **shape)
