"""The benchmark's files and BENCHMARK.json, checked without a chip.

Every configuration, traffic mix and per-layer reader loads; every name
and unit keeps to the characters the benchmark contract allows; every
cell resolves to its files; and ``bench/run.py`` exits non-zero, before
any result line, where no TPU is present.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import run as RUN  # noqa: E402
from workload import load_mix  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append(entry["name"])
    assert len(names) == len(set(names))
    for cell in SPEC["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
        assert cell["chips"] in (1, 4)
        assert 1 <= len(cell["why"]) <= 200
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0 < m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200


def test_every_file_loads_and_every_cell_resolves():
    for path in sorted((BENCH / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        assert cfg["name"] == path.stem
        assert cfg["backend"] in ("oracle", "dist")
    for path in sorted((BENCH / "traffic").glob("*.json")):
        assert load_mix(path)["name"] == path.stem
    for path in sorted((BENCH / "metrics").glob("*.py")):
        assert callable(RUN.load_reader(path.stem))
    for entry in SPEC["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"]
    for cell in SPEC["workloads"]:
        _, cfg, _ = RUN.find_cell(SPEC, cell["name"])
        assert cfg["chips"] == cell["chips"]
        e2e, layer = RUN.cell_metrics(SPEC, cell["name"])
        assert {m["name"] for m in e2e} >= {"setup_s"} and layer
        for m in layer:
            assert (BENCH / "metrics" / f"{m['name']}.py").exists()


def test_peaks_name_their_source_and_refuse_an_unknown_kind():
    table = json.loads((BENCH / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    assert RUN.device_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        RUN.device_peaks("cpu")


def test_run_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", str(2**31 + 5),
         "--seconds", "1", "--trace", "0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU found" in r.stderr
