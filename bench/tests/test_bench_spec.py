"""BENCHMARK.json and the files it names: every cell, configuration, mix
and metric is found by name, and the file keeps to the benchmark's
contract."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    cells = len(SPEC["workloads"])
    assert 2 + 14 * cells <= 43200   # runs of a full check, loosely


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_each_cell_is_found_by_name(w):
    cell = harness.load_cell(w["name"], ROOT)
    assert w["chips"] in (1, 4)
    assert NAME.match(w["name"]) and len(w["why"]) <= 200
    assert {"slots", "page_size", "prefill_chunk", "max_len",
            "kv_pages"} <= set(cell.engine)
    assert cell.check["limit"] > 0
    kinds = {m["kind"] for m in cell.metrics.values()}
    assert kinds == {"e2e", "layer"} and "setup_s" in cell.metrics
    # every per-layer metric of the cell moves an end-to-end one it reports
    for m in SPEC["per_layer"]:
        if w["name"] in m["workloads"]:
            assert m["moves"] in cell.metrics


@pytest.mark.parametrize("c", SPEC["configs"], ids=lambda c: c["name"])
def test_each_config_file_states_its_cuts(c):
    cfg = json.loads((ROOT / c["file"]).read_text())
    assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
    assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for k in c["reduced"]:
        assert not re.search(r"(_size|_dim|_rank|expand|expansion|per_tok)$", k)
    assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").exists()


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_each_metric_has_its_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert callable(harness.reader(m["name"]))
    if m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "\n" not in m["layer"]


def test_no_accelerator_no_result():
    """Off the chip the command exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "correct" not in p.stdout and "metrics" not in p.stdout
