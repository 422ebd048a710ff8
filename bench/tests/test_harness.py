"""BENCHMARK.json against the files the harness finds by name, and the
entry point's refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness as H

SPEC = H.load_json(H.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_finds_its_files():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"])
        assert (H.ROOT / c["file"]).is_file()
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"])
        traffic = H.load_json(H.BENCH / "traffic" / f"{w['traffic']}.json")
        assert (H.BENCH / "drivers" / f"{traffic['driver']}.py").is_file()
        assert w["config"] in {c["name"] for c in SPEC["configs"]}
    for m in SPEC["per_layer"]:
        assert NAME.match(m["name"])
        assert (H.BENCH / "metrics" / f"{m['name']}.py").is_file()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_each_cell_reports_what_the_contract_asks(cell):
    e2e = {m["name"] for m in H.end_to_end_metrics(SPEC, cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = H.per_layer_metrics(SPEC, cell)
    assert layer
    assert all(m["moves"] in e2e for m in layer)


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(H.BENCH / "run.py"), "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert out.returncode != 0
    assert "TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
