"""The harness: what it refuses, what it finds by name, and that
`BENCHMARK.json` and the files it names agree."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.harness import engine, spec

ROOT = str(spec.ROOT)
BENCH = spec.benchmark()


def _run(*args: str, env=None):
    return subprocess.run(
        [sys.executable, "-m", "benchmarks.run", *args], cwd=ROOT,
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})})


def test_a_cpu_platform_is_refused_without_rehearse():
    cell = BENCH["workloads"][0]["name"]
    done = _run("--workload", cell, "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse")
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {m["name"] for m in BENCH["end_to_end"]}
    with pytest.raises(engine.Refused, match="no TPU"):
        engine.require_devices(1, rehearse=False)
    with pytest.raises(engine.Refused, match="needs 4096 chips"):
        engine.require_devices(4096, rehearse=True)


def test_an_unknown_cell_is_refused_before_any_work():
    done = _run("--workload", "no-such.cell", "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert done.returncode != 0 and done.stdout == ""
    assert "no workload" in done.stderr


def test_every_cell_loads_through_the_lookup():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"] == cell.config["chips"]
        assert cell.round and cell.input_rows() > 0
        for step in cell.round:
            query = spec.module("queries", step.query)
            assert set(query.COLUMNS) == {r for r, _ in step.tables}
            assert step.input_bytes() > 0
            for role, table in step.tables:
                widths = spec.module("generators",
                                     table.generator).COLUMN_BYTES
                assert set(query.COLUMNS[role]) < set(widths)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names


def test_benchmark_json_agrees_with_the_metric_files():
    """BENCHMARK.json repeats what each reader declares; they may not
    drift apart."""
    for m in BENCH["per_layer"]:
        mod = spec.module("layer_metrics", m["name"])
        assert (mod.NAME, mod.UNIT, mod.BETTER, mod.LAYER, mod.SOURCE,
                mod.MOVES) == (m["name"], m["unit"], m["better"],
                               m["layer"], m["source"], m["moves"])
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        assert config["name"] == c["name"]
        assert config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        assert set(config["reduced"]) <= set(config["tables"])


def test_the_runner_names_no_cell_query_or_metric():
    with open(os.path.join(ROOT, "benchmarks", "run.py")) as f:
        text = f.read()
    named = [w["name"] for w in BENCH["workloads"]] \
        + [m["name"] for m in BENCH["per_layer"]] \
        + [q[:-3] for q in os.listdir(
            os.path.join(ROOT, "benchmarks", "queries"))
           if q.endswith(".py") and q != "__init__.py"]
    assert [n for n in named if f'"{n}"' in text or f"'{n}'" in text] == []


def test_operators_off_the_device_are_found():
    on = "* TpuProject\n  * TpuScan\nPipeline:\n  anything"
    assert engine.off_device(on) == []
    off = "* TpuProject\n  ! CpuSort [no device sort for x]\n"
    assert engine.off_device(off) == ["  ! CpuSort [no device sort for x]"]
    assert engine.off_device("Pipeline: none") == ["Pipeline: none"]
