"""Finds a cell's files by the names `BENCHMARK.json` gives.

A cell is one entry of `workloads`: a configuration under a traffic
mix.  The configuration's file is the one `configs` names; the traffic
mix is `traffic/<traffic>.json`; each query of the mix is
`queries/<query>.py`, each table's generator `generators/<generator>.py`
and each per-layer metric `layer_metrics/<name>.py`.  Nothing here
lists them, so a later cell brings files and no edit.  No JAX, no
engine: the data-generation workers import this.
"""

import dataclasses
import importlib
import json
import pathlib

#: the checkout: BENCHMARK.json and the engine's package sit here
ROOT = pathlib.Path(__file__).resolve().parents[2]
PACKAGE = ROOT / "benchmarks"

#: what `--rehearse` shrinks every table to: its first file, at one
#: row in this many
REHEARSAL_CUT = 16


def benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def module(kind: str, name: str):
    """`benchmarks/<kind>/<name>.py`, imported."""
    return importlib.import_module(f"benchmarks.{kind}.{name}")


@dataclasses.dataclass(frozen=True)
class Table:
    """One table of a configuration, at the size this run makes it."""

    name: str
    generator: str
    files: int
    rows_per_file: int

    @property
    def rows(self) -> int:
        return self.files * self.rows_per_file


def column_bytes(table: Table, columns) -> int:
    """Bytes the named columns of the table take on the device: rows
    times the widths its generator declares."""
    widths = module("generators", table.generator).COLUMN_BYTES
    return table.rows * sum(widths[c] for c in columns)


@dataclasses.dataclass(frozen=True)
class Step:
    """One query of a round, with the table each of its roles reads."""

    query: str
    tables: tuple  # (role, Table) pairs
    #: operators the plan of this step's collect has to hold
    plan_has: tuple = ()

    def table(self, role: str) -> Table:
        return dict(self.tables)[role]

    def input_bytes(self) -> int:
        """Bytes the query's input columns take on the device, read
        once."""
        wanted = module("queries", self.query).COLUMNS
        return sum(column_bytes(table, wanted[role])
                   for role, table in self.tables)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    round: tuple  # of Step
    end_to_end: tuple  # metric entries of BENCHMARK.json for this cell
    per_layer: tuple

    @property
    def resident(self) -> bool:
        return self.traffic["input"] == "resident"

    def tables(self) -> list:
        """The tables this cell's traffic reads, each once."""
        seen: dict = {}
        for step in self.round:
            for _, table in step.tables:
                seen[table.name] = table
        return list(seen.values())

    def input_rows(self) -> int:
        """Rows a round reads: every query's tables, footer counts."""
        return sum(t.rows for step in self.round for _, t in step.tables)


def _table(config: dict, name: str, rehearse: bool) -> Table:
    entry = config["tables"][name]
    files, rows = entry["files"], entry["rows_per_file"]
    if rehearse:
        files, rows = 1, rows // REHEARSAL_CUT
    return Table(name, entry["generator"], files, rows)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, rehearse: bool = False) -> Cell:
    bench = benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    conf_entry = next(c for c in bench["configs"]
                      if c["name"] == entry["config"])
    with open(ROOT / conf_entry["file"]) as f:
        config = json.load(f)
    with open(PACKAGE / "traffic" / f"{entry['traffic']}.json") as f:
        traffic = json.load(f)
    steps = tuple(
        Step(s["query"], tuple(
            (role, _table(config, table, rehearse))
            for role, table in sorted(s["tables"].items())),
            tuple(s.get("plan_has", ())))
        for s in traffic["round"])
    return Cell(
        name=name, chips=entry["chips"], config=config, traffic=traffic,
        round=steps,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _reports(m, name)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _reports(m, name)))
