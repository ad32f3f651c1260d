"""The 99-query TPC-DS sweep harness (tools/sweep.py, SWEEP_r01.json).

Tier-1 keeps this LEAN: the full execute+oracle sweep over all 99
queries is the offline artifact run (`python -m
spark_rapids_tpu.tools.sweep`); here we assert the harness machinery —
classification stages, failure taxonomy, the satellite fix probes —
plus a full-corpus PARSE pass (cheap) and a 3-query end-to-end slice,
and that the committed artifact satisfies the coverage floors.
"""

import json
import os

import pytest

from spark_rapids_tpu.tools import sweep as SW
from spark_rapids_tpu.tools.tpcds_queries import QUERIES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_corpus_is_complete():
    assert sorted(QUERIES) == list(range(1, 100))
    assert all(q.strip().lower().startswith(("select", "with"))
               for q in QUERIES.values())


def test_full_corpus_parse_floor():
    """Cheap parse-only pass over ALL 99 texts: the grammar accepts at
    least the BASELINE floor (>= 40) — a parser regression that drops
    whole query families fails here, without paying execution."""
    from spark_rapids_tpu.frontends.sql import SqlError, _Parser

    parsed = 0
    for qid, text in QUERIES.items():
        try:
            _Parser(text).parse_select()
            parsed += 1
        except SqlError:
            pass
    assert parsed >= 40, f"only {parsed}/99 parsed"


def test_three_query_slice_end_to_end():
    """q3 (the anchor), q27 (GROUPING SETS satellite), q37 (month
    interval satellite) classify as correct vs the CPU oracle, and the
    fix probes attribute each satellite advance."""
    fe = SW.build_session()
    results = {}
    for qid in (3, 27, 37):
        results[f"q{qid}"] = SW.classify_query(fe, QUERIES[qid])
        assert results[f"q{qid}"]["status"] == "correct", \
            (qid, results[f"q{qid}"])
    adv = SW.fix_probes(fe, {q: QUERIES[q] for q in (3, 27, 37)},
                        results)
    assert "q27" in adv["grouping_sets"]
    assert "q37" in adv["month_year_interval"]
    assert "q3" not in adv["grouping_sets"]


def test_taxonomy_classifier():
    # INTERSECT and EXCEPT parse since PR 38: their two classes are
    # gone, and an error text that merely says "Exception" is no set-op
    assert SW._classify_reason("set-op INTERSECT blah") == "other"
    assert SW._classify_reason("SomeException: no idea") == "other"
    assert SW._classify_reason("unknown function 'stddev_samp'") \
        == "unknown function"
    assert SW._classify_reason("no idea") == "other"


def test_committed_artifact_meets_floors():
    """SWEEP_r01.json (the committed artifact) satisfies the
    BASELINE #5 acceptance floors: >= 40 parsed, >= 20 executed AND
    correct vs the CPU oracle with q3/q67 among them, each satellite
    fix advancing >= 1 query, and the wire subset digest-matching."""
    path = os.path.join(REPO, "SWEEP_r01.json")
    if not os.path.exists(path):
        pytest.skip("SWEEP_r01.json not committed yet")
    with open(path) as f:
        rep = json.load(f)
    t = rep["totals"]
    assert t["queries"] == 99
    assert t["parsed"] >= 40
    assert t["correct"] >= 20
    for q in ("q3", "q67"):
        assert rep["queries"][q]["status"] == "correct", \
            rep["queries"][q]
    adv = rep["satellite_advances"]
    for feature in SW.FIX_FEATURES:
        assert len(adv[feature]) >= 1, (feature, adv)
    for name, v in rep["wire"].items():
        assert v["status"] == "ok" and v["digest_match"], (name, v)


@pytest.mark.parametrize("qid", [38, 87])
def test_the_set_operation_queries_answer_as_the_hand_built_frames(qid):
    """q38 (INTERSECT) and q87 (EXCEPT) parse since PR 38 and lower
    through `DataFrame.intersect` / `subtract`: over the mini catalog
    the SQL text gives what the benchmark's hand-built frames give
    (`benchmarks/queries/q38.py`, `q87.py`), on the CPU engine, and
    the device engine agrees."""
    import importlib

    query = importlib.import_module(f"benchmarks.queries.q{qid}")
    fe = SW.build_session()
    from_sql = fe.sql(QUERIES[qid])
    frames = {role: fe.table(role) for role in query.COLUMNS}
    by_hand = query.build(fe.session, frames)
    want = by_hand.collect(engine="cpu").column(0).to_pylist()
    assert from_sql.collect(engine="cpu").column(0).to_pylist() == want
    assert from_sql.collect(engine="tpu").column(0).to_pylist() == want
    # the answer is a count over a side that is not empty
    store, catalog, web = query.channels(fe.session, frames)
    assert store.collect(engine="cpu").num_rows > want[0] >= 0
