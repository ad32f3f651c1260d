"""The coded group-by's two reductions (masked sum, `segment_sum`) and
the sort path give one answer, the choice between the two follows
K x m alone, and the program's text and the tracer say which ran."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu.types as T
from spark_rapids_tpu import trace
from spark_rapids_tpu.columnar.arrow import _strip_dict_sidecar
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops.groupby import AggSpec

CAP = 16384  # four folds of _MASKED_LANES
_WIDTH = 4

#: value ordinals follow the keys: d DOUBLE, i INT, l LONG
_AGGS = ("sum:d", "count:d", "sum:i", "count_star", "sum:l", "sum:d",
         "min:d", "max:i")
#: distinct matrix columns of _AGGS: live ones, d's validity and value,
#: i's validity and value, l's validity and value
_M = 7


#: largest K the masked form takes at _M columns
_EDGE = G.MAX_MASKED_CELLS // _M

#: name -> per-key dictionary bounds (K is the product of bound + 1);
#: "s" marks a string key
_DOMAINS = {
    "K2": [1],
    "K81": [8, 8],
    "K81_string_key": [8, "s8"],
    "K_at_constant": [_EDGE - 1],
    "K_past_constant": [_EDGE],
}

_FEATURES = ("plain", "null_keys", "null_values", "all_null_group",
             "dead_rows", "live_mask", "int_wrap", "everything")


def _int_key(rng, k, null_keys):
    # the wire pads a dictionary to a pow2 bucket; dict_len is the bound
    padded = max(8, 1 << (k - 1).bit_length())
    n_true = max(1, k - 1) if k > 2 else k
    dvals = np.zeros(padded, np.int64)
    dvals[:n_true] = rng.permutation(n_true) * 1000 + 7
    codes = rng.integers(0, n_true, CAP).astype(np.int32)
    valid = rng.random(CAP) > 0.1 if null_keys else np.ones(CAP, bool)
    return Column(jnp.asarray(np.where(valid, dvals[codes], 0)),
                  jnp.asarray(valid), T.LONG,
                  codes=jnp.asarray(np.where(valid, codes, 0)),
                  dict_values=jnp.asarray(dvals), dict_len=k)


def _string_key(rng, k, null_keys):
    dchars = np.zeros((k, _WIDTH), np.uint8)
    dlens = np.zeros(k, np.int32)
    for j in range(k - 2):  # two dictionary slots stay unused
        word = f"g{j}".encode()
        dchars[j, :len(word)] = list(word)
        dlens[j] = len(word)
    codes = rng.integers(0, k - 2, CAP).astype(np.int32)
    valid = rng.random(CAP) > 0.1 if null_keys else np.ones(CAP, bool)
    codes = np.where(valid, codes, 0)
    return StringColumn(
        jnp.asarray(dchars[codes] * valid[:, None].astype(np.uint8)),
        jnp.asarray(dlens[codes] * valid), jnp.asarray(valid),
        codes=jnp.asarray(codes), dict_chars=jnp.asarray(dchars),
        dict_lens=jnp.asarray(dlens), dict_len=k)


def _make(domain: str, feature: str):
    """(batch, key ordinals, ks, specs, out schema, live_mask)."""
    rng = np.random.default_rng(
        _FEATURES.index(feature) * 16 + list(_DOMAINS).index(domain))
    on = lambda f: feature in (f, "everything")  # noqa: E731
    ks, keys = [], []
    for k in _DOMAINS[domain]:
        if isinstance(k, str):
            ks.append(int(k[1:]))
            keys.append(_string_key(rng, ks[-1], on("null_keys")))
        else:
            ks.append(k)
            keys.append(_int_key(rng, k, on("null_keys")))
    num_rows = CAP - 600 if on("dead_rows") else CAP
    d = rng.random(CAP) * 1e5 + 1.0
    d_valid = rng.random(CAP) > 0.2 if on("null_values") \
        else np.ones(CAP, bool)
    if on("all_null_group"):
        # every d of the first key's code 0 is NULL: sum NULL, count 0
        d_valid &= np.asarray(keys[0].codes) != 0
    i = rng.integers(-2**31, 2**31, CAP).astype(np.int32)
    i_valid = rng.random(CAP) > 0.2 if on("null_values") \
        else np.ones(CAP, bool)
    span = 2**62 if on("int_wrap") else 2**40
    ell = rng.integers(span // 2, span, CAP)
    vals = [Column(jnp.asarray(d), jnp.asarray(d_valid), T.DOUBLE),
            Column(jnp.asarray(i), jnp.asarray(i_valid), T.INT),
            Column(jnp.asarray(ell), jnp.ones(CAP, bool), T.LONG)]
    if on("dead_rows"):  # padding carries validity False, data arbitrary
        dead = jnp.arange(CAP) >= num_rows
        keys = [c.with_validity(c.validity & ~dead) for c in keys]
        vals = [c.with_validity(c.validity & ~dead) for c in vals]
    nk = len(keys)
    fields = [T.Field(f"k{j}", c.dtype) for j, c in enumerate(keys)] \
        + [T.Field("d", T.DOUBLE), T.Field("i", T.INT), T.Field("l", T.LONG)]
    batch = ColumnarBatch(keys + vals, num_rows, T.Schema(fields))
    ordinal = {"d": nk, "i": nk + 1, "l": nk + 2}
    specs, out_fields = [], fields[:nk]
    for j, a in enumerate(_AGGS):
        op, _, col = a.partition(":")
        specs.append(AggSpec(op, ordinal.get(col, 0)))
        vdt = batch.columns[ordinal[col]].dtype if col else None
        out_fields.append(T.Field(f"a{j}", G.agg_output_dtype(specs[-1],
                                                              vdt)))
    live_mask = jnp.asarray(rng.random(CAP) > 0.3) if on("live_mask") \
        else None
    return (batch, list(range(nk)), ks, specs, T.Schema(out_fields),
            live_mask)


def _rows(out: ColumnarBatch, n_keys: int):
    """Group rows as (key tuple, agg values), NULL as None, by key."""
    n = out.concrete_num_rows()
    cols = []
    for c in out.columns:
        valid = np.asarray(c.validity)[:n]
        if isinstance(c, StringColumn):
            chars, lens = np.asarray(c.chars), np.asarray(c.lengths)
            data = [bytes(chars[r, :lens[r]]).decode() for r in range(n)]
        else:
            data = np.asarray(c.data)[:n].tolist()
        cols.append([v if ok else None for v, ok in zip(data, valid)])
    rows = [(tuple(c[r] for c in cols[:n_keys]),
             [c[r] for c in cols[n_keys:]]) for r in range(n)]
    return sorted(rows, key=lambda kv: tuple(
        (v is None, 0 if v is None else v) for v in kv[0]))


def _assert_same(got, want):
    assert [k for k, _ in got] == [k for k, _ in want]
    for (key, g), (_, w) in zip(got, want):
        for a, gv, wv in zip(_AGGS, g, w):
            if isinstance(wv, float) and a.startswith("sum"):
                assert gv == pytest.approx(wv, rel=1e-12, abs=0), (key, a)
            else:  # keys, counts, integer sums, min and max: exact
                assert gv == wv, (key, a, gv, wv)


@pytest.fixture
def coded_events():
    """The `groupby.coded_reduce` events of the test, as attr dicts."""
    trace.enable()
    trace.clear()
    try:
        yield lambda: [e.attrs for e in trace.snapshot()
                       if e.name == "groupby.coded_reduce"]
    finally:
        trace.disable()
        trace.clear()


@pytest.mark.parametrize("feature", _FEATURES)
@pytest.mark.parametrize("domain", list(_DOMAINS))
def test_masked_scatter_and_sort_path_agree(domain, feature, monkeypatch,
                                            coded_events):
    batch, key_ords, ks, specs, out_schema, live_mask = _make(domain,
                                                              feature)
    K = int(np.prod([k + 1 for k in ks]))
    assert G._coded_key_domains(
        [batch.columns[o] for o in key_ords]) == ks

    as_shipped = _rows(G.groupby_aggregate(
        batch, key_ords, specs, out_schema, live_mask), len(ks))
    shipped_kind = coded_events()[-1]
    assert shipped_kind == {
        "kind": "masked" if K * _M <= G.MAX_MASKED_CELLS else "scatter",
        "K": K, "m": _M, "cap": CAP}
    assert (domain == "K_past_constant") == (shipped_kind["kind"]
                                             == "scatter")

    forced = {}
    for kind, cells in (("masked", K * _M), ("scatter", 0)):
        monkeypatch.setattr(G, "MAX_MASKED_CELLS", cells)
        forced[kind] = _rows(G._coded_groupby(
            batch, key_ords, ks, specs, out_schema, live_mask), len(ks))
        assert coded_events()[-1]["kind"] == kind
    sort_path = _rows(G.groupby_aggregate(
        _strip_dict_sidecar(batch), key_ords, specs, out_schema, live_mask),
        len(ks))

    _assert_same(forced["masked"], forced["scatter"])
    _assert_same(forced["masked"], sort_path)
    assert as_shipped == forced[shipped_kind["kind"]]

    if feature in ("all_null_group", "everything"):
        emptied = [v for key, v in forced["masked"]
                   if key[0] == int(batch.columns[0].dict_values[0])]
        assert emptied and all(v[0] is None and v[1] == 0 and v[5] is None
                               and v[6] is None for v in emptied)
    if feature == "int_wrap":  # the LONG sums left int64's range
        exact = sum(int(x) for x in np.asarray(batch.columns[-1].data))
        wrapped = sum(v[4] for _, v in forced["masked"])
        assert exact >= 2**63 and (wrapped - exact) % 2**64 == 0


def _q1_shaped(ks):
    """q1's update: two coded keys; sum and avg of one input stack the
    same operand, as do a count and a sum of it."""
    rng = np.random.default_rng(3)
    keys = [_int_key(rng, k, False) for k in ks]
    vals = [Column(jnp.asarray(rng.random(CAP)), jnp.ones(CAP, bool),
                   T.DOUBLE) for _ in range(5)]
    nk = len(keys)
    specs = [AggSpec("sum", nk + j) for j in (0, 1, 2, 3, 0, 1, 4)] \
        + [AggSpec("count", nk + j) for j in (0, 1, 4)] \
        + [AggSpec("count_star", 0)]
    fields = [T.Field(f"k{j}", T.LONG) for j in range(nk)]
    schema = T.Schema(fields + [T.Field(f"v{j}", T.DOUBLE)
                                for j in range(5)])
    out_schema = T.Schema(fields + [
        T.Field(f"a{j}", G.agg_output_dtype(s, T.DOUBLE))
        for j, s in enumerate(specs)])
    return ColumnarBatch(keys + vals, CAP, schema), specs, out_schema


@pytest.mark.parametrize("ks,kind", [
    ([8, 8], "masked"),
    ([G.MAX_MASKED_CELLS // 11], "scatter"),
])
def test_q1_program_text_and_event(ks, kind, coded_events):
    batch, specs, out_schema = _q1_shaped(ks)
    K = int(np.prod([k + 1 for k in ks]))

    def update(b):
        return G.groupby_aggregate(b, list(range(len(ks))), specs,
                                   out_schema)

    lowered = jax.jit(update).lower(batch)
    assert ("scatter" in lowered.as_text()) == (kind == "scatter")
    assert f"groupby.coded.{kind}" in lowered.as_text(debug_info=True)
    # eighteen stacked columns before: one per distinct operand now
    # (live ones, five validities, five values), and one event a trace
    assert coded_events() == [{"kind": kind, "K": K, "m": 11, "cap": CAP}]


def test_specs_over_one_input_share_a_column(coded_events):
    batch, _, _, _, _, _ = _make("K81", "plain")
    specs = [AggSpec("sum", 2), AggSpec("count", 2), AggSpec("sum", 2),
             AggSpec("count_star", 0)]
    out_schema = T.Schema(
        [T.Field("k0", T.LONG), T.Field("k1", T.LONG),
         T.Field("s", T.DOUBLE), T.Field("c", T.LONG),
         T.Field("s2", T.DOUBLE), T.Field("n", T.LONG)])
    out = G.groupby_aggregate(batch, [0, 1], specs, out_schema)
    assert coded_events()[-1]["m"] == 3  # live ones, validity, value
    n = out.concrete_num_rows()
    assert n > 1
    np.testing.assert_array_equal(np.asarray(out.columns[2].data)[:n],
                                  np.asarray(out.columns[4].data)[:n])
    np.testing.assert_array_equal(np.asarray(out.columns[3].data)[:n],
                                  np.asarray(out.columns[5].data)[:n])


def test_session_evaluates_equal_agg_inputs_once(tmp_path, coded_events):
    """Each aggregate binds its own reference to its input, and
    `BoundReference.eval` makes a new validity each time: the exec
    evaluates equal inputs once, so this q1-shaped query's sixteen
    slots (six sums with their validities, three counts, live ones)
    are nine columns."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.session import (
        TpuSession, avg, col, count_star, lit, sum_)

    n = 3000
    rng = np.random.default_rng(43)
    pq.write_table(pa.table({
        "flag": pa.array([["A", "N", "R"][i]
                          for i in rng.integers(0, 3, n)]),
        "status": pa.array([["F", "O"][i] for i in rng.integers(0, 2, n)]),
        "qty": pa.array(rng.integers(1, 51, n).astype(np.float64)),
        "price": pa.array(rng.uniform(900, 105000, n)),
        "disc": pa.array(rng.integers(0, 11, n) / 100.0),
    }), tmp_path / "lineitem.parquet")
    disc_price = (col("price") * (lit(1.0) - col("disc"))).alias("dp")
    q = (TpuSession().read_parquet(str(tmp_path))
         .select(col("flag"), col("status"), col("qty"), col("price"),
                 col("disc"), disc_price)
         .group_by("flag", "status")
         .agg((sum_("qty"), "sum_qty"), (sum_("price"), "sum_price"),
              (sum_("dp"), "sum_dp"), (avg("qty"), "avg_qty"),
              (avg("price"), "avg_price"), (avg("disc"), "avg_disc"),
              (count_star(), "n")))
    assert q.collect(engine="tpu").num_rows == 6
    # live ones + validity and value of qty, price, dp, disc; the keys'
    # 2- and 3-entry dictionaries are bounded by 8 each on the wire
    assert [(e["kind"], e["K"], e["m"]) for e in coded_events()] \
        == [("masked", 81, 9)]
