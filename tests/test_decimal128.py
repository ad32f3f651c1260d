"""128-bit decimal arithmetic (`ops/int128.py`) against Python's
`decimal`, on the CPU and seeded: carries across the low word, negative
values, magnitudes next to 10^38, overflow to NULL in a product, a sum
and a merge of partial sums, and HALF_UP ties where Spark's
`allowPrecisionLoss` cuts a product's scale and in the two roundings of
a decimal average."""

import decimal
import random

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops import int128 as W
from spark_rapids_tpu.session import TpuSession, avg, col, sum_

D = decimal.Decimal
BIG = 10 ** 38


def _data(values, dtype):
    import jax.numpy as jnp

    if dtype.wide:
        return jnp.asarray(np.array([W.literal_words(v) for v in values],
                                    np.int64))
    return jnp.asarray(np.array(values, np.int64))


def _ints(data, dtype):
    d = np.asarray(data)
    if dtype.wide:
        return [(int(hi) << 64) + (int(lo) & (2 ** 64 - 1)) for lo, hi in d]
    return [int(x) for x in d]


def _half_up(n: int, k: int) -> int:
    """n / 10^k rounded HALF_UP (k > 0), or n * 10^-k."""
    if k <= 0:
        return n * 10 ** (-k)
    q, r = divmod(abs(n), 10 ** k)
    q += 2 * r >= 10 ** k
    return q if n >= 0 else -q


def _spark_product(x, xt, y, yt, out):
    """Spark's decimal product by Python integers: exact, HALF_UP to
    the result scale, None past its precision."""
    v = _half_up(x * y, xt.scale + yt.scale - out.scale)
    return None if abs(v) >= 10 ** out.precision else v


@pytest.mark.parametrize("case", [
    # carries: the low words' sum passes 2^64
    ((2 ** 64 - 1, 1), T.DecimalType(38, 0), T.DecimalType(38, 0)),
    ((2 ** 63, 2 ** 63), T.DecimalType(38, 0), T.DecimalType(38, 0)),
    # negative values borrowing across the low word
    ((-(2 ** 64), -1), T.DecimalType(38, 0), T.DecimalType(38, 0)),
    ((-5, 2 ** 64 + 3), T.DecimalType(38, 2), T.DecimalType(20, 2)),
    # next to 10^38
    ((BIG - 1, -(BIG - 1)), T.DecimalType(38, 0), T.DecimalType(38, 0)),
])
def test_add_matches_python(case):
    (a, b), at, bt = case
    lo, hi, over = W.add(W.as_wide(_data([a], at), at),
                         W.as_wide(_data([b], bt), bt))
    got = _ints(W.pack(lo, hi), T.DecimalType(38, 0))[0]
    assert got == a + b
    assert not bool(over[0])


@pytest.mark.parametrize("seed", range(6))
def test_multiply_matches_python(seed):
    """Random types and magnitudes, every digit count up to the type's
    precision, both signs: the product's value, or NULL, as Spark's."""
    rng = random.Random(seed)
    for _ in range(20):
        p1, p2 = rng.randint(1, 38), rng.randint(1, 38)
        at = T.DecimalType(p1, rng.randint(0, p1))
        bt = T.DecimalType(p2, rng.randint(0, p2))
        out = T.adjust_precision_scale(p1 + p2 + 1, at.scale + bt.scale)
        xs = [rng.choice((-1, 1)) * rng.randint(0, 10 ** rng.randint(1, p1)
                                                - 1) for _ in range(8)]
        ys = [rng.choice((-1, 1)) * rng.randint(0, 10 ** rng.randint(1, p2)
                                                - 1) for _ in range(8)]
        data, ok = W.multiply(_data(xs, at), at, _data(ys, bt), bt, out)
        if ok is None:  # no product of these types can overflow
            assert not W.can_overflow(at, bt, out)
            ok = np.ones(len(xs), bool)
        for x, y, g, o in zip(xs, ys, _ints(data, out), np.asarray(ok)):
            want = _spark_product(x, at, y, bt, out)
            assert (want is not None) == bool(o), (x, y, at, bt, out)
            if o:
                assert g == want, (x, y, at, bt, out)


@pytest.mark.parametrize("x,y,want", [
    # decimal(38,10) x decimal(38,10) is decimal(38,6): the product's
    # scale 20 falls to 6, a tie at the first digit dropped rounds away
    # from zero
    (D("1.0000005000"), D("1"), D("1.000001")),
    (D("-1.0000005000"), D("1"), D("-1.000001")),
    (D("1.0000004999"), D("1"), D("1.000000")),
    (D("0.0000015"), D("0.5"), D("0.000001")),  # 7.5e-7 -> 1e-6
    (D("-0.0000015"), D("0.5"), D("-0.000001")),
    (D("0.0000005"), D("0.9999999999"), D("0.000000")),  # just under
])
def test_precision_loss_rescale_rounds_half_up(x, y, want):
    t = T.DecimalType(38, 10)
    out = T.adjust_precision_scale(77, 20)
    assert out == T.DecimalType(38, 6)
    unscaled = [int(v.scaleb(10)) for v in (x, y)]
    data, ok = W.multiply(_data([unscaled[0]], t), t,
                          _data([unscaled[1]], t), t, out)
    assert ok is not None and bool(ok[0])
    assert D(_ints(data, out)[0]).scaleb(-6) == want
    assert (x * y).quantize(D("0.000001"),
                            rounding=decimal.ROUND_HALF_UP) == want


@pytest.mark.parametrize("values,in_precision,precision,overflows", [
    ([BIG - 1], 38, 38, False),
    ([BIG - 1, 1], 38, 38, True),  # exactly 10^38
    ([-(BIG - 1), -1], 38, 38, True),
    ([BIG - 1, BIG - 1, -(BIG - 1)], 38, 38, False),  # exact total fits
    ([2 ** 126] * 4, 38, 38, True),  # past 128 bits
    ([10 ** 17] * 12, 38, 18, True),  # a narrow sum past 18 digits
    ([10 ** 17] * 9, 38, 18, False),
    # one-word inputs: two terms, the low one carrying into the high
    ([2 ** 32 - 1] * 3 + [-(10 ** 18 - 1)], 18, 28, False),
    ([10 ** 18 - 1] * 64, 18, 28, False),  # past 2^63
    ([-(10 ** 18 - 1)] * 64, 18, 28, False),
    ([10 ** 18 - 1] * 64, 18, 19, True),
])
@pytest.mark.parametrize("marks", [False, True])
def test_sum_settles_or_overflows(values, in_precision, precision,
                                  overflows, marks):
    import jax.numpy as jnp

    in_t = T.DecimalType(in_precision, 0)
    out = T.DecimalType(precision, 0)
    terms = W.sum_terms(_data(values, in_t), in_t,
                        jnp.ones(len(values), bool), marks)
    assert len(terms) == W.terms_of(in_t, marks)
    data, over = W.settle([jnp.sum(t)[None] for t in terms], out, marks)
    assert bool(over[0]) == overflows
    if overflows:
        assert bool(W.poisoned(data, out)[0])
    else:
        assert _ints(data, out)[0] == sum(values)


def test_merge_keeps_an_overflowed_partial():
    """A poisoned partial (an overflowed sum) poisons the merge, where a
    plain total would have come back into range."""
    import jax.numpy as jnp

    t = T.DecimalType(38, 0)
    part = W.poison(_data([0, 5, 7], t), t, jnp.asarray([True, False,
                                                         False]))
    terms = W.sum_terms(part, t, jnp.ones(3, bool), True)
    data, over = W.settle([jnp.sum(x)[None] for x in terms], t, True)
    assert bool(over[0])


@pytest.fixture
def session():
    return TpuSession()


def _dec_table(values, precision, scale, groups=None):
    cols = {"v": pa.array(values, pa.decimal128(precision, scale))}
    if groups is not None:
        cols["g"] = pa.array(groups, pa.int64())
    return pa.table(cols)


@pytest.mark.parametrize("batch_rows", [None, 2])
def test_engine_sum_overflow_is_null(session, batch_rows):
    """sum(decimal(38,0)) is decimal(38,0): four values of 6 x 10^37
    overflow it in one batch's update and, in batches of two rows, in
    the merge of the partials; a group that stays in range keeps its
    exact value."""

    if batch_rows:
        session.conf.set("spark.rapids.tpu.sql.batchSizeRows", batch_rows)
    big = D(6) * D(10) ** 37
    t = _dec_table([big, big, big, big, D(1), D(2)], 38, 0,
                   groups=[1, 1, 1, 1, 2, 2])
    df = session.create_dataframe(t).group_by(col("g")).agg(
        (sum_(col("v")), "s"))
    assert "does not support" not in df.explain()
    got = dict(zip(*df.collect().to_pydict().values()))
    assert got == {1: None, 2: D(3)}
    assert dict(zip(*df.collect(engine="cpu").to_pydict().values())) == got


def _spark_avg(values, in_t):
    """Spark's Average of a decimal column, in Python decimals."""
    from spark_rapids_tpu.exprs.decimal import average_type, divide_type

    sum_t = T.DecimalType(min(in_t.precision + 10, 38), in_t.scale)
    div, out = divide_type(sum_t), average_type(in_t)
    with decimal.localcontext() as ctx:
        ctx.prec = 100
        q = (sum(values) / len(values)).quantize(
            D(1).scaleb(-div.scale), rounding=decimal.ROUND_HALF_UP)
        return q.quantize(D(1).scaleb(-out.scale),
                          rounding=decimal.ROUND_HALF_UP)


@pytest.mark.parametrize("values,precision,scale,want", [
    # decimal(15,2): the divide type is decimal(38,15), the answer
    # decimal(19,6); 0.01 over 20,000 rows is 0.0000005, a tie at the
    # cast to scale 6
    ([D("0.01")] + [D("0.00")] * 19999, 15, 2, D("0.000001")),
    ([D("-0.01")] + [D("0.00")] * 19999, 15, 2, D("-0.000001")),
    # decimal(28,2): the divide type is decimal(38,6), the tie at the
    # division itself, and the cast to decimal(32,6) keeps it
    ([D("0.01")] + [D("0.00")] * 19999, 28, 2, D("0.000001")),
    ([D("0.03")] + [D("0.00")] * 59999, 28, 2, D("0.000001")),
    ([D("1.00"), D("2.00"), D("2.00")], 15, 2, D("1.666667")),
    ([D("-1.00"), D("-2.00"), D("-2.00")], 15, 2, D("-1.666667")),
])
def test_average_rounds_half_up_twice(session, values, precision, scale,
                                      want):
    in_t = T.DecimalType(precision, scale)
    assert _spark_avg(values, in_t) == want
    df = session.create_dataframe(_dec_table(values, precision, scale)
                                  ).agg((avg(col("v")), "a"))
    assert "does not support" not in df.explain()
    assert df.collect().to_pydict()["a"] == [want]
    assert df.collect(engine="cpu").to_pydict()["a"] == [want]


@pytest.mark.parametrize("seed", range(3))
def test_average_matches_python(session, seed):
    rng = random.Random(seed)
    in_t = T.DecimalType(15, 2)
    groups = [rng.randint(0, 4) for _ in range(500)]
    values = [D(rng.randint(-10 ** 13, 10 ** 13)).scaleb(-2)
              for _ in groups]
    df = session.create_dataframe(_dec_table(values, 15, 2, groups)
                                  ).group_by(col("g")).agg(
        (avg(col("v")), "a"), (sum_(col("v")), "s"))
    out = df.collect().to_pydict()
    for g, a, s in zip(out["g"], out["a"], out["s"]):
        mine = [v for v, h in zip(values, groups) if h == g]
        assert s == sum(mine)
        assert a == _spark_avg(mine, in_t)


def _agg_metric(session, name: str) -> int:
    """`name` summed over the last query's aggregate operators."""
    todo, total = [session.history.events[-1].root], 0
    while todo:
        node = todo.pop()
        todo += node.children
        if node.desc.startswith("TpuHashAggregateExec"):
            total += node.metrics.get(name, 0)
    return total


def test_overflow_rows_are_counted(session):
    """decimalOverflowRows: the values a precision check set to NULL,
    deferred to the query's end: two products past decimal(38,0) in
    the update, and one group's sum past it at the finish."""
    big = D(10) ** 20
    t = _dec_table([big, big, D(2), D(3)], 38, 0, groups=[1, 2, 2, 2])
    df = session.create_dataframe(t).group_by(col("g")).agg(
        (sum_(col("v") * col("v")), "sq"))
    got = dict(zip(*df.collect().to_pydict().values()))
    assert got == {1: None, 2: D(13)}
    assert _agg_metric(session, "decimalOverflowRows") == 2
    df = session.create_dataframe(_dec_table(
        [D(6) * D(10) ** 37] * 2 + [D(1)], 38, 0, groups=[1, 1, 2])
    ).group_by(col("g")).agg((sum_(col("v")), "s"))
    assert dict(zip(*df.collect().to_pydict().values())) == {1: None,
                                                              2: D(1)}
    assert _agg_metric(session, "decimalOverflowRows") == 1
