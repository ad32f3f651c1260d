"""What `orders` and `lineitem` share: one chunk of orders and its
lines, drawn as the TPC-H specification's clause 4.2.3 describes them
(dbgen's rules, not dbgen's random streams).

File `i` of `orders` holds the orders of chunk `i` and file `i` of
`lineitem` their lines, clustered by order key in line-number order,
so each generator draws the chunk it belongs to and neither reads the
other's file.  The order-level draws come from
`default_rng([seed, ORDERS_ID, i])` and the line-level ones from
`default_rng([seed, LINEITEM_ID, i])`: a file depends on the seed and
its index alone.

One departure, listed in the configuration under `assumed`: an order
has 1 to 7 lines, uniform, and then as few orders as it takes (about
one in 500) move by one line so that a chunk of n orders holds exactly
4 n lines.  Every file of a table then has the same row count under
every seed, which keeps the shapes the engine compiles for, and so a
run's set-up, the same from seed to seed.
"""

import functools

import numpy as np

ORDERS_ID, LINEITEM_ID = 2, 1

#: the deployment's scale factor: the key domains below follow it
SF = 10
CUSTOMERS = 150_000 * SF
PARTS = 200_000 * SF
SUPPLIERS = 10_000 * SF
CLERKS = 1_000 * SF

#: days since 1970-01-01
STARTDATE = 8035  # 1992-01-01
ENDDATE = 10591  # 1998-12-31
CURRENTDATE = 9298  # 1995-06-17
LINES_PER_ORDER = 4  # the mean of 1..7

RETURNFLAGS = np.array(["A", "N", "R"])
LINESTATUSES = np.array(["F", "O"])
ORDERSTATUSES = np.array(["F", "O", "P"])
PRIORITIES = np.array(["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED",
                       "5-LOW"])
INSTRUCTIONS = np.array(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                         "TAKE BACK RETURN"])
MODES = np.array(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"])


def order_draws(seed: int, index: int, n_orders: int) -> dict:
    """The order-level columns of chunk `index` that need no line, and
    `lines`, how many lines each order has."""
    rng = np.random.default_rng([seed, ORDERS_ID, index])
    # dbgen's sparse keys: the first 8 of every 32
    at = np.arange(index * n_orders, (index + 1) * n_orders, dtype=np.int64)
    lines = rng.integers(1, 8, n_orders).astype(np.int32)
    off = LINES_PER_ORDER * n_orders - int(lines.sum())
    # too few lines: add one to orders that have under 7; too many:
    # take one from orders that have over 1
    room = np.flatnonzero(lines < 7 if off > 0 else lines > 1)
    lines[rng.choice(room, abs(off), replace=False)] += np.sign(off)
    # a customer key is never a multiple of 3: a third place no order
    cust = rng.integers(0, CUSTOMERS * 2 // 3, n_orders)
    return {
        "o_orderkey": at // 8 * 32 + at % 8 + 1,
        "o_custkey": cust // 2 * 3 + cust % 2 + 1,
        "o_orderdate": rng.integers(
            STARTDATE, ENDDATE - 151 + 1, n_orders).astype(np.int32),
        "o_orderpriority": rng.integers(0, len(PRIORITIES),
                                        n_orders).astype(np.int8),
        "o_clerk": rng.integers(1, CLERKS + 1, n_orders).astype(np.int32),
        "o_shippriority": np.zeros(n_orders, np.int32),
        "lines": lines,
    }


def line_draws(seed: int, index: int, orders: dict) -> dict:
    """The lines of the chunk's orders, every column but the comment;
    flags and modes as indexes into the arrays above."""
    rng = np.random.default_rng([seed, LINEITEM_ID, index])
    lines = orders["lines"]
    n = int(lines.sum())
    first = np.cumsum(lines) - lines  # each order's first row
    order_of = np.repeat(np.arange(len(lines), dtype=np.int32), lines)
    orderdate = orders["o_orderdate"][order_of]
    part = rng.integers(1, PARTS + 1, n)
    quantity = rng.integers(1, 51, n)
    # clause 4.2.3: the part's retail price, in cents
    retail = 90000 + part // 10 % 20001 + 100 * (part % 1000)
    ship = orderdate + rng.integers(1, 122, n).astype(np.int32)
    receipt = ship + rng.integers(1, 31, n).astype(np.int32)
    returned = rng.integers(0, 2, n).astype(np.int8) * 2  # "A" or "R"
    return {
        "l_orderkey": orders["o_orderkey"][order_of],
        "l_partkey": part,
        "l_suppkey": (part + rng.integers(0, 4, n)
                      * (SUPPLIERS // 4 + (part - 1) // SUPPLIERS))
        % SUPPLIERS + 1,
        "l_linenumber": (np.arange(n, dtype=np.int32)
                         - first[order_of].astype(np.int32) + 1),
        "l_quantity": quantity.astype(np.float64),
        "l_extendedprice": (quantity * retail) / 100.0,
        "l_discount": rng.integers(0, 11, n) / 100.0,
        "l_tax": rng.integers(0, 9, n) / 100.0,
        "l_returnflag": np.where(receipt <= CURRENTDATE, returned,
                                 np.int8(1)),
        "l_linestatus": (ship > CURRENTDATE).astype(np.int8),
        "l_shipdate": ship,
        "l_commitdate": orderdate + rng.integers(30, 91, n).astype(np.int32),
        "l_receiptdate": receipt,
        "l_shipinstruct": rng.integers(0, len(INSTRUCTIONS),
                                       n).astype(np.int8),
        "l_shipmode": rng.integers(0, len(MODES), n).astype(np.int8),
        "order_of": order_of,
    }


# -- comments ------------------------------------------------------- #

_WORDS = (
    "foxes ideas theodolites pinto beans instructions dependencies excuses "
    "platelets asymptotes courts dolphins multipliers sauternes warthogs "
    "frets dinos attainments somas Tiresias patterns forges braids frays "
    "warhorses dugouts notornis epitaphs pearls tithes waters orbits gifts "
    "sheaves depths sentiments decoys realms pains grouches escapades "
    "sleep wake are cajole haggle nag use boost affix detect integrate "
    "maintain nod was lose sublate solve thrash promise engage hinder "
    "print x-ray breach eat grow impress mold poach serve run dazzle "
    "snooze doze unwind kindle play hang believe doubt furious sly careful "
    "blithe quick fluffy slow quiet ruthless thin close dogged daring "
    "brave stealthy permanent enticing idle busy regular final ironic "
    "even bold silent sometimes always never furiously slyly carefully "
    "blithely quickly fluffily slowly quietly ruthlessly thinly closely "
    "doggedly daringly bravely stealthily permanently enticingly idly "
    "busily regularly finally ironically evenly boldly silently about "
    "above according to across after against along alongside of among "
    "around at atop before behind beneath beside besides between beyond "
    "by despite during except for from in place of inside instead of into "
    "near of on outside over past since through throughout to toward "
    "under until up upon without with within . , ; : ? ! --").split()
_POOL_BYTES = 1 << 20


@functools.lru_cache(maxsize=1)
def _pool() -> np.ndarray:
    """A megabyte of pseudo-text, the same in every run and process, as
    dbgen's own pool is; a comment is a piece of it."""
    rng = np.random.default_rng(19950617)
    words = np.array(_WORDS)[rng.integers(0, len(_WORDS), _POOL_BYTES // 4)]
    text = " ".join(words.tolist()).encode()[:_POOL_BYTES]
    return np.frombuffer(text, np.uint8)


def strings(codes: np.ndarray, values: np.ndarray):
    """The Arrow string array `values[codes]`."""
    import pyarrow as pa

    return pa.DictionaryArray.from_arrays(
        pa.array(codes), pa.array(values)).cast(pa.string())


def comments(rng: np.random.Generator, n: int, shortest: int, longest: int):
    """`n` pieces of the pool, `shortest` to `longest` characters each,
    as an Arrow string array built from its two buffers."""
    import pyarrow as pa

    pool = _pool()
    lengths = rng.integers(shortest, longest + 1, n).astype(np.int32)
    starts = rng.integers(0, len(pool) - longest, n).astype(np.int32)
    offsets = np.zeros(n + 1, np.int32)
    np.cumsum(lengths, out=offsets[1:])
    take = np.repeat(starts - offsets[:-1], lengths)
    take += np.arange(offsets[-1], dtype=np.int32)
    return pa.StringArray.from_buffers(
        n, pa.py_buffer(offsets), pa.py_buffer(pool[take]))
