"""TPC-DS query 87 (`query87.tpl`), DMS = 1200, as the specification
writes it: query 38's three DISTINCTs of (last name, first name, date),
the store channel's EXCEPT the catalog channel's EXCEPT the web
channel's, and how many triples are left: the customers of a day who
bought through the store alone.  No LIMIT.

`build` is the query through the DataFrame API: `q38.py`'s channels,
and `DataFrame.subtract`, EXCEPT DISTINCT, lowered as Spark lowers it:
a `left_anti` join of the three columns by position, every key `<=>`.
A NULL name is a value of the triple and equals itself (`q38.py` says
what a NULL is at each step): a store triple with a NULL name that the
catalog channel holds too is taken away, which `=` would keep.

The plain reference is `q38.py`'s, by import: the same partials, and
`combine` counts the store triples that neither side's set holds.
"""

from benchmarks.queries.q38 import (  # noqa: F401
    ANSWER,
    COLUMNS,
    DMS,
    DRIVER,
    ORDERED,
    answer,
    channels,
    partial,
    united,
)


def build(session, frames):
    from spark_rapids_tpu.session import count_star

    store, catalog, web = channels(session, frames)
    return store.subtract(catalog).subtract(web).agg(
        (count_star(), ANSWER))


def combine(partials: list):
    _, in_catalog, in_web, _ = united(partials)
    return answer(int((~in_catalog & ~in_web).sum()))
