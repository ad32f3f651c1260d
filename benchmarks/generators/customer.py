"""TPC-DS `customer`: the 18 columns of the specification, 500,000 rows
at scale factor 10, one file, drawn by dsdgen's rules as recalled (no
network here; the configuration's file lists each under `assumed`),
with numpy's random streams.

**The names.**  dsdgen draws `c_first_name` and `c_last_name` from two
frequency-weighted lists of some five thousand entries each, so many
customers share both, which is what makes a DISTINCT, an INTERSECT or
an EXCEPT over names do work.  The lists here are made by this code
and depend on no seed: `FIRST_NAMES`, 5,000 distinct names of 3 to 11
letters, and `LAST_NAMES`, 5,000 of 3 to 13, each spelt from syllables
by a fixed stream, in the order of their rank; the name of rank `r`
(from 0) is drawn with weight `1 / (r + 1)`, Zipf's law, about what a
census list of names follows.  Two customers share first and last name
with probability `COLLIDE` (the squares of the weights, summed, for
each list, and the two multiplied: 4.0e-4): the commonest name of a
list is borne by 11% of the customers who have one, the commonest pair
by some 5,600 of the 500,000, and the 465,000 customers with both names
bear some 224,000 distinct pairs.  A name is never padded in the file.

NULLs by dsdgen's rule (`_tpcds.py`): a row is picked with the table's
`nNullPct`, 7% here, and each nullable column of a picked row is
blanked with probability one half: 3.5% of every column but
`c_customer_sk` and `c_customer_id`.  So a NULL first name is as common
as the third name of the list, and whether NULL equals NULL decides
hundreds of triples.  `c_login` is NULL in every row, as dsdgen leaves
it.

Strings reach the plain reference as codes, -1 for NULL: a name's rank
in its list, an index into `SALUTATIONS`, `FLAGS` or `COUNTRIES`, the
number a business key is spelt from, the number of an e-mail address's
host.  **Every column has a random stream of its own**
(`default_rng([seed, id, index, stream])`), so `generate` draws only
the named columns, and the values do not depend on which were named.

Handed fewer rows than the table has (a rehearsal), it holds the
customers with the lowest keys; the channels still draw over all
500,000, so that share of their sales finds a customer.
"""

import numpy as np
import pyarrow as pa

from benchmarks.generators import _tpcds

CUSTOMER_ID = 16

#: rows picked for NULLs, in ten-thousandths (dsdgen's nNullPct)
NULL_PCT = 700
NAMES = 5_000  # entries in each list
FIRST_LETTERS = (3, 11)
LAST_LETTERS = (3, 13)

SALUTATIONS = np.array(["Mr.", "Mrs.", "Ms.", "Miss", "Sir", "Dr."])
FLAGS = np.array(["N", "Y"])
COUNTRIES = np.array(
    "AFGHANISTAN ALBANIA ALGERIA ANDORRA ANGOLA ARGENTINA ARMENIA "
    "AUSTRALIA AUSTRIA AZERBAIJAN BAHAMAS BAHRAIN BANGLADESH BARBADOS "
    "BELARUS BELGIUM BELIZE BENIN BERMUDA BHUTAN BOLIVIA BOTSWANA BRAZIL "
    "BULGARIA BURUNDI CAMBODIA CAMEROON CANADA CHILE CHINA COLOMBIA "
    "COMOROS CROATIA CUBA CYPRUS DENMARK DJIBOUTI DOMINICA ECUADOR EGYPT "
    "ERITREA ESTONIA ETHIOPIA FIJI FINLAND FRANCE GABON GAMBIA GEORGIA "
    "GERMANY GHANA GREECE GRENADA GUAM GUATEMALA GUINEA GUYANA HAITI "
    "HONDURAS HUNGARY ICELAND INDIA INDONESIA IRAQ IRELAND ISRAEL ITALY "
    "JAMAICA JAPAN JORDAN KAZAKHSTAN KENYA KIRIBATI KUWAIT KYRGYZSTAN "
    "LATVIA LEBANON LESOTHO LIBERIA LIECHTENSTEIN LITHUANIA LUXEMBOURG "
    "MADAGASCAR MALAWI MALAYSIA MALDIVES MALI MALTA MAURITANIA MAURITIUS "
    "MEXICO MOLDOVA MONACO MONGOLIA MOROCCO MOZAMBIQUE MYANMAR NAMIBIA "
    "NAURU NEPAL NETHERLANDS NICARAGUA NIGER NIGERIA NORWAY OMAN PAKISTAN "
    "PALAU PANAMA PARAGUAY PERU PHILIPPINES POLAND PORTUGAL QATAR ROMANIA "
    "RWANDA SAMOA SENEGAL SEYCHELLES SINGAPORE SLOVAKIA SLOVENIA SOMALIA "
    "SPAIN SUDAN SURINAME SWAZILAND SWEDEN SWITZERLAND TAJIKISTAN "
    "TANZANIA THAILAND TOGO TONGA TUNISIA TURKEY TURKMENISTAN TUVALU "
    "UGANDA UKRAINE URUGUAY UZBEKISTAN VANUATU VENEZUELA YEMEN ZAMBIA "
    "ZIMBABWE".split())
_TLDS = np.array(["com", "org", "edu"])
HOSTS = 10_000  # an address's host: a word of its number, and a TLD

#: bytes one row of each column takes on the device, in the
#: specification's order: a surrogate key 8, a day, month or year 4, a
#: CHAR(n) or VARCHAR(n) its declared n
COLUMN_BYTES = {
    "c_customer_sk": 8, "c_customer_id": 16, "c_current_cdemo_sk": 8,
    "c_current_hdemo_sk": 8, "c_current_addr_sk": 8,
    "c_first_shipto_date_sk": 8, "c_first_sales_date_sk": 8,
    "c_salutation": 10, "c_first_name": 20, "c_last_name": 30,
    "c_preferred_cust_flag": 1, "c_birth_day": 4, "c_birth_month": 4,
    "c_birth_year": 4, "c_birth_country": 20, "c_login": 13,
    "c_email_address": 50, "c_last_review_date_sk": 8,
}
_STREAM = {name: at for at, name in enumerate(COLUMN_BYTES)}
_PICKED = 100
_NEVER_NULL = ("c_customer_sk", "c_customer_id")

_ONSETS = ("b c d f g h j k l m n p r s t v w z br ch cl dr fr gr kr pl "
           "sh sl st th tr").split()
_VOWELS = "a e i o u y ai ea ee ia io oo ou".split()
_CODAS = [""] * 6 + "l m n r s t ck ll nd ng rd rt ss th".split()


def _spelt(count: int, letters: tuple, stream: int) -> np.ndarray:
    """`count` distinct capitalised names of `letters[0]` to
    `letters[1]` letters, spelt from syllables by a stream that depends
    on nothing but `stream`: the list is part of the code."""
    rng = np.random.default_rng([20_380, stream])
    names: dict = {}
    while len(names) < count:
        picks = rng.integers(0, [len(_ONSETS), len(_VOWELS), len(_CODAS)],
                             (4, 3))
        want = int(rng.integers(letters[0], letters[1] + 1))
        word = "".join(_ONSETS[o] + _VOWELS[v] + _CODAS[c]
                       for o, v, c in picks)[:want]
        if len(word) >= letters[0]:
            names.setdefault(word.capitalize(), None)
    return np.array(list(names))


FIRST_NAMES = _spelt(NAMES, FIRST_LETTERS, 1)
LAST_NAMES = _spelt(NAMES, LAST_LETTERS, 2)
#: the weight of each rank, for both lists: Zipf's law
WEIGHTS = 1.0 / np.arange(1, NAMES + 1)
WEIGHTS /= WEIGHTS.sum()
#: the probability that two customers who have both names share both
COLLIDE = float((WEIGHTS ** 2).sum()) ** 2


def _rng(seed: int, index: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, CUSTOMER_ID, index, stream])


def _first_sale(rng: np.random.Generator, rows: int) -> np.ndarray:
    """`c_first_sales_date_sk`: a day of the five sales years."""
    return rng.integers(_tpcds.SALES_FIRST_DAY, _tpcds.SALES_LAST_DAY + 1,
                        rows) + _tpcds.EPOCH_SK


def _draw(name: str, rng: np.random.Generator, rows: int, seed: int,
          index: int) -> np.ndarray:
    """One column, NULLs not yet in."""
    if name in ("c_customer_sk", "c_customer_id"):
        return np.arange(1, rows + 1, dtype=np.int64)
    if name in ("c_first_name", "c_last_name"):
        return rng.choice(NAMES, rows, p=WEIGHTS).astype(np.int32)
    if name == "c_first_sales_date_sk":
        return _first_sale(rng, rows)
    if name == "c_first_shipto_date_sk":
        # the first sale, drawn again from its own stream, shipped
        # within a month
        sold = _first_sale(
            _rng(seed, index, _STREAM["c_first_sales_date_sk"]), rows)
        return sold + rng.integers(0, 31, rows)
    if name == "c_last_review_date_sk":
        return rng.integers(_tpcds.SALES_LAST_DAY - 365,
                            _tpcds.SALES_LAST_DAY + 1, rows) + _tpcds.EPOCH_SK
    if name == "c_login":
        return np.full(rows, -1, np.int32)
    top = {"c_current_cdemo_sk": _tpcds.CUSTOMER_DEMOGRAPHICS,
           "c_current_hdemo_sk": _tpcds.HOUSEHOLD_DEMOGRAPHICS,
           "c_current_addr_sk": _tpcds.ADDRESSES}.get(name)
    if top is not None:
        return rng.integers(1, top + 1, rows)
    low, high = {"c_salutation": (0, len(SALUTATIONS)),
                 "c_preferred_cust_flag": (0, len(FLAGS)),
                 "c_birth_country": (0, len(COUNTRIES)),
                 "c_birth_day": (1, 29), "c_birth_month": (1, 13),
                 "c_birth_year": (1924, 1993),
                 "c_email_address": (0, HOSTS)}[name]
    return rng.integers(low, high, rows).astype(np.int32)


def generate(seed: int, index: int, rows: int, columns=None) -> dict:
    """The table's first `rows` rows as numpy arrays, the named columns
    only (all 18 where none is named), for the plain reference: numbers
    as they are, strings as codes; NULL is -1."""
    wanted = list(COLUMN_BYTES) if columns is None else list(columns)
    picked = _rng(seed, index, _PICKED).integers(0, 10_000, rows) < NULL_PCT
    out = {}
    for name in wanted:
        rng = _rng(seed, index, _STREAM[name])
        values = _draw(name, rng, rows, seed, index)
        if name not in _NEVER_NULL:
            values = _tpcds.blanked(values, picked & (
                rng.integers(0, 2, rows, dtype=np.uint8) == 1))
        out[name] = values
    return out


def _addresses(cols: dict) -> pa.Array:
    """`First.Last@host.tld`, as dsdgen spells it; NULL where the draw
    was blanked, and a NULL name is left out of the address."""
    host = cols["c_email_address"]
    first = np.where(cols["c_first_name"] < 0, "",
                     FIRST_NAMES[np.maximum(cols["c_first_name"], 0)])
    last = np.where(cols["c_last_name"] < 0, "",
                    LAST_NAMES[np.maximum(cols["c_last_name"], 0)])
    spelt = np.char.add(np.char.add(np.char.add(first, "."), last), "@")
    hosts = _tpcds.words(np.maximum(host, 0))
    tld = _TLDS[np.maximum(host, 0) % len(_TLDS)]
    spelt = np.char.add(np.char.add(np.char.add(spelt, hosts), "."), tld)
    return pa.array(spelt, mask=host == -1)


def to_arrow(cols: dict, seed: int, index: int) -> pa.Table:
    out = {name: _tpcds.arrow(cols[name]) for name in COLUMN_BYTES}
    out.update({
        "c_customer_id": pa.array(
            _tpcds.business_ids(cols["c_customer_id"])),
        "c_salutation": _tpcds.strings(cols["c_salutation"], SALUTATIONS),
        "c_first_name": _tpcds.strings(cols["c_first_name"], FIRST_NAMES),
        "c_last_name": _tpcds.strings(cols["c_last_name"], LAST_NAMES),
        "c_preferred_cust_flag": _tpcds.strings(
            cols["c_preferred_cust_flag"], FLAGS),
        "c_birth_country": _tpcds.strings(cols["c_birth_country"],
                                          COUNTRIES),
        "c_login": pa.nulls(len(cols["c_login"]), pa.string()),
        "c_email_address": _addresses(cols),
    })
    return pa.table({name: out[name] for name in COLUMN_BYTES})
