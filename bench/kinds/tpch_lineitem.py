"""TPC-H LINEITEM, generated from the seed by the rules of the TPC-H
specification (3.0.1, clause 4.2.3), served by one ThallusServer over the
query engine.

Storage follows Arrow's fixed-width types: decimals as int32 hundredths
(the unscaled integer of a decimal(15,2)), dates as int32 days since
1970-01-01 (date32), and the char columns as uint8 codes into the sorted
dictionaries the configuration lists. ``l_comment`` is left out (the device
transport carries fixed-width columns only).
"""
from __future__ import annotations

import dataclasses
import datetime

import numpy as np

EPOCH = datetime.date(1970, 1, 1)


def days(iso: str) -> int:
    return (datetime.date.fromisoformat(iso) - EPOCH).days


@dataclasses.dataclass
class Deployment:
    engine: object            # the program's query engine, table registered
    dataset: str
    table: str
    columns: dict             # column name -> numpy array, in scan order
    nbytes: int


def generate(cfg: dict, seed: int) -> dict[str, np.ndarray]:
    """All LINEITEM columns of ``cfg`` (in the configuration's order) as
    numpy arrays, one row per line, orders in key order."""
    rng = np.random.default_rng([seed, 4, 2, 3])
    sf = cfg["scale_factor"]
    n_orders = cfg["orders"]
    lo_lines, hi_lines = cfg["lines_per_order"]
    start, end = days(cfg["startdate"]), days(cfg["enddate"])
    current = days(cfg["currentdate"])
    dicts = cfg["dictionaries"]

    i = np.arange(n_orders, dtype=np.int64)
    orderkey = (i // 8) * 32 + (i % 8) + 1          # sparse keys, 8 of 32
    orderdate = rng.integers(start, end - 151 + 1, n_orders)
    nlines = rng.integers(lo_lines, hi_lines + 1, n_orders)
    rows = int(nlines.sum())
    first = np.repeat(np.cumsum(nlines) - nlines, nlines)
    odate = np.repeat(orderdate, nlines)

    parts, supps = sf * 200_000, sf * 10_000
    partkey = rng.integers(1, parts + 1, rows)
    supp_i = rng.integers(0, 4, rows)
    suppkey = (partkey + supp_i * (supps // 4 + (partkey - 1) // supps)) \
        % supps + 1
    quantity = rng.integers(1, 51, rows)
    retail = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1_000)
    shipdate = odate + rng.integers(1, 122, rows)
    commitdate = odate + rng.integers(30, 91, rows)
    receiptdate = shipdate + rng.integers(1, 31, rows)
    returned = rng.integers(0, 2, rows).astype(bool)     # "R" or "A"
    flags = dicts["l_returnflag"]
    returnflag = np.where(receiptdate <= current,
                          np.where(returned, flags.index("R"),
                                   flags.index("A")),
                          flags.index("N"))
    status = dicts["l_linestatus"]
    linestatus = np.where(shipdate > current, status.index("O"),
                          status.index("F"))
    values = {
        "l_orderkey": np.repeat(orderkey, nlines),
        "l_partkey": partkey,
        "l_suppkey": suppkey,
        "l_linenumber": np.arange(rows) - first + 1,
        "l_quantity": quantity * 100,
        "l_extendedprice": quantity * retail,
        "l_discount": rng.integers(0, 11, rows),
        "l_tax": rng.integers(0, 9, rows),
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
        "l_shipinstruct": rng.integers(0, len(dicts["l_shipinstruct"]), rows),
        "l_shipmode": rng.integers(0, len(dicts["l_shipmode"]), rows),
    }
    return {name: np.ascontiguousarray(values[name], dtype=dtype)
            for name, dtype in cfg["columns"]}


def build(cfg: dict, seed: int) -> Deployment:
    from repro.core.recordbatch import batch_from_arrays
    from repro.core.schema import schema as make_schema
    from repro.engine import Engine, Table

    columns = generate(cfg, seed)
    sch = make_schema(*[tuple(c) for c in cfg["columns"]])
    table = Table(cfg["table"], sch)
    rows = len(next(iter(columns.values())))
    step = cfg["batch_rows"]
    for lo in range(0, rows, step):
        table.append(batch_from_arrays(
            sch, [a[lo:lo + step] for a in columns.values()]))
    engine = Engine()
    dataset = f"/data/{cfg['table']}"
    engine.register(dataset, table)
    return Deployment(engine, dataset, cfg["table"], columns,
                      sum(a.nbytes for a in columns.values()))
