"""The seeded TPC-H LINEITEM generator (bench/kinds/tpch_lineitem.py), at a
tiny scale: each column's type, range and derivation rule from clause
4.2.3 of the specification, and the same bytes from the same seed."""
from __future__ import annotations

import copy

import numpy as np
import pytest

from bench import harness

CONFIG = harness.load_json(harness.BENCH / "configs" / "tpch-lineitem-sf1.json")
KIND = harness.kind_module(CONFIG)


def tiny(orders: int = 3000) -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg["orders"] = orders
    return cfg


@pytest.fixture(scope="module")
def cols():
    return KIND.generate(tiny(), 2**31 + 17)


def test_columns_and_types(cols):
    assert list(cols) == [name for name, _ in CONFIG["columns"]]
    for name, dtype in CONFIG["columns"]:
        assert cols[name].dtype == np.dtype(dtype), name
    assert "l_comment" not in cols
    widths = sum(np.dtype(d).itemsize for _, d in CONFIG["columns"])
    assert widths == 48


def test_orders_and_lines(cols):
    key = cols["l_orderkey"].astype(np.int64)
    assert np.all(np.diff(key) >= 0)
    orders = np.unique(key)
    assert len(orders) == 3000
    # sparse keys: the first 8 of every 32
    assert np.all((orders - 1) % 32 < 8)
    _, counts = np.unique(key, return_counts=True)
    assert counts.min() >= 1 and counts.max() <= 7
    # line numbers run 1..count within each order
    first = np.r_[0, np.cumsum(counts)[:-1]]
    want = np.arange(len(key)) - np.repeat(first, counts) + 1
    np.testing.assert_array_equal(cols["l_linenumber"], want)


def test_keys_prices_and_money(cols):
    part = cols["l_partkey"].astype(np.int64)
    assert part.min() >= 1 and part.max() <= 200_000
    supp = cols["l_suppkey"].astype(np.int64)
    s = 10_000
    candidates = [(part + i * (s // 4 + (part - 1) // s)) % s + 1
                  for i in range(4)]
    assert np.all(np.any([supp == c for c in candidates], axis=0))
    qty = cols["l_quantity"]
    assert np.all(qty % 100 == 0)
    assert qty.min() >= 100 and qty.max() <= 5000
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1_000)
    np.testing.assert_array_equal(cols["l_extendedprice"],
                                  qty // 100 * retail)
    assert cols["l_discount"].min() >= 0 and cols["l_discount"].max() <= 10
    assert cols["l_tax"].min() >= 0 and cols["l_tax"].max() <= 8


def test_dates_and_flags(cols):
    start, end = KIND.days(CONFIG["startdate"]), KIND.days(CONFIG["enddate"])
    current = KIND.days(CONFIG["currentdate"])
    ship, commit = cols["l_shipdate"], cols["l_commitdate"]
    receipt = cols["l_receiptdate"]
    key = cols["l_orderkey"]
    # every line of an order must fit one order date in
    # [STARTDATE, ENDDATE - 151]: ship = date + [1, 121], commit =
    # date + [30, 90]
    first = np.r_[0, np.flatnonzero(np.diff(key)) + 1]
    lo = np.maximum.reduceat(np.maximum(np.maximum(ship - 121, commit - 90),
                                        start), first)
    hi = np.minimum.reduceat(np.minimum(np.minimum(ship - 1, commit - 30),
                                        end - 151), first)
    assert np.all(lo <= hi)
    assert np.all((receipt - ship >= 1) & (receipt - ship <= 30))
    flags = CONFIG["dictionaries"]["l_returnflag"]
    rf = np.array(flags)[cols["l_returnflag"]]
    assert np.all(rf[receipt > current] == "N")
    assert set(rf[receipt <= current]) <= {"R", "A"}
    status = np.array(CONFIG["dictionaries"]["l_linestatus"])[
        cols["l_linestatus"]]
    np.testing.assert_array_equal(status == "O", ship > current)
    assert cols["l_shipinstruct"].max() < 4
    assert cols["l_shipmode"].max() < 7


def test_same_seed_same_bytes():
    a = KIND.generate(tiny(500), 123)
    b = KIND.generate(tiny(500), 123)
    c = KIND.generate(tiny(500), 124)
    for name in a:
        assert a[name].tobytes() == b[name].tobytes()
    assert any(a[n].tobytes() != c[n].tobytes() for n in a)


def test_sf1_size():
    cfg = CONFIG
    assert cfg["orders"] == 1_500_000 * cfg["scale_factor"]
    # 1..7 lines per order, 4 on average: about 6.0M rows
    lo, hi = cfg["lines_per_order"]
    assert cfg["orders"] * (lo + hi) / 2 == 6_000_000
