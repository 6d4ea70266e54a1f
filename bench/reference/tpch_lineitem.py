"""Plain reference for queries over the generated LINEITEM: the same
SELECT, evaluated with numpy over the whole columns, sharing no code with
the program's engine.

It reads the one query shape the traffic mixes send:

    SELECT c1, c2, ... FROM <table> [WHERE <col> <op> <int> [AND ...]]

A WHERE clause is a conjunction of comparisons of a column with an integer.
"""
from __future__ import annotations

import operator
import re

import numpy as np

_OPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
        ">=": operator.ge, "=": operator.eq}
_QUERY = re.compile(r"^\s*SELECT\s+(?P<cols>.+?)\s+FROM\s+(?P<table>\w+)"
                    r"(?:\s+WHERE\s+(?P<where>.+?))?\s*$", re.I | re.S)
_TERM = re.compile(r"^\s*(\w+)\s*(<=|>=|<|>|=)\s*(-?\d+)\s*$")


def parse(sql: str) -> tuple[list[str], list[tuple[str, str, int]]]:
    m = _QUERY.match(sql)
    if m is None:
        raise ValueError(f"not a query the reference reads: {sql!r}")
    cols = [c.strip() for c in m["cols"].split(",")]
    terms = []
    if m["where"]:
        for term in re.split(r"\s+AND\s+", m["where"], flags=re.I):
            t = _TERM.match(term)
            if t is None:
                raise ValueError(f"not a term the reference reads: {term!r}")
            terms.append((t[1], t[2], int(t[3])))
    return cols, terms


def answer(columns: dict, sql: str) -> dict[str, np.ndarray]:
    """The query's result columns, rows in scan order."""
    cols, terms = parse(sql)
    if not terms:
        return {c: columns[c] for c in cols}
    mask = np.ones(len(columns[cols[0]]), bool)
    for col, op, value in terms:
        mask &= _OPS[op](columns[col].astype(np.int64), value)
    return {c: columns[c][mask] for c in cols}


def narrowed(result: dict, decimals: list[str]) -> dict[str, np.ndarray]:
    """The control: the reference's answer with its decimal columns held
    in bfloat16, the two-byte type that would halve their bytes to HBM."""
    import ml_dtypes

    out = {}
    for name, values in result.items():
        if name in decimals:
            values = values.astype(np.float32).astype(ml_dtypes.bfloat16) \
                .astype(np.float32).astype(values.dtype)
        out[name] = values
    return out
