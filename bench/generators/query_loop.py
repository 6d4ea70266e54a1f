"""Closed-loop queries from one client, each landed in HBM as it arrives.

The traffic file lists query templates. A template's ``draw`` gives
integer ranges (inclusive) and ``bind`` derives the SQL's parameters from
them; every combination of the draws is one query, so every seed sends the
same set of queries, each seed in its own order (a seeded permutation,
repeated for as long as the window lasts).

One query: ``ThallusClient.run_query`` with a sink that lands each batch
through ``batch_to_device`` as it arrives and hands it to the mix's
consumer, a jitted device operation over the landed columns; the query
ends when every landed column and every consumer result is ready on the
device. Latency runs from submit to then.

A jitted operation compiles once for each input shape, and a filter's
batches have as many row counts as it has batches. So the sink pads each
batch's columns with zero rows to a power of two (at least 1024 rows, at
most the batch size) before landing it, as a consumer on the chip has to.
Zero rows add nothing to either consumer, which therefore takes the landed
arrays alone. The window then meets only shapes that set-up has warmed.
Landed bytes count the result's rows only.

Consumers (``consumer.op`` in the traffic file), each in uint32
arithmetic that wraps, so the result is exact and needs no mask:

* ``digest``: per column, the sum of its values and the sum of value *
  row, row counted within the batch; the host shifts each batch's second
  sum by the rows before it, which gives a checksum of the whole answer
  in scan order that does not depend on where the batches split;
* ``revenue``: the sum of the product of two columns, as two partial sums
  of the product's high and low bits.

Correctness, once the window has closed: every query's consumer result
against the plain reference's, and the answers of a seeded sample of the
window's queries (``keep_every``), read back from the device and compared
value for value.
"""
from __future__ import annotations

import ast
import datetime
import itertools
import time

import numpy as np

from bench import chip, harness


# ---------------------------------------------------------------------------
# the mix
# ---------------------------------------------------------------------------


def _days(y: int, m: int, d: int) -> int:
    return (datetime.date(y, m, d) - datetime.date(1970, 1, 1)).days


_FUNCS = {"days": _days}
_BINOPS = {ast.Add: lambda a, b: a + b, ast.Sub: lambda a, b: a - b,
           ast.Mult: lambda a, b: a * b}


def evaluate(expr: str, names: dict) -> int:
    """Integer arithmetic over the drawn names: + - * and ``days(y, m, d)``
    (days since 1970-01-01)."""
    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, int):
            return node.value
        if isinstance(node, ast.Name):
            return names[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _BINOPS:
            return _BINOPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id in _FUNCS):
            return _FUNCS[node.func.id](*map(ev, node.args))
        raise ValueError(f"not an expression of the mix: {expr!r}")
    return ev(ast.parse(expr, mode="eval"))


def expand(template: dict) -> list[str]:
    draws = template.get("draw", {})
    keys = list(draws)
    out = []
    for combo in itertools.product(*(range(lo, hi + 1)
                                     for lo, hi in draws.values())):
        names = dict(zip(keys, combo))
        params = {k: evaluate(e, names)
                  for k, e in template.get("bind", {}).items()}
        out.append(template["sql"].format(**params))
    return out


def plan(traffic: dict, seed: int) -> list[str]:
    """The queries of one cycle of the mix, in this seed's order."""
    queries = [q for t in traffic["queries"] for q in expand(t)]
    order = np.random.default_rng([seed, 7]).permutation(len(queries))
    return [queries[i] for i in order]


# ---------------------------------------------------------------------------
# timed layers
# ---------------------------------------------------------------------------


class TimedReader:
    def __init__(self, reader, run: harness.Run, session):
        self.reader, self.run, self.session = reader, run, session
        self.schema = reader.schema

    def read_next(self):
        t = time.perf_counter()
        with self.session.span("engine"):
            batch = self.reader.read_next()
        self.run.add("engine_s", time.perf_counter() - t)
        return batch


class TimedEngine:
    """The program's engine, with a span around each call into it."""

    def __init__(self, engine, run: harness.Run, session):
        self.engine, self.run, self.session = engine, run, session

    def execute(self, sql: str, dataset: str):
        t = time.perf_counter()
        with self.session.span("engine"):
            reader = self.engine.execute(sql, dataset)
        self.run.add("engine_s", time.perf_counter() - t)
        return TimedReader(reader, self.run, self.session)


def measured_transport_s(stats) -> float:
    """The transport's measured host work for one batch: buffer
    allocation, the placement copy and batch assembly. The modeled wire
    time (``total_s``, ``modeled_wire_s``) is never read."""
    return stats.alloc_s + stats.wire.measured_copy_s + stats.deserialize_s


def bucket(rows: int, cap: int) -> int:
    size = 1024
    while size < rows:
        size *= 2
    return min(size, cap)


def padded(batch, rows: int):
    from repro.core.recordbatch import batch_from_arrays

    if rows == batch.num_rows:
        return batch
    return batch_from_arrays(batch.schema, [
        np.pad(c.values, (0, rows - batch.num_rows)) for c in batch.columns])


LOW_BITS = 14


def _digest(columns):
    import jax.numpy as jnp

    r = jnp.arange(columns[0].shape[0], dtype=jnp.uint32)
    return jnp.stack([jnp.stack([jnp.sum(c.astype(jnp.uint32),
                                         dtype=jnp.uint32),
                                 jnp.sum(c.astype(jnp.uint32) * r,
                                         dtype=jnp.uint32)])
                      for c in columns])


def _revenue(columns):
    import jax.numpy as jnp

    a, b = columns
    prod = a.astype(jnp.uint32) * b.astype(jnp.uint32)
    return jnp.stack([jnp.sum(prod >> LOW_BITS, dtype=jnp.uint32),
                      jnp.sum(prod & ((1 << LOW_BITS) - 1), dtype=jnp.uint32)])


CONSUMERS = {"digest": _digest, "revenue": _revenue}
WRAP = 1 << 32


def digest_host(answer: dict) -> list[int]:
    """``digest`` of a whole answer, in numpy: per column, the sum of its
    values and of value * row, mod 2**32."""
    out = []
    for values in answer.values():
        v = values.astype(np.uint64)
        r = np.arange(len(v), dtype=np.uint64)
        out += [int(v.sum() % WRAP), int((v * r % WRAP).sum() % WRAP)]
    return out


def combine(op: str, parts: list, rows: list) -> list[int]:
    """A query's consumer result from its batches' partial results."""
    if not parts:
        return []
    if op == "digest":
        total = np.zeros(np.shape(parts[0]), dtype=object)
        offset = 0
        for part, n in zip(parts, rows):
            s0, s1 = (part[:, k].astype(object) for k in (0, 1))
            total += np.stack([s0, s1 + offset * s0], axis=1)
            offset += n
        return [int(x) % WRAP for x in total.reshape(-1)]
    hi, lo = np.sum(np.stack(parts).astype(np.uint64), axis=0)
    return [int(hi) * (1 << LOW_BITS) + int(lo)]


def expected(op: str, consumer: dict, answer: dict) -> list[int]:
    if op == "digest":
        return digest_host(answer)
    a, b = (answer[c].astype(np.int64) for c in consumer["columns"])
    return [int(np.sum(a * b))]


class Lander:
    """The sink: pads, lands through ``batch_to_device`` and starts the
    consumer on each batch."""

    def __init__(self, traffic: dict, batch_rows: int, run: harness.Run,
                 session):
        import jax

        self.consumer = traffic["consumer"]
        self.op = jax.jit(CONSUMERS[self.consumer["op"]])
        self.cap, self.run, self.session = batch_rows, run, session

    def warm(self, dtypes: dict) -> None:
        """Compile the consumer, for one query's columns (name -> dtype),
        at every bucket the window can meet."""
        import jax
        import jax.numpy as jnp

        rows = 1024
        while True:
            size = min(rows, self.cap)
            cols = {n: jnp.zeros((size,), d) for n, d in dtypes.items()}
            jax.block_until_ready(self.op(self.inputs(cols)))
            if size == self.cap:
                return
            rows *= 2

    def inputs(self, columns: dict) -> tuple:
        names = self.consumer.get("columns", list(columns))
        return tuple(columns[n] for n in names)

    def query(self, server, sql: str, dataset: str):
        """Submit, land and consume every batch, wait until it is all on
        the device. Returns the landed (batch, rows) pairs, the consumer's
        partial results (on the device) and the latency."""
        import jax
        from repro.core import ThallusClient
        from repro.core.device_transport import batch_to_device

        run, span = self.run, self.session.span
        landed, parts = [], []

        def sink(batch):
            t = time.perf_counter()
            rows = batch.num_rows
            with span("h2d"):
                dev = batch_to_device(padded(batch, bucket(rows, self.cap)))
            run.add("h2d_s", time.perf_counter() - t)
            with span("consume"):
                parts.append(self.op(self.inputs(dev.columns)))
            landed.append((dev, rows))
            run.add("bytes_landed", sum(c.values.itemsize for c in
                                        batch.columns) * rows)

        t_submit = time.perf_counter()
        with span("query"):
            client = ThallusClient(server, sink=sink)
            client.run_query(sql, dataset)
            t = time.perf_counter()
            with span("ready"):
                jax.block_until_ready(([d.columns for d, _ in landed], parts))
            t_done = time.perf_counter()
        run.add("ready_s", t_done - t)
        run.add("transport_measured_s",
                sum(measured_transport_s(s) for s in client.stats))
        run.add("queries", 1)
        return landed, parts, t_done - t_submit


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def to_host(landed, host) -> dict[str, np.ndarray]:
    """An answer's columns read back (``host`` holds each landed batch's
    columns as numpy), each batch cut to its rows."""
    if not landed:
        return {}
    return {n: np.concatenate([cols[n][:rows] for cols, (_, rows)
                               in zip(host, landed)])
            for n in landed[0][0].columns}


def wrong_values(got: dict, want: dict) -> int:
    """Values that differ, bit for bit, between two answers; a missing or
    extra row counts once for each column."""
    wrong = 0
    for name, w in want.items():
        g = got.get(name)
        if g is None:
            wrong += len(w)
            continue
        n = min(len(g), len(w))
        same = (g[:n].dtype == w.dtype) and np.array_equal(
            g[:n].view(np.uint8).reshape(n, -1),
            w[:n].view(np.uint8).reshape(n, -1))
        if not same:
            wrong += int(np.sum(np.asarray(g[:n]) != w[:n])) or n
        wrong += abs(len(g) - len(w))
    return wrong


def compare(done, kept, deployment, reference, consumer: dict,
            control=None) -> dict:
    """The numbers compared. ``wrong_results``: queries whose consumer
    result differs from the reference's; ``wrong_values``: values of the
    kept answers that differ. With ``control`` (a list of decimal
    columns) the reference's own answers, narrowed as the control says,
    stand in for the program's."""
    import jax

    op = consumer["op"]
    memo: dict[tuple, object] = {}

    def once(kind, sql, make):
        """Each reference answer and result is worked out once per query."""
        if (kind, sql) not in memo:
            memo[kind, sql] = make()
        return memo[kind, sql]

    def want(sql):
        return once("answer", sql,
                    lambda: reference.answer(deployment.columns, sql))

    def narrowed(sql):
        return once("narrowed", sql,
                    lambda: reference.narrowed(want(sql), control))

    wrong_results = 0
    parts = jax.device_get([p for _, p, _ in done])
    for (sql, _, rows), got in zip(done, parts):
        truth = once("result", sql, lambda: expected(op, consumer, want(sql)))
        if control is not None:
            got_result = once("control", sql, lambda: expected(
                op, consumer, narrowed(sql)))
        else:
            got_result = combine(op, got, rows)
        wrong_results += got_result != truth
    wrong = 0
    hosts = jax.device_get([[d.columns for d, _ in landed]
                            for _, landed in kept])       # one read-back
    for (sql, landed), host in zip(kept, hosts):
        got = narrowed(sql) if control is not None else to_host(landed, host)
        wrong += wrong_values(got, want(sql))
    return {"wrong_results": wrong_results, "wrong_values": wrong,
            "answers_checked": len(kept), "results_checked": len(done)}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run(session, control=None) -> dict:
    from repro.core import Fabric, ThallusServer

    cell, seed = session.cell, session.seed
    traffic = cell.traffic
    kind = harness.kind_module(cell.config)
    reference = harness.reference_module(cell.config)
    deployment = kind.build(cell.config, seed)
    rec = session.run_record
    server = ThallusServer(TimedEngine(deployment.engine, rec, session),
                           Fabric())
    queries = plan(traffic, seed)
    lander = Lander(traffic, cell.config["batch_rows"], rec, session)

    # warm-up: every query template once, and the consumer at every bucket
    for template in traffic["queries"]:
        sql = expand(template)[0]
        names, _ = reference.parse(sql)
        lander.warm({n: deployment.columns[n].dtype for n in names})
        lander.query(server, sql, deployment.dataset)

    keep_every = int(traffic["keep_every"])
    # the first kept answer is one of the first three, so that a short
    # window still compares one
    offset = int(np.random.default_rng([seed, 11]).integers(min(keep_every,
                                                                 3)))
    kept, done = [], []
    rec.counters.clear()
    with session.window():
        t0 = time.perf_counter()
        deadline = t0 + session.seconds
        for i in itertools.count():
            sql = queries[i % len(queries)]
            landed, parts, latency = lander.query(server, sql,
                                                  deployment.dataset)
            rec.latencies_s.append(latency)
            done.append((sql, parts, [n for _, n in landed]))
            if i % keep_every == offset:
                kept.append((sql, landed))
            del landed
            if time.perf_counter() >= deadline:
                break
        rec.window_s = time.perf_counter() - t0
    peak = chip.memory_peak(session.devices)
    numbers = compare(done, kept, deployment, reference, traffic["consumer"],
                      control)
    session.checks.add("wrong_results", numbers["wrong_results"], 0)
    session.checks.add("wrong_values", numbers["wrong_values"], 0)
    session.checks.add("answers_unchecked",
                       int(numbers["answers_checked"] == 0), 0)
    return {"attempted": int(rec.counters["queries"]), "failed": 0,
            "memory_peak_bytes": peak, "numbers": numbers}
