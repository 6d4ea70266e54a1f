"""The training feed, closed loop: each step waits for its batch.

The loop is ``repro.launch.train.train``'s, step for step: pull a batch
from the ``ThallusLoader``, ``device_put`` each array with the batch
sharding, run the donated jitted step, and read the step counter and the
token count back (the host syncs on every step, as ``train`` does). The
benchmark builds the same parts itself because ``train`` fixes its weights
(``PRNGKey(0)``) and its corpus, and gives no handle on the state.

Set-up runs the first ``checked_steps`` steps through this same loop on
the same state and reads them: each step's loss, the per-leaf norm of the
first gradient as AdamW took it (from Adam's first moment after one step,
m / (1 - beta1)), and the per-leaf norm of the parameters' change over
those steps, before the next step takes the state. The window then goes on
from there with the same state and step. After the window the state is
freed and the plain reference follows the same steps on the same rows.
"""
from __future__ import annotations

import time

import numpy as np

from bench import chip, harness, weights


def leaf_norms(tree, vocab: int):
    """Per-leaf L2 norms, traced (call inside ``jax.jit``); the embedding
    table over its first ``vocab`` rows: the program pads it with rows the
    reference does not have."""
    import jax
    import jax.numpy as jnp

    def norm(path, x):
        if path[-1].key == "embed":
            x = x[:vocab]
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    return jax.tree_util.tree_map_with_path(norm, tree)


def named(tree) -> dict:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(k.key for k in path): float(x) for path, x in flat}


def gap(prog: dict, ref: dict, keep=None) -> float:
    """The worst leaf's gap between the program's norm and the reference's,
    over the larger of that leaf's reference norm and the median leaf's."""
    names = [k for k in ref if keep is None or k in keep]
    median = float(np.median([ref[k] for k in names]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median) for k in names)


def moved_leaves(ref_grad: dict) -> set:
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's; the others move under Adam by round-off alone."""
    median = float(np.median(list(ref_grad.values())))
    return {k for k, v in ref_grad.items() if v >= 1e-3 * median}


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared: loss, first gradient and parameter change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                   ref["loss"]))
    return {"loss_gap": loss, "grad_gap": gap(prog["grad"], ref["grad"]),
            "change_gap": gap(prog["change"], ref["change"],
                              moved_leaves(ref["grad"]))}


def batch_rows(rows: np.ndarray, step: int, batch_seqs: int):
    """The corpus rows step ``step`` (from 1) takes, in scan order, and
    their labels (next token; the last position has none, -1)."""
    n = len(rows) // batch_seqs * batch_seqs
    lo = (step - 1) * batch_seqs % n
    tokens = rows[lo:lo + batch_seqs]
    labels = np.concatenate([tokens[:, 1:], np.full((len(tokens), 1), -1,
                                                    tokens.dtype)], axis=1)
    return tokens, labels


def token_mismatches(batches: list, rows: np.ndarray, batch_seqs: int) -> int:
    """Values of the device batches that are not the corpus rows of their
    step in scan order (tokens and labels)."""
    wrong = 0
    for step, batch in enumerate(batches, 1):
        want_t, want_l = batch_rows(rows, step, batch_seqs)
        for got, want in ((batch["tokens"], want_t),
                          (batch["labels"], want_l)):
            got = np.asarray(got)
            wrong += (int(np.sum(got != want)) if got.shape == want.shape
                      else want.size)
    return wrong


def run(session, limits: dict | None = None) -> dict:
    import jax
    from repro.models import make_rules, mesh_context

    cell, seed = session.cell, session.seed
    cfg, traffic = cell.config, cell.traffic
    kind = harness.kind_module(cfg)
    reference = harness.reference_module(cfg)
    limits = limits or cfg["limits"]
    dep = kind.build(cfg, traffic, seed, session.devices)
    rec = session.run_record
    vocab = weights.dims(cfg)["V"]
    b1 = cfg["train"]["optimizer"]["beta1"]
    checked = int(traffic["checked_steps"])
    state, loader = dep.state, dep.loader
    dep.state = None
    batches, losses = [], []
    prog = {"loss": losses}

    with dep.mesh, mesh_context(dep.mesh, make_rules(dep.arch, dep.mesh)):
        grad_norms = jax.jit(lambda m: leaf_norms(
            jax.tree.map(lambda x: x / (1 - b1), m), vocab))
        change_norms = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, kind.to_program_layout(
                weights.make(cfg, k), p)), vocab))
        data_iter = iter(loader)

        def one_step():
            nonlocal state, data_iter
            while True:
                with session.span("loader"):
                    try:
                        host_batch = next(data_iter)
                        break
                    except StopIteration:
                        loader.load_state_dict({"batch_offset": 0})
                        data_iter = iter(loader)
            with session.span("device_put"):
                batch = {k: jax.device_put(v, dep.bspec)
                         for k, v in host_batch.items()}
            with session.span("step"):
                state, metrics = dep.step_fn(state, batch)
            with session.span("sync"):        # train() reads both back
                int(state["step"])
                tokens = int(metrics["tokens"])
            batches.append(batch)
            return tokens, metrics

        # set-up: the checked steps, read through the same loop and state
        for k in range(1, checked + 1):
            _, metrics = one_step()
            losses.append(float(metrics["loss"]))
            if k == 1:
                prog["grad"] = named(grad_norms(state["opt"]["m"]))
        prog["change"] = named(change_norms(state["params"],
                                            weights.seed_key(seed)))

        rec.counters.clear()
        with session.window():
            t0 = time.perf_counter()
            deadline = t0 + session.seconds
            while True:
                tokens, _ = one_step()
                rec.add("tokens", tokens)
                rec.add("steps", 1)
                if time.perf_counter() >= deadline:
                    break
            rec.window_s = time.perf_counter() - t0

    peak = chip.memory_peak(session.devices)
    rows = dep.rows
    del state, dep                  # the reference runs on a freed chip
    wrong = token_mismatches(batches, rows, traffic["batch_seqs"])
    del batches
    steps = [batch_rows(rows, k, traffic["batch_seqs"])
             for k in range(1, checked + 1)]
    ref = reference.readings(cfg, seed, steps)
    got = numbers(prog, ref)
    session.checks.add("token_mismatches", wrong, 0)
    for name, value in got.items():
        session.checks.add(name, value, limits[name])
    return {"attempted": int(rec.counters["steps"]), "failed": 0,
            "memory_peak_bytes": peak, "program": prog, "reference": ref,
            "numbers": got}

