"""CPU rehearsal of every traffic generator through the harness's own
functions, at tiny sizes: the result line's fields, and ``correct`` coming
out false when the timed path is broken underneath (an answer or a token
altered where it is produced, half of a batch left out, a step that
returns its state unchanged) or when the control stands in for the
program. The run command itself refuses the CPU, which is checked too."""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import chip, harness

FEED = "feed.granite-3-2b.4x2048"
PROJECTION = "scan.lineitem-sf1.projection"
Q6 = "scan.lineitem-sf1.q6"
SEED = 2**31 + 4242


@pytest.fixture
def session(monkeypatch):
    """Builds a Session on the CPU: peaks from the v5e row, no compile
    cache (the tests share a process with others)."""
    import jax

    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    monkeypatch.setattr(harness, "peaks_for",
                        lambda kind: peaks["TPU v5 lite"])
    monkeypatch.setattr(chip, "enable_compile_cache", lambda: None)

    def make(cell, seconds=0.3, trace=False):
        return chip.Session(cell, SEED, seconds, trace, jax.devices()[:1],
                            time.perf_counter())
    return make


def tiny_scan(name: str) -> harness.Cell:
    cell = harness.find_cell(name)
    cell.config.update(orders=3000, batch_rows=2048)
    return cell


@pytest.fixture
def tiny_feed(monkeypatch):
    """The feed cell at a CPU size: the program's granite config reduced
    (GQA kept), the configuration file cut to the same sizes."""
    import repro.configs

    arch = dataclasses.replace(
        repro.configs.get_config("granite-3-2b").reduced(), num_layers=2,
        num_kv_heads=2)
    monkeypatch.setattr(repro.configs, "get_config", lambda name: arch)
    cell = harness.find_cell(FEED)
    cell.config.update(hidden_size=arch.d_model, intermediate_size=arch.d_ff,
                       num_attention_heads=arch.num_heads,
                       num_key_value_heads=arch.num_kv_heads,
                       num_hidden_layers=arch.num_layers,
                       vocab_size=arch.vocab_size,
                       attention_multiplier=arch.resolved_head_dim ** -0.5)
    cell.config["program"]["num_layers"] = arch.num_layers
    cell.traffic.update(seq_len=32)
    cell.traffic["corpus"].update(num_seqs=128)
    return cell


def check_line(line: str, cell: harness.Cell) -> dict:
    out = json.loads(line)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"]: m["unit"] for m in cell.end_to_end}
    assert {k: v["unit"] for k, v in out["metrics"].items()} == want
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for check in out["checks"].values():
        assert set(check) == {"value", "limit"}
    return out


@pytest.mark.parametrize("name", [PROJECTION, Q6])
def test_scan_line(session, name):
    cell = tiny_scan(name)
    line, checks = session(cell).run()
    out = check_line(line, cell)
    assert out["correct"] and checks.correct
    assert out["checks"]["wrong_results"]["value"] == 0


def test_feed_line(session, tiny_feed):
    line, _ = session(tiny_feed).run()
    out = check_line(line, tiny_feed)
    assert out["correct"]
    assert out["checks"]["token_mismatches"]["value"] == 0


def altered(generator, monkeypatch):
    """One value of each batch's first column changed where the batch is
    produced for the device."""
    real = generator.padded

    def pad(batch, rows):
        from repro.core.recordbatch import batch_from_arrays

        arrays = [c.values.copy() for c in batch.columns]
        arrays[0][0] += 1
        return real(batch_from_arrays(batch.schema, arrays), rows)
    monkeypatch.setattr(generator, "padded", pad)


def half_rows(generator, monkeypatch):
    """Half of each batch's rows left out."""
    real = generator.padded
    monkeypatch.setattr(generator, "padded", lambda batch, rows: real(
        batch.slice(0, batch.num_rows // 2), rows))


@pytest.mark.parametrize("name", [PROJECTION, Q6])
@pytest.mark.parametrize("fault", [altered, half_rows])
def test_scan_fault_is_not_correct(session, monkeypatch, name, fault):
    cell = tiny_scan(name)
    generator = harness.generator_module(cell.traffic)
    fault(generator, monkeypatch)
    s = session(cell)
    generator.run(s)
    assert not s.checks.correct
    assert s.checks.items["wrong_results"]["value"] > 0


@pytest.mark.parametrize("name", [PROJECTION, Q6])
def test_scan_control_is_not_correct(session, name):
    cell = tiny_scan(name)
    s = session(cell)
    out = harness.generator_module(cell.traffic).run(
        s, control=cell.config["decimals"])
    assert not s.checks.correct
    assert out["numbers"]["wrong_values"] > 0


def unchanged_state(make_train_step):
    def make(cfg, tcfg):
        step = make_train_step(cfg, tcfg)

        def frozen(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return frozen
    return make


def half_batch(make_train_step):
    def make(cfg, tcfg):
        step = make_train_step(cfg, tcfg)

        def half(state, batch):
            rows = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:rows] for k, v in batch.items()})
        return half
    return make


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_feed_step_fault_is_not_correct(session, tiny_feed, monkeypatch,
                                        fault):
    import repro.training

    monkeypatch.setattr(repro.training, "make_train_step",
                        fault(repro.training.make_train_step))
    _, checks = session(tiny_feed).run()
    assert not checks.correct


def test_feed_token_fault_is_not_correct(session, tiny_feed, monkeypatch):
    from repro.data import loader as loader_mod

    real = loader_mod.ThallusLoader.__iter__

    def altered_iter(self):
        for i, batch in enumerate(real(self)):
            if i == 1:
                batch = dict(batch, tokens=batch["tokens"].copy())
                batch["tokens"][0, 5] += 1
            yield batch

    monkeypatch.setattr(loader_mod.ThallusLoader, "__iter__", altered_iter)
    _, checks = session(tiny_feed).run()
    assert not checks.correct
    assert checks.items["token_mismatches"]["value"] > 0


def test_feed_control_fails_a_limit(tiny_feed):
    """The control (the reference in bfloat16) against the float32
    reference, at the CPU size, reads above at least one of the cell's
    limits."""
    from bench import control

    limits = tiny_feed.config["limits"]
    got = control.feed_readings(tiny_feed, SEED)["control_bf16"]
    assert any(got[k] > limits[k] for k in limits), (got, limits)


def run_command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", PROJECTION, "--seed",
         str(SEED), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_the_cpu():
    done = run_command(harness.ROOT)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_run_fails_with_only_the_benchmark(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = run_command(tmp_path)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
