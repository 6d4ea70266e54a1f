"""Bring-up check: the Thallus main path, end to end, on one TPU.

    python chip_smoke.py              # one chip: scan, feed, kernels
    python chip_smoke.py --chips 4    # four chips: sharded feed step and
                                      # sharded columns, against one chip

Each phase prints one JSON line with its own numbers (seconds, XLA backend
compile seconds, bytes landed in HBM, peak device bytes); the last line is
``{"ok": true, "device": {...}}``. Any failed check exits nonzero before
that line is printed. There is no CPU fallback: without a TPU the script
exits nonzero at once. Everything runs in this one process, which holds the
chip(s) for its whole life.

Phases (one chip):

* ``scan`` — a seeded 2^25-row x 8 float32 table (1 GiB) behind a
  ThallusServer; ``SELECT c0, ..., c7`` through ThallusClient into
  ``batch_to_device`` (columns stay resident) and through RpcClient into
  ``batch_to_device_packed``; every column compared bit for bit with the
  table's host batches.
* ``feed`` — ``repro.launch.train``'s own loop (ThallusLoader -> device_put
  -> donated, jitted step) at granite-3-2b's published widths, depth cut as
  ``FEED_LAYERS`` says; the device tokens are checked against the token
  table's rows in scan order, every loss and grad norm must be finite, the
  first loss must sit within 0.5 of ln(vocab), and the loss must move by
  more than the four-chip comparison's bound.
* ``kernels`` — the Pallas kernels compiled (not interpreted) at one real
  size each, against their ``ref.py`` oracles.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

SEED = 0
# scan: 2^25 rows x 8 float32 columns = 1 GiB, in 256 batches of 2^17 rows
SCAN_ROWS = 1 << 25
SCAN_COLS = 8
SCAN_BATCH_ROWS = 1 << 17
# feed: granite-3-2b at its published widths (d_model 2048, 32 query / 8 KV
# heads of 64, d_ff 8192, vocab 49155); only the depth is cut. With f32
# params + Adam m, v and master (16 B/param) and remat=full at 4 x 2048
# tokens, the v5e compiler's memory_analysis() of the step gives 14.07 GiB
# for 8 layers and 15.62 GiB for 9, and refuses 10 (15.75 GiB of HBM). But
# the 8-layer step, compiled for one v5e by jax/libtpu 0.9.0/0.0.34, returns
# MLP gate/up gradients about 300 times too large (4 x 2048 tokens; 2 x 2048
# and 6 layers are right), so the cut is 6 of the 40 layers, the deepest
# whose one-chip step was seen to match a plain jax.grad. The feed checks
# its first step against that reference in every run.
FEED_ARCH = "granite-3-2b"
FEED_LAYERS = 6
FEED_ARGS = ["--steps", "5", "--seq-len", "2048", "--batch-seqs", "4",
             "--num-seqs", "32", "--remat", "full", "--transport", "thallus",
             "--ckpt-every", "0", "--log-every", "1"]
FEED_CKPT_DIR = ROOT / "artifacts" / "chip_smoke_ckpt"
# four chips against one: the same bf16 MXU passes over the same f32
# params, only summed in another order once the step is sharded over
# `model`; a run's loss must move by more than this bound
FEED_LOSS_RTOL = 1e-4
FEED_GNORM_RTOL = 1e-3
# kernels: one real size each
TAKE_SHAPE = (1 << 20, 128)      # a 128-lane float32 column, 512 MiB
TAKE_SELECTED = 1 << 18
VALIDITY_ROWS = 1 << 25          # the scan table's row count
ATTN_SHAPE = (1, 2048, 32, 8, 64)   # B, S, H, KV, hd: granite at 2048
ATTN_TOL = 2e-2                  # bf16 output: kernel and oracle round apart

class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def same_bits(a: np.ndarray, b: np.ndarray) -> bool:
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.view(np.uint8), b.view(np.uint8)))


class CompileClock:
    """Sums XLA backend compile time, as JAX's monitoring events report it."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, name: str, secs: float, **_) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs


class Phase:
    """Times one phase from its creation and prints its JSON line."""

    def __init__(self, name: str, devices, clock: CompileClock):
        self.name, self.devices, self.clock = name, devices, clock
        self.t0, self.c0 = time.perf_counter(), clock.seconds

    def report(self, bytes_landed: int, **numbers) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        line = {"phase": self.name,
                "seconds": time.perf_counter() - self.t0,
                "compile_seconds": self.clock.seconds - self.c0,
                "bytes_landed": bytes_landed,
                "peak_bytes_in_use": peaks[0] if len(peaks) == 1 else peaks,
                **numbers}
        print(json.dumps(line), flush=True)


# ---------------------------------------------------------------------------
# scan
# ---------------------------------------------------------------------------


def scan_phase(devices, clock) -> None:
    import jax
    from repro.core import Fabric, RpcClient, ThallusClient, ThallusServer
    from repro.core.device_transport import (batch_to_device,
                                             batch_to_device_packed)
    from repro.engine import Engine, make_numeric_table

    ph = Phase("scan", devices, clock)
    t = time.perf_counter()
    table = make_numeric_table("t", SCAN_ROWS, SCAN_COLS,
                               batch_rows=SCAN_BATCH_ROWS, seed=SEED,
                               dtype="float32")
    engine = Engine()
    engine.register("/data/t", table)
    server = ThallusServer(engine, Fabric())
    sql = ("SELECT " + ", ".join(f"c{i}" for i in range(SCAN_COLS))
           + " FROM t")
    setup_s = time.perf_counter() - t

    t = time.perf_counter()
    thallus = [batch_to_device(b) for b in
               ThallusClient(server).run_query(sql, "/data/t")]
    jax.block_until_ready([d.columns for d in thallus])
    thallus_s = time.perf_counter() - t

    t = time.perf_counter()
    rpc = [batch_to_device_packed(b) for b in
           RpcClient(server).run_query(sql, "/data/t")]
    jax.block_until_ready([d.columns for d in rpc])
    rpc_s = time.perf_counter() - t

    for path, landed in (("thallus", thallus), ("rpc", rpc)):
        check(len(landed) == len(table.batches),
              f"scan/{path}: {len(landed)} batches landed, table has "
              f"{len(table.batches)}")
        for i, (dev, host) in enumerate(zip(landed, table.batches)):
            for col in host.columns:
                name = col.field.name
                check(same_bits(np.asarray(dev[name]), col.values),
                      f"scan/{path}: batch {i} column {name} differs "
                      "from the table")
    del rpc
    resident = sum(a.nbytes for d in thallus for a in d.columns.values())
    check(resident == table.nbytes,
          f"scan: {resident} bytes resident, table {table.nbytes}")
    ph.report(resident, rows=table.num_rows, columns=SCAN_COLS,
              batches=len(thallus), table_setup_seconds=setup_s,
              thallus_seconds=thallus_s, rpc_seconds=rpc_s,
              bit_exact_paths=["thallus", "rpc"])
    del thallus


# ---------------------------------------------------------------------------
# feed
# ---------------------------------------------------------------------------


def one_chip_mesh(devices):
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices[:1])


def feed_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config(FEED_ARCH), num_layers=FEED_LAYERS)


def first_step_reference(cfg, mesh, args, tokens, labels) -> tuple:
    """Loss and gradient norm of the train loop's first step computed
    plainly: ``jax.grad`` of the model loss at the loop's initial params
    (same init, same seed) on its first rows, without the optimizer."""
    import jax
    from repro.models import init_params, loss_fn, make_rules, mesh_context
    from repro.training import global_norm

    with mesh, mesh_context(mesh, make_rules(cfg, mesh)):
        params = init_params(cfg, jax.random.PRNGKey(0))
        batch = {"tokens": tokens, "labels": labels}
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(cfg, p, batch, remat=args.remat)))(params)
        return float(loss), float(jax.jit(global_norm)(grads))


def run_feed(cfg, mesh) -> dict:
    """One training run through repro.launch.train on ``mesh``; returns its
    per-step losses, grad norms and wall times after checking the device
    tokens against the token table and the first step against a plain
    ``jax.grad`` of the same loss."""
    from repro.data.tokens import shift_labels
    from repro.launch.train import build_parser, token_table, train

    shutil.rmtree(FEED_CKPT_DIR, ignore_errors=True)   # nothing to resume
    args = build_parser().parse_args(
        FEED_ARGS + ["--ckpt-dir", str(FEED_CKPT_DIR)])
    rows = np.concatenate([b.column("tokens").values
                           for b in token_table(cfg, args).batches])
    rows = rows.reshape(-1, args.seq_len)
    first = rows[:args.batch_seqs]
    ref_loss, ref_gnorm = first_step_reference(cfg, mesh, args, first,
                                               shift_labels(first))
    out = {"loss": [], "grad_norm": [], "step_seconds": [],
           "bytes_in_use": None, "bytes_landed": 0}
    last = [time.perf_counter()]

    def on_step(step, batch, metrics):
        want = rows[(step - 1) * args.batch_seqs: step * args.batch_seqs]
        check(same_bits(np.asarray(batch["tokens"]), want),
              f"feed: step {step} tokens on device are not the table rows "
              "in scan order")
        check(same_bits(np.asarray(batch["labels"]), shift_labels(want)),
              f"feed: step {step} labels are not the shifted tokens")
        out["loss"].append(float(metrics["loss"]))
        out["grad_norm"].append(float(metrics["grad_norm"]))
        out["bytes_landed"] += sum(int(v.nbytes) for v in batch.values())
        now = time.perf_counter()
        out["step_seconds"].append(now - last[0])
        last[0] = now
        if out["bytes_in_use"] is None:
            out["bytes_in_use"] = [(d.memory_stats() or {}).get(
                "bytes_in_use", 0) for d in mesh.devices.flat]

    train(cfg, args, mesh, on_step=on_step)
    shutil.rmtree(FEED_CKPT_DIR, ignore_errors=True)
    check(len(out["loss"]) == args.steps,
          f"feed: {len(out['loss'])} of {args.steps} steps ran")
    check(all(math.isfinite(v) for v in out["loss"] + out["grad_norm"]),
          f"feed: non-finite loss or grad norm {out}")
    first_want = math.log(cfg.vocab_size)
    check(abs(out["loss"][0] - first_want) <= 0.5,
          f"feed: step-1 loss {out['loss'][0]} is not within 0.5 of "
          f"ln({cfg.vocab_size}) = {first_want}")
    for key, got, want, rtol in (
            ("loss", out["loss"][0], ref_loss, FEED_LOSS_RTOL),
            ("grad norm", out["grad_norm"][0], ref_gnorm, FEED_GNORM_RTOL)):
        check(abs(got - want) <= rtol * abs(want),
              f"feed: step-1 {key} {got} vs {want} from a plain jax.grad, "
              f"beyond rtol {rtol}")
    out["first_step_reference"] = {"loss": ref_loss, "grad_norm": ref_gnorm}
    moved = abs(out["loss"][-1] - out["loss"][0])
    check(moved > FEED_LOSS_RTOL * out["loss"][0],
          f"feed: the loss moved by {moved} over {args.steps} steps, within "
          f"the four-chip bound rtol {FEED_LOSS_RTOL}")
    return out


def feed_phase(devices, clock) -> None:
    from repro.configs import get_config

    cfg = feed_config()
    published = get_config(FEED_ARCH).num_layers
    mesh = one_chip_mesh(devices)
    ph = Phase("feed", devices, clock)
    out = run_feed(cfg, mesh)
    ph.report(out.pop("bytes_landed"), arch=cfg.name,
              layers=cfg.num_layers, published_layers=published,
              depth_cut=f"{cfg.num_layers} of {published} layers: 8 fit "
                        "HBM with Adam state at 4 x 2048 tokens, but the "
                        "8-layer one-chip step returns wrong MLP gradients; "
                        "6 was seen right",
              d_model=cfg.d_model, heads=cfg.num_heads,
              kv_heads=cfg.num_kv_heads, d_ff=cfg.d_ff,
              vocab=cfg.vocab_size, mesh=dict(mesh.shape), **out)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def kernels_phase(devices, clock) -> None:
    import jax
    import jax.numpy as jnp
    from repro.engine import make_numeric_table
    from repro.kernels import interpret_mode
    from repro.kernels.attention import attention_ref, flash_gqa
    from repro.kernels.pack import (inverse_routing, layout_segments,
                                    pack_ref, pack_segments, pack_tiles,
                                    stage_segments, tiles_for, unpack_tiles,
                                    unpack_segments)
    from repro.kernels.take import (bitmap_expand_ref, expand_validity,
                                    take_column, take_ref)

    check(not interpret_mode(), "kernels would run interpreted")

    def compiled(name, fn, *args):
        text = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in text, f"kernels/{name}: no Mosaic kernel "
              "in the lowered program")

    ph = Phase("kernels", devices, clock)
    landed = 0
    key = jax.random.PRNGKey(SEED)
    k_take, k_idx, k_bm, k_q, k_k, k_v = jax.random.split(key, 6)

    # pack / unpack: the serialize of one scan batch (8 x 2^17 float32)
    batch = make_numeric_table("t", SCAN_BATCH_ROWS, SCAN_COLS,
                               batch_rows=SCAN_BATCH_ROWS, seed=SEED,
                               dtype="float32").batches[0]
    segs = [c.values for c in batch.columns]
    packed, lens = pack_segments(segs)
    staged, _ = stage_segments(segs)
    seg_ids, tile_ids, _ = layout_segments(lens)
    staged, seg_ids, tile_ids = map(jnp.asarray, (staged, seg_ids, tile_ids))
    compiled("pack_tiles", pack_tiles, staged, seg_ids, tile_ids)
    check(same_bits(np.asarray(packed),
                    np.asarray(pack_ref(staged, seg_ids, tile_ids))),
          "kernels/pack_segments differs from pack_ref")
    max_tiles = max(tiles_for(n) for n in lens)
    compiled("unpack_tiles", lambda p, g: unpack_tiles(
        p, g, n_seg=len(lens), max_tiles=max_tiles),
        jnp.concatenate([packed, jnp.zeros_like(packed[:1])]),
        jnp.asarray(inverse_routing(lens, max_tiles)))
    for seg, got in zip(segs, unpack_segments(packed, lens)):
        check(same_bits(got, seg.view(np.uint8)),
              "kernels/unpack_segments does not give back the segment")
    landed += packed.nbytes

    # take: a 128-lane float32 column, a quarter of its rows selected
    values = jax.random.normal(k_take, TAKE_SHAPE, jnp.float32)
    idx = jax.random.randint(k_idx, (TAKE_SELECTED,), 0, TAKE_SHAPE[0],
                             jnp.int32)
    compiled("take_column", take_column, values, idx)
    got = take_column(values, idx)
    check(same_bits(np.asarray(got), np.asarray(take_ref(values, idx))),
          "kernels/take_column differs from take_ref")
    landed += values.nbytes + got.nbytes
    del values, got

    # validity: one bit per scan-table row
    bitmap = jax.random.randint(k_bm, (VALIDITY_ROWS // 8,), 0, 256,
                                jnp.int32).astype(jnp.uint8)
    compiled("expand_validity",
             lambda b: expand_validity(b, VALIDITY_ROWS), bitmap)
    mask = expand_validity(bitmap, VALIDITY_ROWS)
    want = bitmap_expand_ref(bitmap, VALIDITY_ROWS)
    check(same_bits(np.asarray(mask), np.asarray(want)),
          "kernels/expand_validity differs from bitmap_expand_ref")
    landed += bitmap.nbytes + mask.nbytes

    # flash attention: granite-3-2b's heads at 2048 tokens
    B, S, H, KV, hd = ATTN_SHAPE
    q = jax.random.normal(k_q, (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(k_k, (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(k_v, (B, S, KV, hd), jnp.bfloat16)
    compiled("flash_gqa", flash_gqa, q, k, v)
    got = flash_gqa(q, k, v).astype(jnp.float32)

    def heads(x):           # (B, S, n, hd) -> (B*H, S, hd), GQA-expanded
        x = jnp.repeat(x, H // x.shape[2], axis=2)
        return x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    want = attention_ref(heads(q), heads(k), heads(v)).astype(jnp.float32)
    want = want.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    diff = jnp.abs(got - want)
    err = float(jnp.max(diff))
    check(bool(jnp.all(diff <= ATTN_TOL * (1 + jnp.abs(want)))),
          f"kernels/flash_gqa off its oracle by up to {err}, beyond "
          f"atol = rtol = {ATTN_TOL}")
    landed += q.nbytes + k.nbytes + v.nbytes
    ph.report(landed, kernels=["pack_tiles", "unpack_tiles",
                               "take_column", "expand_validity",
                               "flash_gqa"],
              take_shape=list(TAKE_SHAPE), take_selected=TAKE_SELECTED,
              validity_rows=VALIDITY_ROWS, attn_shape=list(ATTN_SHAPE),
              flash_max_abs_err=err, flash_tol=ATTN_TOL)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def sharded_feed_phase(devices, clock) -> None:
    """The feed step on the (data=1, model=4) host mesh against the same
    run on the first of those chips alone."""
    from repro.launch.mesh import make_host_mesh

    cfg = feed_config()
    four = make_host_mesh()
    one = one_chip_mesh(devices)
    ph = Phase("feed_4chip", devices, clock)
    sharded = run_feed(cfg, four)
    single = run_feed(cfg, one)
    gaps = {}
    for key, rtol in (("loss", FEED_LOSS_RTOL),
                      ("grad_norm", FEED_GNORM_RTOL)):
        for s, (a, b) in enumerate(zip(sharded[key], single[key]), 1):
            check(abs(a - b) <= rtol * abs(b),
                  f"feed_4chip: step {s} {key} {a} on 4 chips vs {b} "
                  f"on one, beyond rtol {rtol}")
        gaps[f"{key}_max_rel_gap"] = max(
            abs(a - b) / abs(b) for a, b in zip(sharded[key], single[key]))
    used = sharded["bytes_in_use"]
    check(min(used) >= max(used) // 2,
          f"feed_4chip: state is not spread over the chips: {used}")
    ph.report(sharded["bytes_landed"], mesh=dict(four.shape),
              layers=cfg.num_layers, loss_4chip=sharded["loss"],
              loss_1chip=single["loss"],
              grad_norm_4chip=sharded["grad_norm"],
              grad_norm_1chip=single["grad_norm"],
              loss_rtol=FEED_LOSS_RTOL, grad_norm_rtol=FEED_GNORM_RTOL,
              **gaps,
              bytes_in_use_4chip=used,
              step_seconds_4chip=sharded["step_seconds"],
              step_seconds_1chip=single["step_seconds"])


def sharded_columns_phase(devices, clock) -> None:
    """batch_to_device with training_batch_specs: rows split over the four
    chips, each shard on its own chip, every column bit for bit."""
    import jax
    from jax.sharding import AxisType
    from repro.core import Fabric, ThallusClient, ThallusServer
    from repro.core.device_transport import (batch_to_device,
                                             training_batch_specs)
    from repro.data import make_token_table
    from repro.engine import Engine, make_numeric_table

    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
    spec = training_batch_specs(mesh)
    engine = Engine()
    engine.register("/data/t", make_numeric_table(
        "t", 4 * SCAN_BATCH_ROWS, SCAN_COLS, batch_rows=SCAN_BATCH_ROWS,
        seed=SEED, dtype="float32"))
    engine.register("/data/tokens", make_token_table(
        "tokens", 32, 2048, 49155, seqs_per_batch=32, seed=SEED))
    server = ThallusServer(engine, Fabric())
    ph = Phase("columns_4chip", devices, clock)
    landed = 0
    queries = (("SELECT " + ", ".join(f"c{i}" for i in range(SCAN_COLS))
                + " FROM t", "/data/t"),
               ("SELECT tokens FROM tokens", "/data/tokens"))
    for sql, dataset in queries:
        for host in ThallusClient(server).run_query(sql, dataset):
            dev = batch_to_device(host, mesh, spec)
            for col in host.columns:
                arr = dev[col.field.name]
                shards = arr.addressable_shards
                check({s.device for s in shards} == set(devices),
                      f"columns_4chip: {col.field.name} on "
                      f"{[s.device.id for s in shards]}")
                for s in shards:
                    check(s.data.shape[0] * len(devices) == host.num_rows
                          and same_bits(np.asarray(s.data),
                                        col.values[s.index]),
                          f"columns_4chip: {col.field.name} shard on "
                          f"device {s.device.id} is wrong")
                check(same_bits(np.asarray(arr), col.values),
                      f"columns_4chip: {col.field.name} differs")
                landed += arr.nbytes
    ph.report(landed, spec=str(spec), mesh=dict(mesh.shape))


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip checks")
    opts = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: src/repro is not beside this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform} "
              "devices)", file=sys.stderr)
        return 1
    if len(devices) < opts.chips:
        print(f"chip_smoke: --chips {opts.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    devices = devices[:opts.chips]

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    phases = ((sharded_feed_phase, sharded_columns_phase)
              if opts.chips == 4 else (scan_phase, feed_phase, kernels_phase))
    try:
        for phase in phases:
            phase(devices, clock)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
