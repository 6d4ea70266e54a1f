"""Training launcher: end-to-end driver wiring every substrate together.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \\
        --steps 200 --reduced --batch-seqs 8 --seq-len 128

Data flows: columnar token shards (engine) → Thallus zero-copy transport
(protocol) → ``device_put`` of each token batch → pjit'd train
step on the host mesh → columnar checkpoints (training.checkpoint). The
``--transport rpc`` flag switches the input pipeline to the serialize-based
baseline — the paper's comparison, selectable in production.

Fault tolerance: resumes from the latest checkpoint (params + optimizer +
data cursor); `--kill-at` simulates a mid-run crash for the restart test.
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Callable

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs import ARCH_IDS, get_config
from ..configs.base import ArchConfig
from ..core import Fabric, ThallusServer
from ..data import ThallusLoader, make_token_table
from ..engine import Engine, Table
from ..models import make_rules, mesh_context, param_specs
from ..training import (CheckpointManager, OptimizerConfig, TrainConfig,
                        init_train_state, make_train_step, train_state_shapes)
from .cache import enable_compile_cache
from .mesh import make_host_mesh


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch-seqs", type=int, default=8)
    ap.add_argument("--num-seqs", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=("none", "dots", "full"))
    ap.add_argument("--transport", default="thallus", choices=("thallus", "rpc"))
    ap.add_argument("--replicas", type=int, default=2,
                    help="data-server replicas (straggler backup)")
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50,
                    help="checkpoint every N steps and at the end; 0 saves "
                         "no checkpoint")
    ap.add_argument("--kill-at", type=int, default=0,
                    help="simulate a crash after N steps (restart test)")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


def token_table(cfg: ArchConfig, args: argparse.Namespace) -> Table:
    """The seeded token corpus every data-server replica serves."""
    return make_token_table("tokens", args.num_seqs, args.seq_len,
                            cfg.vocab_size,
                            seqs_per_batch=max(args.batch_seqs * 4, 32))


def train(cfg: ArchConfig, args: argparse.Namespace, mesh: Mesh,
          on_step: Callable[[int, dict, dict], None] | None = None) -> None:
    """Run the training loop of ``args`` (see :func:`build_parser`) for
    ``cfg`` on ``mesh``. ``on_step(step, device_batch, metrics)`` sees each
    step's device batch and metrics after the step."""
    tcfg = TrainConfig(
        optimizer=OptimizerConfig(learning_rate=args.lr, warmup_steps=20,
                                  decay_steps=max(args.steps, 100)),
        remat=args.remat, microbatches=args.microbatches)
    rules = make_rules(cfg, mesh)

    # -- data plane: replicated Thallus servers over columnar token shards
    table = token_table(cfg, args)
    servers = []
    for r in range(args.replicas):
        eng = Engine()
        eng.register("/data/tokens", table)
        servers.append(ThallusServer(eng, Fabric()))
    loader = ThallusLoader(servers, "SELECT tokens FROM tokens",
                           "/data/tokens", seq_len=args.seq_len,
                           batch_seqs=args.batch_seqs,
                           transport=args.transport)

    # -- state: init or resume, placed by the param specs -------------------
    mgr = CheckpointManager(f"{args.ckpt_dir}/{cfg.name}", keep_last=2)
    with mesh, mesh_context(mesh, rules):
        shapes = train_state_shapes(cfg, tcfg)
        pspecs = param_specs(cfg, shapes["params"], mesh)
        state_specs = {"params": pspecs,
                       "opt": {k: pspecs for k in shapes["opt"]}, "step": P()}
        state_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                       state_specs)
        latest = mgr.latest_step()
        if latest is not None:
            print(f"[resume] restoring step {latest}")
            state, man = mgr.restore(latest, like=shapes, mesh=mesh,
                                     specs=state_specs)
            loader.load_state_dict(man.cursors)
        else:
            state = jax.jit(functools.partial(init_train_state, cfg, tcfg),
                            out_shardings=state_shardings)(
                                jax.random.PRNGKey(0))

        step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=0,
                          out_shardings=(state_shardings,
                                         NamedSharding(mesh, P())))
        bspec = NamedSharding(mesh, P(tuple(a for a in ("data",)
                                            if a in mesh.axis_names)))
        t0 = time.time()
        tokens_seen = 0
        step = int(state["step"])
        data_iter = iter(loader)
        while step < args.steps:
            try:
                host_batch = next(data_iter)
            except StopIteration:
                loader.load_state_dict({"batch_offset": 0})
                data_iter = iter(loader)
                continue
            batch = {k: jax.device_put(v, bspec) for k, v in host_batch.items()}
            state, metrics = step_fn(state, batch)
            step = int(state["step"])
            tokens_seen += int(metrics["tokens"])
            if on_step is not None:
                on_step(step, batch, metrics)
            if step % args.log_every == 0:
                dt = time.time() - t0
                print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"tok/s {tokens_seen/max(dt,1e-9):,.0f} "
                      f"transport {loader.stats.transport_s*1e3:.1f}ms "
                      f"(backups={loader.stats.backup_requests})", flush=True)
            if args.ckpt_every and step % args.ckpt_every == 0:
                path = mgr.save(step, state, cursors=loader.state_dict())
                print(f"[ckpt] step {step} -> {path}")
            if args.kill_at and step >= args.kill_at:
                print(f"[crash] simulated failure at step {step} — relaunch "
                      "to resume from the latest checkpoint")
                return
        if args.ckpt_every:
            mgr.save(step, state, cursors=loader.state_dict())
        print(f"done: {step} steps, {tokens_seen:,} tokens, "
              f"final loss {float(metrics['loss']):.4f}")


def main() -> None:
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    train(cfg, args, make_host_mesh())


if __name__ == "__main__":
    main()
