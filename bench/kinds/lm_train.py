"""A decoder-only language model in training, fed by the Thallus token
loader, as ``repro.launch.train`` builds it: replicated ThallusServers over
a columnar token shard, ``ThallusLoader`` → ``device_put`` → the donated,
jitted ``make_train_step`` on a (data, model) mesh.

What the benchmark makes itself, from the seed: the token corpus and the
weights (``bench/weights.py``, one jitted call on the device, in the
program's layout and shardings). The program's own config is checked
against the configuration file before anything runs.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench import harness, weights

TOKENS_SQL = "SELECT tokens FROM tokens"
TOKENS_PATH = "/data/tokens"


@dataclasses.dataclass
class Deployment:
    arch: object              # the program's ArchConfig
    mesh: object
    state: dict               # the train state, placed by the param specs
    step_fn: object           # jitted, state donated
    bspec: object             # the batch sharding the loop puts with
    loader: object            # ThallusLoader over the replicas
    rows: np.ndarray          # the corpus, (num_seqs, seq_len), scan order


def program_config(cfg: dict):
    """The program's ArchConfig for this configuration, checked key by key
    against the file; a difference is an error, not a quiet change."""
    from repro.configs import get_config

    prog = cfg["program"]
    arch = dataclasses.replace(get_config(prog["arch"]),
                               num_layers=prog["num_layers"])
    d = weights.dims(cfg)
    want = {"family": "dense", "num_layers": d["L"], "d_model": d["D"],
            "num_heads": d["H"], "num_kv_heads": d["KV"],
            "resolved_head_dim": d["hd"], "d_ff": d["F"],
            "vocab_size": d["V"], "activation": "swiglu",
            "tie_embeddings": cfg["tie_word_embeddings"],
            "norm_eps": cfg["rms_norm_eps"], "rope_theta": cfg["rope_theta"],
            "embed_scale": cfg["embedding_multiplier"] != 1.0,
            "zero_centered_norm": False, "qk_norm": False,
            "logit_softcap": None, "moe": None}
    have = {k: getattr(arch, k) for k in want}
    if have != want:
        bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
        raise harness.BenchError(f"the program's {prog['arch']} departs "
                                 f"from the configuration: {bad}")
    if cfg["attention_multiplier"] != d["hd"] ** -0.5 or \
            cfg["residual_multiplier"] != 1.0 or cfg["logits_scaling"] != 1.0:
        raise harness.BenchError("the program has no attention, residual or "
                                 "logits multipliers other than the plain ones")
    return arch


def make_corpus(traffic: dict, vocab: int, seed: int) -> np.ndarray:
    """Token rows from the seed: uniform ids, each odd position a fixed
    function of the one before (31 * t + 7 mod vocab), so there is
    something to learn."""
    c = traffic["corpus"]
    rng = np.random.default_rng([seed, 0x70CE5])
    toks = rng.integers(0, vocab, (c["num_seqs"], traffic["seq_len"]),
                        dtype=np.int32)
    toks[:, 1::2] = (toks[:, ::2].astype(np.int64) * 31 + 7) % vocab
    return toks


def token_table(rows: np.ndarray, seqs_per_batch: int):
    """The corpus as the program's columnar token table (seq_id, tokens)."""
    from repro.core.recordbatch import batch_from_arrays
    from repro.data.tokens import TOKEN_SCHEMA
    from repro.engine import Table

    table = Table("tokens", TOKEN_SCHEMA)
    n, seq_len = rows.shape
    for lo in range(0, n, seqs_per_batch):
        part = rows[lo:lo + seqs_per_batch]
        ids = np.repeat(np.arange(lo, lo + len(part), dtype=np.int64),
                        seq_len)
        table.append(batch_from_arrays(TOKEN_SCHEMA, [ids, part.reshape(-1)]))
    return table


def to_program_layout(params: dict, program_shapes: dict):
    """The benchmark's weights in the program's tree: the embedding table
    padded with zero rows to the program's padded vocabulary."""
    import jax.numpy as jnp

    out = dict(params)
    rows = program_shapes["embed"].shape[0]
    out["embed"] = jnp.pad(params["embed"],
                           ((0, rows - params["embed"].shape[0]), (0, 0)))
    return out


def build(cfg: dict, traffic: dict, seed: int, devices) -> Deployment:
    import jax
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
    from repro.core import Fabric, ThallusServer
    from repro.data import ThallusLoader
    from repro.engine import Engine
    from repro.models import make_rules, mesh_context, param_specs
    from repro.training import (OptimizerConfig, TrainConfig,
                                make_train_step, train_state_shapes)
    from repro.training.optimizer import init_opt_state

    arch = program_config(cfg)
    train = cfg["train"]
    tcfg = TrainConfig(optimizer=OptimizerConfig(**train["optimizer"]),
                       remat=train["remat"], microbatches=1,
                       param_dtype=train["param_dtype"])
    mesh = jax.make_mesh((1, len(devices)), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)

    rows = make_corpus(traffic, arch.vocab_size, seed)
    table = token_table(rows, traffic["corpus"]["seqs_per_batch"])
    servers = []
    for _ in range(train["replicas"]):
        engine = Engine()
        engine.register(TOKENS_PATH, table)
        servers.append(ThallusServer(engine, Fabric()))
    loader = ThallusLoader(servers, TOKENS_SQL, TOKENS_PATH,
                           seq_len=traffic["seq_len"],
                           batch_seqs=traffic["batch_seqs"],
                           transport=traffic["transport"])

    with mesh, mesh_context(mesh, make_rules(arch, mesh)):
        shapes = train_state_shapes(arch, tcfg)
        pspecs = param_specs(arch, shapes["params"], mesh)
        state_specs = {"params": pspecs,
                       "opt": {k: pspecs for k in shapes["opt"]}, "step": P()}
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 state_specs)

        def init(key):
            params = to_program_layout(weights.make(cfg, key), shapes["params"])
            return {"params": params,
                    "opt": init_opt_state(tcfg.optimizer, params),
                    "step": jax.numpy.zeros((), jax.numpy.int32)}

        state = jax.jit(init, out_shardings=shardings)(weights.seed_key(seed))
        step_fn = jax.jit(make_train_step(arch, tcfg), donate_argnums=0,
                          out_shardings=(shardings, NamedSharding(mesh, P())))
    bspec = NamedSharding(mesh, P("data"))
    return Deployment(arch, mesh, state, step_fn, bspec, loader, rows)
