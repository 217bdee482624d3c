"""End-to-end federated training driver (runnable on CPU).

Federated fine-tuning of any assigned architecture (reduced preset for CPU)
with FedDANE / FedAvg / FedProx / variants from the core library:

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b \
      --rounds 20 --devices-per-round 4 --local-epochs 2 --algo feddane

Data: procedural federated LM corpus (per-device character-role Markov
chains, see repro.data.leaf_like) tokenized into the model's vocab.
Checkpoints every --ckpt-every rounds via repro.checkpoint.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.configs import get_arch
from repro.configs.base import FederatedConfig
from repro.core import FederatedTrainer
from repro.data.leaf_like import generate_shakespeare_like
from repro.data.batching import FederatedData
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, model_specs, param_count
from repro.models import transformer


def make_lm_fed_data(num_devices: int, seq_len: int, batch_size: int,
                     samples_cap: int, seed: int) -> FederatedData:
    devices = generate_shakespeare_like(
        num_devices=num_devices, seed=seed, sample_cap=samples_cap)
    out = []
    for d in devices:
        toks = d["tokens"][:, :seq_len]
        labs = d["labels"][:, :seq_len]
        out.append({"tokens": toks, "labels": labs})
    return FederatedData(out, batch_size=batch_size, name="fed_lm")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--algo", default="feddane",
                    choices=("fedavg", "fedprox", "feddane",
                             "feddane_pipelined", "feddane_decayed",
                             "inexact_dane", "scaffold"))
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--num-devices", type=int, default=16)
    ap.add_argument("--devices-per-round", type=int, default=4)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--mu", type=float, default=0.01)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--samples-per-device", type=int, default=32)
    ap.add_argument("--full-size", action="store_true",
                    help="use the full (not reduced) architecture")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if not args.full_size:
        cfg = cfg.reduced(num_layers=args.layers, d_model=args.d_model,
                          vocab_size=args.vocab)
    print(f"arch={cfg.name} params~{param_count(model_specs(cfg)):,}")

    if cfg.encoder_decoder or cfg.frontend == "patches":
        print("note: audio/VLM archs use stub frontends; federated LM "
              "training here drives the decoder on token data only")

    data = make_lm_fed_data(args.num_devices, args.seq_len + 1,
                            args.batch_size, args.samples_per_device,
                            args.seed)

    def loss_fn(params, batch):
        b = {"tokens": batch["tokens"][:, :-1],
             "labels": batch["labels"][:, :-1]}
        if cfg.encoder_decoder:
            B, S = b["tokens"].shape
            b["frames"] = jnp.zeros((B, S, cfg.d_model), jnp.float32)
        if cfg.frontend == "patches":
            P = cfg.num_prefix_embeddings
            B = b["tokens"].shape[0]
            b["patches"] = jnp.zeros((B, P, cfg.d_model), jnp.float32)
            b["labels"] = jnp.concatenate(
                [jnp.full((B, P), -1, jnp.int32), b["labels"]], axis=1)
        return transformer.loss_fn(params, b, cfg, remat="none")

    fed = FederatedConfig(
        algorithm=args.algo, num_devices=args.num_devices,
        devices_per_round=args.devices_per_round,
        local_epochs=args.local_epochs, local_batch_size=args.batch_size,
        learning_rate=args.lr, mu=args.mu, seed=args.seed)
    trainer = FederatedTrainer(loss_fn, data, fed)
    params = init_params(model_specs(cfg), jax.random.PRNGKey(args.seed))

    st = trainer.init(params)
    t0 = time.time()
    for r in range(args.rounds):
        st = trainer.round(st)
        loss = trainer.global_loss(st.params)
        print(f"round {st.round:4d} comm {st.comm_rounds:4d} "
              f"loss {loss:.4f}  ({time.time()-t0:.1f}s)")
        if args.ckpt_dir and (r + 1) % args.ckpt_every == 0:
            path = save_checkpoint(args.ckpt_dir, st.params, step=st.round)
            print(f"  checkpoint -> {path}")
    print(f"done: {args.rounds} rounds in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
