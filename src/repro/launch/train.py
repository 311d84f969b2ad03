"""Training launcher — the full decentralized pipeline of paper §5.1:

1. extract frozen-encoder features for every unique sample (stub frontend);
2. balanced spherical k-means → K disjoint shards + centroid router;
3. train K experts fully independently (per-expert data, optimizer,
   checkpoints — zero communication), or the dense baseline on everything;
4. save per-expert checkpoints + the router.

It runs the reduced (smoke) configs against the synthetic clustered corpus
end to end; the production mesh (each expert maps to one pod — see
sharding/rules.py) is so far only lowered by the dry-run.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_8b \
        --mode decentralized --experts 2 --steps 200 --out /tmp/run
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.checkpoint import ckpt
from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.data.partition import partition_dataset
from repro.data.pipeline import LoaderConfig, ShardLoader, expert_loaders
from repro.data.synthetic import SyntheticConfig, SyntheticMultimodal
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import (TrainConfig, init_train_state,
                                 train_host_loop)


def build_corpus(args) -> SyntheticMultimodal:
    return SyntheticMultimodal(SyntheticConfig(
        vocab=args.vocab, seq_len=args.seq_len, n_latent=args.latent,
        n_samples=args.samples, feature_dim=args.feature_dim,
        seed=args.seed))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, default="qwen3_8b")
    ap.add_argument("--mode", choices=["dense", "decentralized"],
                    default="decentralized")
    ap.add_argument("--experts", type=int, default=2)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16,
                    help="dense global batch; experts use batch/K (paper "
                         "§6.1 compute matching)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--latent", type=int, default=4)
    ap.add_argument("--samples", type=int, default=2048)
    ap.add_argument("--feature-dim", type=int, default=32)
    ap.add_argument("--clustering", choices=["balanced", "two_stage"],
                    default="balanced")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="/tmp/repro_run")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch).reduced(vocab=args.vocab)
    model = build_model(cfg)
    corpus = build_corpus(args)
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                      total_steps=args.steps)
    tc = TrainConfig(opt=opt)
    os.makedirs(args.out, exist_ok=True)

    if args.mode == "dense":
        loader = ShardLoader(corpus, LoaderConfig(batch_size=args.batch))
        state = init_train_state(model, jax.random.PRNGKey(args.seed), opt)
        t0 = time.time()
        state, hist = train_host_loop(
            model, state, loader, args.steps, tc,
            callback=lambda s, m: print(f"dense step {s}: {m}", flush=True))
        ckpt.save_expert(args.out, 0, args.steps, state)
        print(f"dense done in {time.time()-t0:.1f}s; "
              f"final loss {hist[-1]['loss']:.4f}")
        return

    # ---- decentralized: partition → independent experts -----------------
    feats = corpus.all_features()
    part = partition_dataset(feats, args.experts,
                             algorithm=args.clustering, seed=args.seed)
    sizes = [len(s) for s in part.shards]
    print(f"partitioned {len(feats)} samples into {sizes} "
          f"(balanced k-means, {part.clustering.n_iter} iters)")
    ckpt.save_router(args.out, part.clustering.centroids,
                     part.router.config.temperature,
                     part.router.config.top_k)

    per_expert_batch = max(args.batch // args.experts, 1)
    loaders = expert_loaders(corpus, part.shards, per_expert_batch)
    summary = []
    for k in range(args.experts):
        # each expert: its own seed, its own data, its own optimizer — and
        # NO communication with the others (train them on separate nodes in
        # production; sequentially here).
        state = init_train_state(model,
                                 jax.random.PRNGKey(args.seed + 100 + k), opt)
        t0 = time.time()
        state, hist = train_host_loop(
            model, state, loaders[k], args.steps, tc,
            callback=lambda s, m, k=k: print(f"expert {k} step {s}: {m}",
                                             flush=True))
        path = ckpt.save_expert(args.out, k, args.steps, state)
        summary.append({"expert": k, "shard_size": sizes[k],
                        "final_loss": hist[-1]["loss"],
                        "wall_s": round(time.time() - t0, 1),
                        "checkpoint": path})
        print(f"expert {k} done: {summary[-1]}", flush=True)

    with open(os.path.join(args.out, "train_summary.json"), "w") as f:
        json.dump({"args": vars(args), "experts": summary}, f, indent=1)
    print("decentralized training complete →", args.out)


if __name__ == "__main__":
    main()
