"""Training driver: ``python -m repro.launch.train --arch <id> [options]``.

Runs real steps (reduced configs on CPU; assigned configs on a TPU mesh)
with checkpoint/resume — kill it mid-run and it continues from the last
atomic checkpoint.  The dry-run path (``--dryrun``) lowers/compiles only.

Examples:
    python -m repro.launch.train --arch qwen3-4b --reduce --steps 50
    python -m repro.launch.train --arch sasrec --reduce --steps 100
    python -m repro.launch.train --arch distclub-paper --reduce --steps 20
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from .. import configs
from ..core.types import BanditHyper
from ..train import optimizer
from ..train.checkpoint import CheckpointManager
from . import compile_cache


def _reduced_cfg(spec):
    if spec.family == "lm":
        return dataclasses.replace(
            spec.cfg, n_layers=2 * spec.cfg.block_layers, d_model=128,
            n_heads=4, n_kv_heads=min(4, spec.cfg.n_kv_heads), d_head=32,
            d_ff=256, vocab=2048,
            n_experts=min(8, spec.cfg.n_experts),
            d_ff_expert=128 if spec.cfg.is_moe else 0,
            top_k=min(2, spec.cfg.top_k), dtype=jnp.float32,
            attn_chunk=128, microbatches=1)
    if spec.family == "recsys":
        return dataclasses.replace(spec.cfg, n_items=4096)
    if spec.family == "gnn":
        return dataclasses.replace(spec.cfg, d_feat=64, n_classes=7)
    return spec.cfg


def train_lm(spec, args):
    from ..models import transformer as tr

    cfg = _reduced_cfg(spec) if args.reduce else spec.cfg
    key = jax.random.PRNGKey(args.seed)
    params = tr.init_lm(key, cfg)
    opt = optimizer.adamw_init(params)
    mgr = CheckpointManager(args.ckpt_dir or f"/tmp/repro_train_{spec.arch_id}",
                            keep=2)

    restored, start = mgr.restore_latest(
        jax.eval_shape(lambda: (params, opt)))
    if restored is not None:
        params, opt = restored
        print(f"resumed from checkpoint step {start}")
    else:
        start = 0

    @jax.jit
    def step(params, opt, tokens):
        loss, grads = jax.value_and_grad(tr.lm_loss)(
            params, cfg, tokens[:, :-1], tokens[:, 1:])
        params, opt = optimizer.adamw_update(grads, opt, params, lr=3e-4)
        return params, opt, loss

    B, S = args.batch, args.seq
    # learnable synthetic stream: zipfian unigram (entropy << log V), so the
    # loss visibly falls from log(V) toward the unigram entropy
    data_logits = -1.5 * jnp.log(jnp.arange(1, cfg.vocab + 1, dtype=jnp.float32))
    for i in range(start, args.steps):
        k = jax.random.fold_in(key, i)
        tokens = jax.random.categorical(k, data_logits, shape=(B, S + 1))
        t0 = time.perf_counter()
        params, opt, loss = step(params, opt, tokens)
        loss = float(loss)
        if i % args.log_every == 0:
            print(f"step {i:5d}  loss {loss:.4f}  "
                  f"{time.perf_counter() - t0:.2f}s")
        if (i + 1) % args.ckpt_every == 0:
            mgr.save((params, opt), i + 1)
    print("done; final loss", loss)


def train_bandit(spec, args):
    from ..core import distclub, env, env_ops

    hyper: BanditHyper = spec.cfg
    n, d = (2048, 25) if args.reduce else (20480, 25)
    e, _ = env.make_synthetic_env(jax.random.PRNGKey(0), n, d, 50,
                                  hyper.n_candidates)
    ops = env_ops.synthetic_ops(e)
    state, metrics, nclu = distclub.run(
        ops, jax.random.PRNGKey(args.seed), hyper, n_epochs=args.steps, d=d)
    T = int(metrics.interactions.sum())
    print(f"{T} interactions, reward/random = "
          f"{float(metrics.reward.sum()) / float(metrics.rand_reward.sum()):.3f}, "
          f"clusters {nclu.tolist()[-5:]}")


def train_recsys(spec, args):
    from ..models.recsys import dcn_v2, mind, seqrec

    cfg = _reduced_cfg(spec) if args.reduce else spec.cfg
    key = jax.random.PRNGKey(args.seed)
    if spec.arch_id == "dcn-v2":
        params = dcn_v2.init_dcn(key, cfg)
        opt = optimizer.adagrad_init(params)

        @jax.jit
        def step(params, opt, k):
            dense = jax.random.normal(k, (args.batch, cfg.n_dense))
            sparse = jax.random.randint(k, (args.batch, cfg.n_sparse), 0,
                                        cfg.vocab_per_field)
            labels = jax.random.bernoulli(k, 0.3, (args.batch,)).astype(
                jnp.float32)
            loss, g = jax.value_and_grad(dcn_v2.dcn_loss)(
                params, cfg, dense, sparse, labels)
            params, opt = optimizer.adagrad_update(g, opt, params)
            return params, opt, loss
    else:
        init, loss_fn = ((mind.init_mind, mind.mind_loss)
                         if spec.arch_id == "mind"
                         else (seqrec.init_seqrec,
                               seqrec.sampled_softmax_loss))
        params = init(key, cfg)
        opt = optimizer.adagrad_init(params)

        @jax.jit
        def step(params, opt, k):
            hist = jax.random.randint(k, (args.batch, cfg.seq_len), 1,
                                      cfg.n_items)
            tgt = (hist if spec.arch_id != "mind"
                   else jax.random.randint(k, (args.batch,), 1, cfg.n_items))
            loss, g = jax.value_and_grad(loss_fn)(params, cfg, hist, tgt, k)
            params, opt = optimizer.adagrad_update(g, opt, params)
            return params, opt, loss

    for i in range(args.steps):
        params, opt, loss = step(params, opt, jax.random.fold_in(key, i))
        if i % args.log_every == 0:
            print(f"step {i:5d}  loss {float(loss):.4f}")
    print("done; final loss", float(loss))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reduce", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ckpt-dir")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    compile_cache.enable()

    spec = configs.get(args.arch)
    if spec.family == "lm":
        train_lm(spec, args)
    elif spec.family == "bandit":
        train_bandit(spec, args)
    elif spec.family == "recsys":
        train_recsys(spec, args)
    else:
        raise SystemExit("use tests/benchmarks for the GNN training path")


if __name__ == "__main__":
    main()
