"""Serving driver: ``python -m repro.launch.serve --arch <id>``.

Runs batched online recommendation with a policy-pluggable
``OnlineBandit`` session over a recsys model's embeddings (reduced scale
on CPU) — ``--policy {distclub,dccb,club,linucb}`` serves any of the four
bandits through the identical transaction — reporting reward vs the
random policy and throughput.  For LM archs it runs reduced-config decode
steps against a KV cache.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from .. import configs
from . import compile_cache


def serve_recsys(spec, args):
    from .. import serve
    from ..core import env as bandit_env
    from ..core.types import BanditHyper
    from ..models.recsys import seqrec

    d, K = 32, 20
    cfg = seqrec.SeqRecConfig(n_items=4096, embed_dim=d, n_blocks=2,
                              n_heads=2, seq_len=16)
    model = seqrec.init_seqrec(jax.random.PRNGKey(0), cfg)
    world, _ = bandit_env.make_synthetic_env(
        jax.random.PRNGKey(1), n_users=args.users, d=d, n_clusters=8,
        n_candidates=K)
    hyper = BanditHyper(alpha=0.05, gamma=2.4, n_candidates=K)
    session = serve.OnlineBandit.create(
        args.users, d, hyper, policy=args.policy,
        refresh_every=args.users * 4)
    theta = world.theta

    def reward_fn(key, user_ids, contexts, choice):
        return bandit_env.step_rewards(key, theta[user_ids], contexts,
                                       choice)

    key = jax.random.PRNGKey(2)
    tot_r = tot_rand = 0.0
    t0 = time.perf_counter()
    for step in range(args.steps):
        k_u, k_c, k_s, key = jax.random.split(key, 4)
        users = jax.random.permutation(k_u, args.users)[:args.batch]
        cand = jax.random.randint(k_c, (args.batch, K), 0, cfg.n_items)
        ctx = serve.embed_candidates(model["item_embed"], cand)
        session, choice, m = serve.step(session, k_s, users, ctx, reward_fn)
        tot_r += float(m.reward)
        tot_rand += float(m.rand_reward)
    dt = time.perf_counter() - t0
    n = args.steps * args.batch
    print(f"[{args.policy}] {n} requests in {dt:.1f}s = {n / dt:.0f} req/s; "
          f"reward/random = {tot_r / tot_rand:.3f}")


def serve_lm(spec, args):
    from ..models import transformer as tr

    cfg = dataclasses.replace(
        spec.cfg, n_layers=2 * spec.cfg.block_layers, d_model=128, n_heads=4,
        n_kv_heads=min(4, spec.cfg.n_kv_heads), d_head=32, d_ff=256,
        vocab=2048, n_experts=min(8, spec.cfg.n_experts),
        d_ff_expert=128 if spec.cfg.is_moe else 0,
        top_k=min(2, spec.cfg.top_k), dtype=jnp.float32, attn_chunk=128)
    params = tr.init_lm(jax.random.PRNGKey(0), cfg)
    B, S = args.batch, 128
    prompt = jax.random.randint(jax.random.PRNGKey(1), (B, 16), 0, cfg.vocab)
    _, cache = tr.lm_prefill(params, cfg, prompt)
    kc = jnp.pad(cache[0], ((0, 0),) * 4 + ((0, S - 16), (0, 0)))
    vc = jnp.pad(cache[1], ((0, 0),) * 4 + ((0, S - 16), (0, 0)))

    decode = jax.jit(lambda p, t, c, pos: tr.lm_decode_step(p, cfg, t, c, pos))
    tok = prompt[:, -1]
    t0 = time.perf_counter()
    for pos in range(16, 16 + args.steps):
        logits, (kc, vc) = decode(params, tok, (kc, vc), jnp.int32(pos))
        tok = jnp.argmax(logits, -1)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    print(f"decoded {args.steps} tokens x {B} seqs in {dt:.1f}s = "
          f"{args.steps * B / dt:.0f} tok/s (reduced config)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="sasrec")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--users", type=int, default=256)
    ap.add_argument("--policy", default="distclub",
                    choices=["distclub", "dccb", "club", "linucb"],
                    help="serving policy (recsys archs)")
    args = ap.parse_args()
    compile_cache.enable()
    spec = configs.get(args.arch)
    if spec.family == "lm":
        serve_lm(spec, args)
    else:
        serve_recsys(spec, args)


if __name__ == "__main__":
    main()
