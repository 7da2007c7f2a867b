"""Seeded worlds the cells serve: who the users are, what the catalog
holds, and how users reward what they are shown.  Harness data, shared
by the timed path (as inputs and as the reward function) and by the
plain reference; nothing here imports the program.

Catalog world.  ``world`` is one uint32 word drawn from ``--seed``.  Users
belong to ``cohorts`` cohorts (user ``u`` to ``u % cohorts``, the locale
a front end knows) and prefer ``normalize(C[cohort] + 0.05 noise_u)``;
items are ``normalize(R[region] + item_noise * noise_i)`` over
``regions`` region centroids, or iid unit vectors when ``regions`` is 0.
A request's click probability is ``(1 + x . theta_u) / 2``.

The world word rides as the first word of each transaction's PRNG key,
so the reward function recovers the user preferences from its key
instead of closing over a seed-dependent table: a closed-over array is
compiled into the program as a constant, and every seed would then
compile anew.

Paper world.  The planted-cluster synthetic set of Mahadik et al.
(Table 1): ``n_clusters`` unit centroids, user ``u`` in a uniformly drawn
cluster with 0.05 within-cluster noise, K fresh unit candidates per
interaction.  Its preference table is closed over by the environment
functions ``distclub.run`` takes as static arguments, so it is drawn from
the configuration's fixed ``world_seed``; ``--seed`` draws the run keys,
and with them every candidate set and every reward.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _normalize(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def world_word(seed: int) -> int:
    """A uint32 world word from any whole seed (up to and past 2**32)."""
    s = int(seed)
    return (s ^ (s >> 32) ^ 0x9E3779B9) & 0xFFFFFFFF


def tx_key(word: int, tx: int):
    """The raw uint32[2] PRNG key of transaction ``tx``: world word first."""
    import numpy as np
    return np.array([word, tx & 0xFFFFFFFF], np.uint32)


def _wkey(word, stream):
    base = jnp.stack([jnp.asarray(word, jnp.uint32),
                      jnp.uint32(0x5EED0000 + stream)])
    return base


def user_theta(word, uids, *, d, cohorts, noise=0.05):
    """Preference vectors ``[len(uids), d]`` of users ``uids`` (clipped >= 0)."""
    C = _normalize(jax.random.normal(_wkey(word, 1), (cohorts, d)))
    u = jnp.maximum(uids, 0)
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(_wkey(word, 2), u)
    eps = jax.vmap(lambda k: jax.random.normal(k, (d,)))(keys)
    return _normalize(C[u % cohorts] + noise * eps)


def catalog_embeddings(word, *, n_items, d, regions, item_noise):
    """``[n_items, d]`` unit-norm item embeddings of the seeded catalog."""
    eps = jax.random.normal(_wkey(word, 5), (n_items, d))
    if regions <= 0:
        return _normalize(eps)
    R = _normalize(jax.random.normal(_wkey(word, 3), (regions, d)))
    region = jax.random.randint(_wkey(word, 4), (n_items,), 0, regions)
    return _normalize(R[region] + item_noise * eps)


def click_draws(key, n):
    """The uniform draws that decide each request's click."""
    return jax.random.uniform(key, (n,))


def make_reward_fn(*, d, cohorts):
    """``reward_fn(key, uids, ctx, slot) -> (realized, p, best, rand)`` for
    ``serve.step_catalog``; one object per process, so the compiled
    transaction is reused."""

    def reward_fn(key, uids, ctx, slot):
        theta = user_theta(key[0], uids, d=d, cohorts=cohorts)
        x = jnp.take_along_axis(ctx, slot[:, None, None], axis=1)[:, 0]
        p = 0.5 * (1.0 + jnp.sum(x * theta, axis=-1))
        p_all = 0.5 * (1.0 + jnp.sum(ctx * theta[:, None, :], axis=-1))
        u = click_draws(key, uids.shape[0])
        realized = (u < p).astype(ctx.dtype)
        return realized, p, jnp.max(p_all, axis=-1), jnp.mean(p_all, axis=-1)

    return reward_fn


def warm_history(word, emb, *, n_users, d, cohorts, length):
    """Per-user statistics after ``length`` seeded interactions with
    uniformly drawn catalog items: ``(Minv, b, occ)`` with
    ``Minv = (I + sum x x')^-1`` and ``b = sum r x``."""
    ids = jax.random.randint(_wkey(word, 6), (n_users, length), 0,
                             emb.shape[0])
    X = emb[ids]                                              # [n, L, d]
    theta = user_theta(word, jnp.arange(n_users), d=d, cohorts=cohorts)
    p = 0.5 * (1.0 + jnp.einsum("nld,nd->nl", X, theta,
                                precision=jax.lax.Precision.HIGHEST))
    r = (jax.random.uniform(_wkey(word, 7), p.shape) < p).astype(jnp.float32)
    M = jnp.eye(d) + jnp.einsum("nld,nle->nde", X, X,
                                precision=jax.lax.Precision.HIGHEST)
    b = jnp.einsum("nl,nld->nd", r, X, precision=jax.lax.Precision.HIGHEST)
    return jnp.linalg.inv(M), b, jnp.full((n_users,), length, jnp.int32)


# ---------------------------------------------------------------------------
# the paper's synthetic set
# ---------------------------------------------------------------------------


def paper_theta(world_seed, *, n_users, d, n_clusters, noise=0.05):
    k_c, k_a, k_n = jax.random.split(jax.random.PRNGKey(world_seed), 3)
    C = _normalize(jax.random.normal(k_c, (n_clusters, d)))
    labels = jax.random.randint(k_a, (n_users,), 0, n_clusters)
    return _normalize(C[labels] + noise * jax.random.normal(k_n, (n_users, d)))


def _user_keys(key, n):
    return jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
        key, jnp.arange(n, dtype=jnp.int32))


def paper_env_fns(theta, K):
    """``(contexts_fn, rewards_fn)`` in the form ``distclub.run``'s
    environment takes (single host: ``row0`` is 0)."""
    n, d = theta.shape

    def contexts_fn(key, occ, row0=0):
        keys = _user_keys(key, occ.shape[0])
        return jax.vmap(
            lambda k: _normalize(jax.random.normal(k, (K, d))))(keys)

    def rewards_fn(key, occ, contexts, choice, row0=0):
        p_all = 0.5 * (1.0 + jnp.sum(contexts * theta[:, None, :], axis=-1))
        p = jnp.take_along_axis(p_all, choice[:, None], axis=1)[:, 0]
        keys = _user_keys(key, p_all.shape[0])
        u = jax.vmap(lambda k: jax.random.uniform(k, ()))(keys)
        return ((u < p).astype(contexts.dtype), p, jnp.max(p_all, axis=-1),
                jnp.mean(p_all, axis=-1))

    return contexts_fn, rewards_fn
