"""The MovieLens-25M serving world: how active each user is, and what each
user watched before the window opens.  Harness data on top of
``world.py``: the catalog, the user preferences and the reward function
are that module's, so ``drivers/open_loop.check_tx`` applies unchanged.

Source: MovieLens 25M (GroupLens; Harper & Konstan, ACM TiiS 2015):
162,541 users, 62,423 movies and 25,000,095 ratings, every user with at
least 20.  The feature width (19, one per genre) and the 10 user
clusters follow how arXiv:2007.08061 sets up its MovieLens set.

Activity.  User ``u`` has ``a_u >= floor`` interactions and the whole set
sums to the source's rating count, so the mean is the source's 153.8.
The tail's shape is assumed: the excess over the floor follows a
lognormal of ``sigma`` (1.39 puts the median near 71 and the largest
user near 27,000 ratings).  The activities are the lognormal's
quantiles, scaled to the total and rounded so the sum is exact, in an
order drawn from the seed given.

The deployment is one rating log: its geometry (the genre centroids,
each movie's genre, the cohort centroids and each user's preference),
each user's activity and the warm history all come from the
configuration's fixed ``world_seed`` (its ``world.world_word``), so that
every run serves the same users and catalog from the same state; a
run's ``--seed`` draws only its traffic.

Warm history.  ``a_u`` interactions per user: with probability
``in_region_share`` the movie is drawn uniformly from one of the
``top_regions`` genres (item regions) that the user's cohort centroid
scores highest, else uniformly from the whole catalog; the click is the
reward function's ``Bernoulli((1 + x . theta_u) / 2)``.  The statistics
``M = I + sum x x'``, ``b = sum r x`` are folded on the device over a
work list of (block of ``USER_BLOCK`` users, step of ``STEP`` of their
interactions) pairs, users in descending activity so that a block's
users need about as many steps each; the ``[n, L, d]`` history never
exists.  ``Minv`` comes from the program's batched SPD inverse.
"""
from __future__ import annotations

import functools
import statistics

import jax
import numpy as np

from . import world as W
from .traffic import generator as gen

USER_BLOCK = 256            # users folded together
STEP = 64                   # interactions per user per step


def activities(seed: int, *, n_users: int, total: int, floor: int,
               sigma: float) -> np.ndarray:
    """``[n_users]`` int64 interaction counts, each ``>= floor``, summing
    to ``total``, in an order drawn from ``seed``."""
    q = (np.arange(n_users) + 0.5) / n_users
    inv = statistics.NormalDist().inv_cdf
    x = np.exp(sigma * np.array([inv(v) for v in q]))
    x *= (total - floor * n_users) / x.sum()
    a = np.floor(x).astype(np.int64)
    short = total - floor * n_users - int(a.sum())
    a[np.argsort(a - x, kind="stable")[:short]] += 1
    return a[gen.rng(seed, 11).permutation(n_users)] + floor


def top_regions(word, *, d: int, cohorts: int, regions: int, top: int):
    """``[cohorts, top]`` the item regions each cohort centroid scores
    highest (``world.user_theta``'s centroids against
    ``world.catalog_embeddings``' region centroids)."""
    import jax.numpy as jnp
    C = W._normalize(jax.random.normal(W._wkey(word, 1), (cohorts, d)))
    R = W._normalize(jax.random.normal(W._wkey(word, 3), (regions, d)))
    score = jnp.dot(C, R.T, precision=jax.lax.Precision.HIGHEST)
    return jnp.argsort(-score, axis=1)[:, :top].astype(jnp.int32)


def work_list(act: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(order, block, step)``: users in descending activity, padded to
    whole blocks with id -1, and one (block, step) pair per ``STEP``
    interactions each block's busiest user needs.  The set of activities
    is the same for every seed, so the list's length is too."""
    order = np.argsort(-act, kind="stable").astype(np.int32)
    n_blocks = -(-len(act) // USER_BLOCK)
    order = np.concatenate([order, np.full(n_blocks * USER_BLOCK
                                           - len(act), -1, np.int32)])
    busiest = act[order[::USER_BLOCK]]
    steps = -(-busiest // STEP)
    block = np.repeat(np.arange(n_blocks, dtype=np.int32), steps)
    step = np.concatenate([np.arange(s, dtype=np.int32) for s in steps])
    return order, block, step


@functools.partial(jax.jit, static_argnames=(
    "d", "cohorts", "regions", "top", "share"))
def _fold(word, emb, order, act_sorted, block, step, unsort, *, d, cohorts,
          regions, top, share):
    """``(M, b)`` of every user, in user order (see the module docstring)."""
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    N = emb.shape[0]
    region = jax.random.randint(W._wkey(word, 4), (N,), 0, regions)
    by_region = jnp.argsort(region, stable=True).astype(jnp.int32)
    count = jnp.bincount(region, length=regions)
    first = jnp.cumsum(count) - count
    tops = top_regions(word, d=d, cohorts=cohorts, regions=regions, top=top)
    theta = W.user_theta(word, order, d=d, cohorts=cohorts)
    cohort = jnp.maximum(order, 0) % cohorts
    base = W._wkey(word, 12)
    n_pad = order.shape[0]
    M0 = jnp.broadcast_to(jnp.eye(d, dtype=jnp.float32), (n_pad, d, d))
    b0 = jnp.zeros((n_pad, d), jnp.float32)
    shape = (USER_BLOCK, STEP)

    def body(i, carry):
        M, b = carry
        r0 = block[i] * USER_BLOCK
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, USER_BLOCK)
        k1, k2, k3, k4 = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(base, block[i]), step[i]),
            4)
        t = step[i] * STEP + jnp.arange(STEP)
        live = t[None, :] < sl(act_sorted)[:, None]
        reg = tops[sl(cohort)[:, None],
                   jax.random.randint(k2, shape, 0, top)]
        u = jax.random.uniform(k3, shape)
        in_reg = by_region[first[reg] + jnp.minimum(
            (u * count[reg]).astype(jnp.int32), count[reg] - 1)]
        anywhere = jnp.minimum((u * N).astype(jnp.int32), N - 1)
        item = jnp.where(jax.random.uniform(k1, shape) < share, in_reg,
                         anywhere)
        x = emb[item] * live[..., None]                   # [Bu, STEP, d]
        p = 0.5 * (1.0 + jnp.einsum("usd,ud->us", x, sl(theta),
                                    precision=hi))
        r = ((jax.random.uniform(k4, shape) < p) & live).astype(jnp.float32)
        dM = jnp.einsum("usd,use->ude", x, x, precision=hi)
        db = jnp.einsum("us,usd->ud", r, x, precision=hi)
        M = jax.lax.dynamic_update_slice_in_dim(M, sl(M) + dM, r0, 0)
        b = jax.lax.dynamic_update_slice_in_dim(b, sl(b) + db, r0, 0)
        return M, b

    M, b = jax.lax.fori_loop(0, block.shape[0], body, (M0, b0))
    return M[unsort], b[unsort]


def warm_history(word, emb, act, *, d: int, cohorts: int, regions: int,
                 top: int, share: float):
    """``(Minv, b, occ)`` of every user after the warm history (device
    arrays); ``act`` is ``activities``' host array."""
    import jax.numpy as jnp
    from repro.kernels.spdinv.ops import spd_inverse
    order, block, step = work_list(act)
    act_sorted = np.where(order >= 0, act[np.maximum(order, 0)], 0)
    unsort = np.empty(len(act), np.int32)
    unsort[order[:len(act)]] = np.arange(len(act), dtype=np.int32)
    M, b = _fold(np.uint32(word), emb, jnp.asarray(order),
                 jnp.asarray(act_sorted, jnp.int32), jnp.asarray(block),
                 jnp.asarray(step), jnp.asarray(unsort), d=d,
                 cohorts=cohorts, regions=regions, top=top, share=share)
    return spd_inverse(M), b, jnp.asarray(act, jnp.int32)
