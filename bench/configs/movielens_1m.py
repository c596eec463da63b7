"""MovieLens 1M: a ratings table of (user, movie, stars) rows, stored
sorted by user id, and low-rank matrix factorization (``lmf``) over it.

The generator is the benchmark's own, made into one jitted call on the
device from the seed. User degrees are fixed by quantile (the seed only
assigns them to user ids); at the published shape they read minimum 20,
median 96 and maximum 2,314 ratings per user, and the most-rated movie
has about 3,400 ratings (3,416-3,482 for the keys PRNGKey(0)-(2)). Each
user rates distinct movies, drawn without replacement by a Zipf-like
popularity. Stars are a planted low-rank score rounded and clipped to
1-5, mean about 3.58.

The reference is sequential incremental gradient descent in plain
``jax.numpy`` at ``Precision.HIGHEST``: one ``lax.scan`` step per rating
that reads row i of the user factors and row j of the movie factors and
updates those two rows, with the catalog's diminishing schedule from the
configuration's ``alpha0``, and mu apportioned to each rating by the
mean user and movie degrees. It restates the program's documented
initialisation (``init_scale`` N(0, 1) factors from ``PRNGKey(seed)``,
split into the user and the movie key) and imports nothing of it.
"""

from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

import reference as ref_lib

F32 = jnp.float32


def user_degrees(cfg) -> np.ndarray:
    """Ratings per user, fixed by quantile: a lognormal clipped to
    ``[min_user_ratings, max_user_ratings]``, scaled so that the degrees
    sum to ``ratings`` exactly (largest remainders round)."""
    users, total = cfg["users"], cfg["ratings"]
    lo = cfg["min_user_ratings"]
    hi = min(cfg["max_user_ratings"], cfg["movies"])
    if not lo * users <= total <= hi * users:
        raise ValueError(f"{total} ratings cannot give {users} users "
                         f"{lo}..{hi} ratings each")
    normal = statistics.NormalDist()
    z = np.array([normal.inv_cdf((k + 0.5) / users) for k in range(users)])
    shape = np.exp(cfg["generator"]["user_degree_sigma"] * z)
    a, b = 0.0, float(total)
    for _ in range(200):  # bisect the scale that hits the total
        c = 0.5 * (a + b)
        a, b = (c, b) if np.clip(c * shape, lo, hi).sum() < total else (a, c)
    deg = np.clip(b * shape, lo, hi)
    base = np.floor(deg).astype(np.int64)
    short = total - int(base.sum())
    room = np.where(base < hi, deg - base, -1.0)
    base[np.argsort(-room, kind="stable")[:short]] += 1
    return base.astype(np.int32)


@functools.partial(jax.jit, static_argnames=(
    "users", "movies", "ratings", "rank", "offset", "center", "user_bias",
    "movie_bias", "interaction", "noise"))
def _ratings(key, degrees, *, users, movies, ratings, rank, offset, center,
             user_bias, movie_bias, interaction, noise):
    kd, km, kg, ku, kv, kp, kq, kn = jax.random.split(key, 8)
    deg = degrees[jax.random.permutation(kd, users)]
    # movie ids take the popularity ranks in a seeded order
    pop_rank = jax.random.permutation(km, movies)
    logp = -jnp.log(pop_rank.astype(F32) + offset)
    # each user's movies: the top deg[u] of Gumbel-perturbed log
    # popularities, i.e. a weighted draw without replacement
    keys = logp[None, :] + jax.random.gumbel(kg, (users, movies), F32)
    picked = jnp.argsort(-keys, axis=1).astype(jnp.int32)
    take = jnp.arange(movies)[None, :] < deg[:, None]
    flat = jnp.nonzero(take.reshape(-1), size=ratings)[0]
    i = (flat // movies).astype(jnp.int32)  # ascending: sorted by user
    j = picked.reshape(-1)[flat]
    p = jax.random.normal(kp, (users, rank), F32) / jnp.sqrt(rank)
    q = jax.random.normal(kq, (movies, rank), F32)
    score = (center
             + user_bias * jax.random.normal(ku, (users,), F32)[i]
             + movie_bias * jax.random.normal(kv, (movies,), F32)[j]
             + interaction * jnp.sum(p[i] * q[j], axis=-1)
             + noise * jax.random.normal(kn, (ratings,), F32))
    return {"i": i, "j": j, "v": jnp.clip(jnp.round(score), 1.0, 5.0)}


def generate(cfg, key):
    """The ratings table ``{i, j, v}``, sorted by user id, on the device."""
    g = cfg["generator"]
    return _ratings(
        key, jnp.asarray(user_degrees(cfg)),
        users=cfg["users"], movies=cfg["movies"], ratings=cfg["ratings"],
        rank=int(g["planted_rank"]), offset=float(g["movie_offset"]),
        center=float(g["center"]), user_bias=float(g["user_bias"]),
        movie_bias=float(g["movie_bias"]),
        interaction=float(g["interaction"]), noise=float(g["noise"]),
    )


def rows(cfg) -> int:
    return cfg["ratings"]


def task_args(cfg, task: str) -> dict:
    t = cfg["techniques"][task]
    return {"n_rows": cfg["users"], "n_cols": cfg["movies"],
            "rank": t["rank"], "mu": t["mu"], "alpha0": t["alpha0"]}


def _degrees(cfg):
    """Mean ratings per user and per movie: each rating carries mu over
    these shares of its two rows' squared norms."""
    n = cfg["ratings"]
    return max(n / cfg["users"], 1.0), max(n / cfg["movies"], 1.0)


@jax.jit
def _epoch(left, right, i, j, v, alphas, mu, deg_user, deg_movie):
    def body(lr, ex):
        left, right = lr
        ii, jj, vv, a = ex
        li, rj = left[ii], right[jj]
        err = jnp.dot(li, rj, precision=ref_lib.HIGHEST) - vv
        gl = 2 * err * rj + 2 * mu * li / deg_user
        gr = 2 * err * li + 2 * mu * rj / deg_movie
        return (left.at[ii].add(-a * gl), right.at[jj].add(-a * gr)), None

    return jax.lax.scan(body, (left, right), (i, j, v, alphas))[0]


def reference_fit(cfg, data, task, seed, epochs, ordering, dtype=F32):
    """The factors ``{"L", "R"}`` after ``epochs`` epochs of ``task`` from
    the query's initial factors, computed in ``dtype`` (float32 for the
    check, lower for the control)."""
    if task != "lmf":
        raise ValueError(f"no reference for {task!r}")
    t = cfg["techniques"][task]
    kl, kr = jax.random.split(jax.random.PRNGKey(seed))
    left = t["init_scale"] * jax.random.normal(
        kl, (cfg["users"], t["rank"]), F32)
    right = t["init_scale"] * jax.random.normal(
        kr, (cfg["movies"], t["rank"]), F32)
    left, right = left.astype(dtype), right.astype(dtype)
    deg_user, deg_movie = _degrees(cfg)
    scalars = [jnp.asarray(x, dtype) for x in (t["mu"], deg_user, deg_movie)]
    i, j, v = data["i"], data["j"], data["v"].astype(dtype)
    n = i.shape[0]
    for e, perm in enumerate(ref_lib.epoch_orders(ordering, seed, n, epochs)):
        ie, je, ve = (i, j, v) if perm is None else (i[perm], j[perm], v[perm])
        alphas = ref_lib.diminishing(t["alpha0"], n, e * n, n, dtype)
        left, right = _epoch(left, right, ie, je, ve, alphas, *scalars)
    return {"L": left.astype(F32), "R": right.astype(F32)}


@jax.jit
def _loss(left, right, i, j, v, mu):
    err = jnp.sum(left[i] * right[j], axis=-1) - v
    return jnp.sum(err * err) + mu * (jnp.sum(left * left)
                                      + jnp.sum(right * right))


def reference_loss(cfg, data, task, model, dtype=F32) -> float:
    """The squared error over every rating plus mu times the factors'
    squared Frobenius norms, computed in ``dtype``."""
    mu = jnp.asarray(cfg["techniques"][task]["mu"], dtype)
    return float(_loss(model["L"].astype(dtype), model["R"].astype(dtype),
                       data["i"], data["j"], data["v"].astype(dtype), mu))


def epoch_work(cfg, task, lanes: int):
    """(operations, HBM bytes) of one epoch of ``lanes`` fits that share
    the table, from the published shape: per rating and lane a 2r dot,
    two 3r row gradients and two 2r row updates (12 r operations), and
    the two factor rows read and written (16 r bytes); the rating itself
    (two int32 ids and a float32, 12 bytes) is read once for all lanes."""
    n, r = cfg["ratings"], cfg["techniques"][task]["rank"]
    return 12 * r * n * lanes, n * (12 + 16 * r * lanes)
