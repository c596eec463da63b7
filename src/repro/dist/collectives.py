"""Cross-shard collectives for the length-sharded decode path.

Decode attention over a KV cache sharded on the *length* dim (DESIGN.md
§4): each "model" shard runs flash-decode over its local cache slice,
producing partial (out, m, l) online-softmax stats; the partials combine
exactly with a tiny logsumexp-weighted all-reduce — the only cross-shard
traffic is O(B * H * hd), independent of cache length.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.decode import ref as decode_ref_lib


def flash_decode_combine(out, m, l, axis_name: str):
    """Combine per-shard flash-decode partials across ``axis_name``.

    out: [BH, hd] (locally softmax-normalized), m/l: [BH] (local max /
    normalizer). Exact: equals softmax over the concatenated cache. Shards
    whose slice is entirely masked carry m = -inf-like and get weight 0.
    """
    out32 = out.astype(jnp.float32)
    m_star = jax.lax.pmax(m, axis_name)
    w = l * jnp.exp(m - m_star)  # [BH]
    denom = jax.lax.psum(w, axis_name)
    num = jax.lax.psum(w[:, None] * out32, axis_name)
    return (num / jnp.maximum(denom, 1e-30)[:, None]).astype(out.dtype)


def sharded_flash_decode(q, k_cache, v_cache, length, mesh, *,
                         axis_name: str = "model"):
    """Distributed flash-decode: q [B, H, hd] (replicated), caches
    [B, S, Kv, hd] length-sharded over ``axis_name``; ``length`` is the
    shared valid-prefix scalar (int32). Returns [B, H, hd], replicated.
    """
    b, h, hd = q.shape
    _, s, kv, _ = k_cache.shape
    n_shards = mesh.shape[axis_name]
    if s % n_shards:
        raise ValueError(f"cache length {s} not divisible by {n_shards}")
    scale = 1.0 / (hd ** 0.5)
    s_loc = s // n_shards

    def local(q_rep, k_loc, v_loc, glen):
        shard = jax.lax.axis_index(axis_name)
        # positions this shard owns: [shard*s_loc, (shard+1)*s_loc)
        loc_len = jnp.clip(glen[0] - shard * s_loc, 0, s_loc)
        qf = q_rep.reshape(b * h, hd)
        kf = k_loc.transpose(0, 2, 1, 3).reshape(b * kv, s_loc, hd)
        vf = v_loc.transpose(0, 2, 1, 3).reshape(b * kv, s_loc, hd)
        of, m, l = decode_ref_lib.decode_ref(qf, kf, vf, loc_len, scale=scale)
        of = flash_decode_combine(of, m, l, axis_name)
        return of.reshape(b, h, hd)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P(),
            P(None, axis_name, None, None),
            P(None, axis_name, None, None),
            P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, jnp.asarray(length, jnp.int32).reshape(1))
