"""Ring attention: exact attention over a sequence-sharded mesh axis.

No reference analogue (the reference predates transformers; SURVEY.md
section 5 lists long-context as greenfield) -- this is the north-star
'scale the sequence' capability, built the TPU way:

- the sequence axis is sharded over mesh axis ``axis_name``;
- K/V blocks rotate around the ring with ``lax.ppermute`` (neighbour ICI
  hops, no all-gather, so per-chip memory stays O(T_local));
- each hop updates a numerically-stable online softmax (flash-attention
  style: running max ``m``, normaliser ``l``, weighted accumulator ``o``),
  in fp32 regardless of input dtype;
- causal masking uses *global* positions derived from the block's origin
  device, so a fully-masked remote block contributes exactly zero.

Designed to run inside ``shard_map`` (per-device view).  Compute/communicate
overlap is left to XLA's latency-hiding scheduler (the ppermute for hop i+1
is independent of hop i's einsum).
"""

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax, shard_map


def ring_self_attention(q, k, v, axis_name: str, causal: bool = False):
    """Per-device blocks q,k,v: (B, T_local, H, Dh) -> (B, T_local, H, Dh).

    Exact (not approximate): equals single-device softmax attention on the
    gathered sequence, up to fp32 accumulation order.
    """
    n_dev = lax.psum(1, axis_name)
    my = lax.axis_index(axis_name)
    b, t, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    q32 = q.astype(jnp.float32)
    o0 = jnp.zeros((b, h, t, d), jnp.float32)
    l0 = jnp.zeros((b, h, t), jnp.float32)
    m0 = jnp.full((b, h, t), -jnp.inf, jnp.float32)

    qpos = my * t + jnp.arange(t)  # global positions of local queries

    def hop(carry, i):
        o, l, m, kb, vb = carry
        src = (my + i) % n_dev  # origin device of the current k/v block
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32,
                            kb.astype(jnp.float32)) * scale
        if causal:
            kpos = src * t + jnp.arange(t)
            mask = (kpos[None, :] <= qpos[:, None]).astype(jnp.float32)
        else:
            mask = jnp.ones((t, t), jnp.float32)
        scores = jnp.where(mask > 0, scores, -jnp.inf)

        bm = jnp.max(scores, axis=-1)                      # (b,h,q)
        new_m = jnp.maximum(m, bm)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(scores - safe_m[..., None]) * mask     # masked -> 0
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vb.astype(jnp.float32))

        # rotate k/v to the next device (receive the block of my+i+1)
        perm = [(j, (j - 1) % n_dev) for j in range(n_dev)]
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
        return (o, l, new_m, kb, vb), None

    (o, l, m, _, _), _ = lax.scan(hop, (o0, l0, m0, k, v),
                                  jnp.arange(n_dev))
    out = o / jnp.maximum(l, 1e-30)[..., None]             # (b,h,q,d)
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def sequence_shard_attention(q, k, v, mesh, axis_name="seq", causal=False):
    """Convenience wrapper: global (B, T, H, D) arrays -> shard_map'd ring."""
    from jax.sharding import PartitionSpec as P

    fn = shard_map(
        partial(ring_self_attention, axis_name=axis_name, causal=causal),
        mesh=mesh,
        in_specs=(P(None, axis_name), P(None, axis_name), P(None, axis_name)),
        out_specs=P(None, axis_name),
        check_vma=False,
    )
    return fn(q, k, v)
