"""Ulysses-style all-to-all sequence parallelism.

The second long-context strategy next to the ppermute ring
(parallel/ring_attention.py): instead of rotating K/V blocks around the
ring, one ``lax.all_to_all`` re-shards the activations from
sequence-sharded to HEAD-sharded, full attention runs locally on each
device's head slice, and a second all_to_all restores sequence sharding
(the DeepSpeed-Ulysses communication pattern -- PAPERS.md; public pattern,
re-implemented here on XLA collectives).

Trade-off vs ring: 2 all_to_alls of the activations per attention (cheap
on ICI, O(T*D/P) per device) and exact full-sequence attention with no
per-block online softmax; requires num_heads % P == 0.
"""

import jax
from jax import lax

from bigdl_tpu.nn.attention import dot_product_attention


def ulysses_self_attention(q, k, v, axis_name, causal=False):
    """q, k, v: (N, T_local, H, Dh), sequence sharded over ``axis_name``
    (shard_map context).  -> (N, T_local, H, Dh).
    """
    p = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % p:
        raise ValueError(
            f"ulysses needs num_heads ({h}) divisible by the sequence "
            f"axis size ({p})")

    def seq_to_heads(x):
        # (N, T/P, H, Dh) -> (N, T, H/P, Dh)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    y = dot_product_attention(qg, kg, vg, causal=causal)
    return heads_to_seq(y)
