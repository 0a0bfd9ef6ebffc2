"""What a layer keeps between the steps of paged generation, declared.

A layer's ``state_spec(dtype)`` names each leaf of its generation state
and says of which KIND it is; ``allocate`` turns a tree of such specs into
the device leaves ``init_paged_cache`` returns, and the serving scheduler
(``serving/generation.py``) reads the kinds to know what it may do with a
leaf:

- ``"block"``: state PER TOKEN (K and V rows, ``Hkv * D`` wide: sized by
  the key/value heads of a grouped-query layer; a latent row).  The leaf is
  ``(num_blocks + 1, block_size) + shape``; a sequence addresses it
  through its block table (``serving/paging.py``), the last block is the
  trash block, a full block may be shared by every sequence with the same
  prefix, and a shared block is copied before it is written.
- ``"slot"``: state PER SEQUENCE (a recurrent state, a convolution's
  tail: a delta-rule layer's ``(H, d, d)`` and a state-space layer's
  ``(H, P, N)`` float32 state, the latter stored ``(H / pack, N, pack
  P)``, ``ops/ssd.py``; each with the last ``taps - 1`` rows of its
  short convolution's input).  The leaf is ``(slots + 1,) + shape``; a row addresses it by its
  slot id, the last row is the trash slot, a slot is zeroed when its
  sequence's first chunk arrives, and it cannot be rebuilt from blocks: a
  model with such a leaf gets no prefix hit.
- ``"counter"``: what a step counted (``shape``, written anew by every
  step and never read by one); the scheduler fetches it with the step's
  tokens.
"""

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

BLOCK, SLOT, COUNTER = "block", "slot", "counter"


class StateSpec(NamedTuple):
    kind: str
    shape: Tuple[int, ...]
    dtype: object


def is_spec(x):
    return isinstance(x, StateSpec)


def allocate(specs, num_blocks: int, block_size: int, slots: int = 0):
    """Zero leaves for a tree of ``StateSpec`` (``num_blocks`` and ``slots``
    without their trash entries)."""
    def leaf(s):
        lead = {BLOCK: (int(num_blocks) + 1, int(block_size)),
                SLOT: (int(slots) + 1,), COUNTER: ()}[s.kind]
        return jnp.zeros(lead + tuple(s.shape), s.dtype)

    return jax.tree.map(leaf, specs, is_leaf=is_spec)


def kinds(specs):
    """The tree of kinds, leaf for leaf."""
    return jax.tree.map(lambda s: s.kind, specs, is_leaf=is_spec)


def has_slot_state(specs):
    return any(s.kind == SLOT
               for s in jax.tree.leaves(specs, is_leaf=is_spec))
