"""In-jit token sampling: temperature / top-k / top-p drawn INSIDE the
compiled decode step.

A decode step that only argmaxes stays pure but locks serving to
greedy output.  The obvious
extension -- ship logits to the host and sample there -- adds a
device->host round-trip of ``(slots, vocab)`` floats per generated
token, exactly the transfer the decode path was built to avoid.
Instead sampling runs inside the jitted step:

- every slot carries its sampling knobs as RUNTIME ARRAYS (temperature,
  top_k, top_p, seed -- one row each), so greedy and sampled slots
  share one executable and changing knobs never recompiles;
- randomness is ``fold_in(PRNGKey(seed), position)`` per row: the draw
  for the token at sequence position ``p`` depends only on (seed, p),
  so a given (seed, prompt) replays the same stream regardless of which
  slot it lands in, how prefill was chunked, or what its neighbours do
  -- deterministic replay is what makes fleet retries idempotent;
- the draw itself is Gumbel-max over the masked, temperature-scaled
  logits (argmax(logits/T + gumbel) samples the softmax exactly), which
  needs no normalization and no host sync.

``temperature <= 0`` means greedy, and the work follows what the tick's
rows ask for, decided on the device from the ``temperature`` array:

- a tick whose rows are ALL greedy runs one argmax over the logits and
  nothing else -- no sort, no gather, no softmax, no draw;
- a tick with at least one sampling row runs the sampled branch over
  every row: ONE two-operand sort gives the ranked logits and their ids
  together (the keys of the sort ARE the ranked logits, so nothing is
  gathered back over the vocabulary), then the cuts, the Gumbel draw and
  a one-element-a-row gather of the picked id; its greedy rows still
  take the argmax.

The branch is a real conditional (``lax.cond`` on a runtime value), so
one executable still serves both kinds of tick; it stays one as long as
``sample_tokens`` is never put under ``vmap``, which would turn the
conditional into a select that runs both sides.
"""

import jax
import jax.numpy as jnp
from jax import lax


class SamplingParams:
    """Per-request sampling knobs, validated once at submission.

    ``temperature <= 0`` is greedy (top_k/top_p ignored); ``top_k <= 0``
    disables the k-cut; ``top_p`` keeps the smallest set of tokens whose
    probability mass reaches it (``1.0`` disables, ``0.0`` degenerates
    to greedy-at-temperature).  ``seed=None`` asks the scheduler to mint
    one -- pass an explicit seed for deterministic replay.
    """

    __slots__ = ("temperature", "top_k", "top_p", "seed")

    def __init__(self, temperature=0.0, top_k=0, top_p=1.0, seed=None):
        temperature = float(temperature)
        top_k = int(top_k)
        top_p = float(top_p)
        if not temperature == temperature:            # NaN
            raise ValueError("temperature must not be NaN")
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if seed is not None:
            seed = int(seed)
            if not 0 <= seed < 2 ** 31:
                raise ValueError(f"seed must fit in 31 bits, got {seed}")
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.seed = seed

    @property
    def greedy(self):
        return self.temperature <= 0.0

    def __repr__(self):
        return (f"SamplingParams(temperature={self.temperature}, "
                f"top_k={self.top_k}, top_p={self.top_p}, "
                f"seed={self.seed})")


GREEDY = SamplingParams()


def _greedy(logits, *_knobs):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _sampled(logits, temperature, top_k, top_p, seed, position):
    vocab = logits.shape[-1]
    # Work in sorted order (descending): top-k is a rank cut and top-p a
    # cumulative-mass cut over the same sort.  The sort carries the ids
    # along (what ``argsort`` does, stably) and its keys are the ranked
    # logits, negated twice, which is exact.
    neg, order = lax.sort(
        (-logits, lax.broadcasted_iota(jnp.int32, logits.shape, 1)),
        dimension=-1, num_keys=1, is_stable=True)
    ranked = -neg
    temp = jnp.maximum(temperature, 1e-6).astype(jnp.float32)[:, None]
    scaled = ranked / temp

    rank = jnp.arange(vocab, dtype=jnp.int32)[None, :]
    k = jnp.where(top_k > 0, top_k, vocab).astype(jnp.int32)[:, None]
    keep = rank < k
    probs = jax.nn.softmax(scaled, axis=-1)
    # keep a token iff the mass STRICTLY BEFORE it is < top_p: the
    # smallest prefix reaching top_p survives, and rank 0 always does
    # (mass-before is 0), so top_p=0.0 degenerates to argmax not to an
    # empty support
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep = keep & (mass_before < top_p[:, None])
    keep = keep.at[:, 0].set(True)

    masked = jnp.where(keep, scaled, -jnp.inf)
    # Gumbel-max: argmax(masked + G) ~ softmax(masked).  One fold_in per
    # row keyed purely on (seed, position).
    keys = jax.vmap(
        lambda s, p: jax.random.fold_in(
            jax.random.PRNGKey(s.astype(jnp.uint32)), p))(
        seed, position.astype(jnp.uint32))
    gumbel = jax.vmap(lambda key, row: jax.random.gumbel(
        key, row.shape, dtype=row.dtype))(keys, masked)
    pick = jnp.argmax(masked + gumbel, axis=-1)
    drawn = jnp.take_along_axis(
        order, pick[:, None], axis=-1)[:, 0].astype(jnp.int32)
    return jnp.where(temperature > 0.0, drawn, _greedy(logits))


def sample_tokens(logits, temperature, top_k, top_p, seed, position):
    """Draw one token per row from ``logits`` -- traceable, fixed-shape.

    logits       (rows, vocab) float
    temperature  (rows,) float; <= 0 selects greedy for that row
    top_k        (rows,) int32; <= 0 disables
    top_p        (rows,) float in [0, 1]
    seed         (rows,) int32/uint32 per-request RNG seed
    position     (rows,) int32 sequence position of the token being
                 drawn -- the fold-in counter, so the draw is a pure
                 function of (seed, position)

    Returns (rows,) int32 token ids.
    """
    # both branches are module-level functions of the same operands, so a
    # bare call traces them once a shape and not once a call
    return lax.cond(jnp.any(temperature > 0.0), _sampled, _greedy,
                    logits.astype(jnp.float32), temperature, top_k, top_p,
                    seed, position)
