"""The one general generator of inputs.  A traffic mix is a data file of
parameters; everything drawn here comes from ``--seed`` alone, so the same
seed gives the same inputs and every seed the same SET of sizes (lengths
are drawn once from a fixed seed and only their order and the ids depend
on ``--seed``): a run's work does not depend on its seed."""

import numpy as np

#: lengths of a mix are drawn from this seed, whatever ``--seed`` is
SIZES_SEED = 20260930


def rng_for(seed, stream=0):
    """A generator for ``--seed`` (any whole number, 2**31 and beyond)."""
    return np.random.default_rng([int(seed), int(stream)])


def prng_key(seed):
    """A JAX key for ``--seed`` (folded in two halves: it may pass 2**31).
    Always handed to a compiled program as an ARGUMENT, so that no seed is
    compiled in and one cached program serves every seed."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              seed >> 31)


def markov_tokens(seed, n_seq, seq_len, vocab, branch=4):
    """Next-token pairs from a seeded Markov stream (a copy of the
    program's ``models/transformer.py:synthetic_corpus``, so that a falling
    loss means something): every token is followed by one of ``branch``
    successors.  Returns int32 ``(x, y)`` of shape (n_seq, seq_len), all
    rows different."""
    rng = rng_for(seed, 1)
    trans = rng.integers(0, vocab, size=(vocab, branch))
    toks = np.empty((n_seq, seq_len + 1), np.int32)
    toks[:, 0] = rng.choice(vocab, n_seq, replace=n_seq > vocab)
    choice = rng.integers(0, branch, size=(n_seq, seq_len))
    for t in range(seq_len):
        toks[:, t + 1] = trans[toks[:, t], choice[:, t]]
    return toks[:, :-1], toks[:, 1:]


def uniform_images(seed, n, size, channels, classes):
    """A pool of float32 images in [0, 1) and int32 labels."""
    rng = rng_for(seed, 2)
    pool = rng.random((n, size, size, channels), dtype=np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    return pool, labels


def lognormal_lengths(n, median, sigma, low, high):
    """``n`` whole lengths, log-normal around ``median``, clipped.  Drawn
    from ``SIZES_SEED``: the SET is the same for every ``--seed``."""
    rng = np.random.default_rng([SIZES_SEED, n, int(median)])
    raw = np.exp(np.log(median) + sigma * rng.standard_normal(n))
    return np.clip(np.rint(raw), low, high).astype(np.int64)


def generate_requests(seed, spec, vocab):
    """The request stream of a ``closed_loop_generate`` mix: a list of
    ``(prompt ids, max_new_tokens)`` that clients take in turn.  The SET of
    ``pool`` sizes is as ``lognormal_lengths`` gives it, the same for every
    seed, and about as many as one window serves; the stream is ``epochs``
    passes over it, each in an order of its own and with fresh ids that
    ``--seed`` decides (so no pass finds the last one's prefixes cached),
    and a window sees about the same sizes whatever the seed.  Prompt +
    output never passes ``spec['max_total']``."""
    n = int(spec["pool"])
    p, o = spec["prompt"], spec["output"]
    prompts = lognormal_lengths(n, p["median"], p["sigma"], p["min"],
                                p["max"])
    outputs = lognormal_lengths(n + 1, o["median"], o["sigma"], o["min"],
                                o["max"])[:n]
    outputs = np.minimum(outputs, int(spec["max_total"]) - prompts)
    rng = rng_for(seed, 3)
    out = []
    for _ in range(int(spec.get("epochs", 1))):
        for i in rng.permutation(n):
            ids = rng.integers(0, vocab, int(prompts[i])).astype(np.int32)
            out.append((ids, int(outputs[i])))
    return out
