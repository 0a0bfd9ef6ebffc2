"""Binary TreeLSTM sentiment classification (reference:
example/treeLSTMSentiment -- SST trees + GloVe; here synthetic sentences
over a fixed complete parse tree, with a class-correlated leaf signal so
the model provably learns).

    python examples/tree_lstm_sentiment.py --steps 60
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

def complete_tree(leaves):
    """Dense tree encoding over ``leaves`` words (nNodes, 3):
    leaf rows [0, 0, word_pos_1based]; internal [left, right, 0]; root
    flagged -1 in column 3 (see nn/tree.py BinaryTreeLSTM)."""
    import numpy as np

    n_nodes = 2 * leaves - 1
    t = np.zeros((n_nodes, 3), np.float32)
    for i in range(leaves):
        t[i] = [0, 0, i + 1]
    nxt = leaves
    level = list(range(1, leaves + 1))       # 1-based node ids
    while len(level) > 1:
        parents = []
        for a, b in zip(level[0::2], level[1::2]):
            t[nxt] = [a, b, 0]
            parents.append(nxt + 1)
            nxt += 1
        level = parents
    t[n_nodes - 1][2] = -1                   # root flag
    return t


def main(argv=None):
    import numpy as np
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim

    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--dim", type=int, default=16)
    args = p.parse_args(argv)

    rng = np.random.default_rng(0)
    n, leaves, vocab = 256, 8, 50
    tree = complete_tree(leaves)
    n_nodes = tree.shape[0]

    toks = rng.integers(2, vocab, (n, leaves)).astype(np.int32)
    labels = rng.integers(0, 2, n).astype(np.int32)
    pos, neg = labels == 1, labels == 0
    toks[pos, :4] = rng.integers(2, vocab // 2, (int(pos.sum()), 4))
    toks[neg, :4] = rng.integers(vocab // 2, vocab, (int(neg.sum()), 4))

    embed = nn.LookupTable(vocab, args.dim)
    tree_lstm = nn.BinaryTreeLSTM(args.dim, args.dim)
    head = nn.Linear(args.dim, 2)
    crit = nn.CrossEntropyCriterion()
    method = optim.Adam(learning_rate=1e-2)

    from bigdl_tpu.nn.module import child_rng
    from bigdl_tpu.utils.random_generator import RNG

    key = RNG.next_key()
    emb_spec = jax.ShapeDtypeStruct((32, leaves), jnp.int32)
    p_embed, _ = embed.setup(child_rng(key, 0), emb_spec)
    hid_spec = jax.ShapeDtypeStruct((32, leaves, args.dim), jnp.float32)
    p_tree, _ = tree_lstm.setup(child_rng(key, 1), hid_spec)
    p_head, _ = head.setup(
        child_rng(key, 2),
        jax.ShapeDtypeStruct((32, args.dim), jnp.float32))
    params = {"embed": p_embed, "tree": p_tree, "head": p_head}
    opt_state = method.init_state(params)
    trees = jnp.asarray(np.broadcast_to(tree, (32, n_nodes, 3)))

    def forward(q, x):
        e, _ = embed.apply(q["embed"], (), x)
        h, _ = tree_lstm.apply(q["tree"], (), (e, trees[: x.shape[0]]))
        logits, _ = head.apply(q["head"], (), h[:, -1])   # root node state
        return logits

    @jax.jit
    def step(q, os_, x, t):
        def loss_fn(qq):
            return crit.apply(forward(qq, x).astype(jnp.float32), t)

        loss, g = jax.value_and_grad(loss_fn)(q)
        nq, no = method.update(g, os_, q)
        return nq, no, loss

    for i in range(args.steps):
        idx = rng.integers(0, n, 32)
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(toks[idx]),
                                       jnp.asarray(labels[idx]))
        if i % 10 == 0:
            print(f"step {i}: loss {float(loss):.4f}")

    logits = forward(params, jnp.asarray(toks[:32]))
    acc = float((np.asarray(logits).argmax(1) == labels[:32]).mean())
    print(f"train accuracy: {acc:.3f}")


if __name__ == "__main__":
    main()
