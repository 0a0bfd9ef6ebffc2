"""ResNet-50 step decomposition on the real TPU: where do the 119 ms go?

Runs component variants with per-step blocked timing and dumps HLO
statistics (op-kind histogram, conv dtypes) for the full train step.
Usage:  python tools/profile_resnet.py [variant ...]
Variants: fwd fwdbwd full batch256 nocast nhwc_hlo
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _bench(compiled, args, steps=8, chain_idx=2):
    """Dispatch-N-then-fetch-a-VALUE timing: a result value cannot exist
    before its execution completes.  Each dispatch's input batch is
    perturbed by ``0 * (a scalar of the previous output)`` -- a
    structural data dependency chaining step i+1 onto step i, so the
    final value fetch proves ALL N executed serially even if the
    transport overlapped independent dispatches (the extra elementwise
    add costs ~0.2 ms against a >15 ms step)."""
    import jax

    args = list(args)
    x0 = args[chain_idx]
    # warmup one FULL chained iteration so the tiny chain graphs
    # (ravel/getitem/mul/add) compile outside the timed loop
    out = compiled(*args)
    dep = jax.tree_util.tree_leaves(out)[0].ravel()[0]
    args[chain_idx] = x0 + (dep * 0).astype(x0.dtype)
    out = compiled(*args)
    float(jax.tree_util.tree_leaves(out)[0].ravel()[0])
    t0 = time.perf_counter()
    for _ in range(steps):
        out = compiled(*args)
        dep = jax.tree_util.tree_leaves(out)[0].ravel()[0]
        args[chain_idx] = x0 + (dep * 0).astype(x0.dtype)
    float(jax.tree_util.tree_leaves(out)[0].ravel()[0])  # drains the chain
    return (time.perf_counter() - t0) / steps


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu import optim
    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn import CrossEntropyCriterion
    from bigdl_tpu.optim.train_step import make_train_step, _cast_tree

    variants = sys.argv[1:] or ["fwd", "fwdbwd", "full", "batch256", "hlo"]
    batch = int(os.environ.get("PROF_BATCH", "128"))

    model = ResNet(depth=50, class_num=1000)
    model.build(jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.bfloat16))
    params, mstate = model.parameters()[0], model.state()
    crit = CrossEntropyCriterion()
    method = optim.SGD(learning_rate=0.02, momentum=0.9, dampening=0.0,
                       weight_decay=1e-4)
    opt_state = method.init_state(params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, 224, 224, 3)),
                    dtype=jnp.bfloat16)
    t = jnp.asarray(rng.integers(0, 1000, batch), dtype=jnp.int32)
    key = jax.random.key(0)

    def loss_fn(p, ms, xx, tt, kk):
        cp = _cast_tree(p, jnp.bfloat16)
        out, new_ms = model.apply(cp, ms, xx, training=True, rng=kk)
        return crit.apply(out.astype(jnp.float32), tt), new_ms

    if "fwd" in variants:
        f = jax.jit(lambda p, ms, xx, tt, kk: loss_fn(p, ms, xx, tt, kk)[0])
        c = f.lower(params, mstate, x, t, key).compile()
        dt = _bench(c, (params, mstate, x, t, key))
        print(f"fwd only:        {dt*1e3:8.2f} ms")

    if "fwdbwd" in variants:
        g = jax.jit(lambda p, ms, xx, tt, kk: jax.value_and_grad(
            lambda q: loss_fn(q, ms, xx, tt, kk)[0])(p))
        c = g.lower(params, mstate, x, t, key).compile()
        dt = _bench(c, (params, mstate, x, t, key))
        print(f"fwd+bwd:         {dt*1e3:8.2f} ms")

    if "full" in variants:
        step = jax.jit(make_train_step(model, crit, method,
                                       compute_dtype=jnp.bfloat16))
        c = step.lower(params, mstate, opt_state, x, t, key).compile()
        dt = _bench(c, (params, mstate, opt_state, x, t, key), chain_idx=3)
        fl = float(c.cost_analysis().get("flops", 0))
        print(f"full step:       {dt*1e3:8.2f} ms   "
              f"mfu={fl/dt/197e12:.3f} flops={fl:.3e}")

    if "batch256" in variants:
        b2 = 256
        x2 = jnp.asarray(rng.standard_normal((b2, 224, 224, 3)),
                         dtype=jnp.bfloat16)
        t2 = jnp.asarray(rng.integers(0, 1000, b2), dtype=jnp.int32)
        model2 = ResNet(depth=50, class_num=1000)
        model2.build(jax.ShapeDtypeStruct((b2, 224, 224, 3), jnp.bfloat16))
        p2, ms2 = model2.parameters()[0], model2.state()
        step = jax.jit(make_train_step(model2, crit, method,
                                       compute_dtype=jnp.bfloat16))
        os2 = method.init_state(p2)
        c = step.lower(p2, ms2, os2, x2, t2, key).compile()
        dt = _bench(c, (p2, ms2, os2, x2, t2, key), steps=6, chain_idx=3)
        fl = float(c.cost_analysis().get("flops", 0))
        print(f"full step b256:  {dt*1e3:8.2f} ms   "
              f"mfu={fl/dt/197e12:.3f} imgs/s={b2/dt:.0f}")

    if "hlo" in variants:
        step = jax.jit(make_train_step(model, crit, method,
                                       compute_dtype=jnp.bfloat16))
        c = step.lower(params, mstate, opt_state, x, t, key).compile()
        txt = c.as_text()
        import collections
        import re

        kinds = collections.Counter()
        conv_dtypes = collections.Counter()
        for m in re.finditer(r"^\s*(?:ROOT )?%?[\w.-]+ = (\w+)\[[^\]]*\]\{?[^ ]* (\w+)\(", txt, re.M):
            dtype, op = m.group(1), m.group(2)
            kinds[op] += 1
            if op == "convolution":
                conv_dtypes[dtype] += 1
        print("top ops:", kinds.most_common(12))
        print("conv output dtypes:", dict(conv_dtypes))
        n_transpose = txt.count(" transpose(")
        n_convert = txt.count(" convert(")
        print(f"transpose ops: {n_transpose}, convert ops: {n_convert}")
        from bigdl_tpu.utils import hlo as hlo_audit

        mem = hlo_audit.memory_analysis_summary(c)
        if mem:
            # same normalized fields attach_cost stamps on telemetry
            # headers and hlo_audit renders -- one probe, no drift
            print("memory:", json.dumps(mem))


if __name__ == "__main__":
    main()
