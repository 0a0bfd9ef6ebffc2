"""Int8 vs bf16 ResNet-50 INFERENCE on-chip A/B.

The reference's BigQuant headline (docs/docs/whitepaper.md:192, Fig. 10):
~4x model-size reduction and up to ~2x inference speedup at <0.1%
accuracy drop. This driver measures the TPU-native analogue: the same
built model served in bf16 vs rewritten by ``nn.quantized.quantize``
(int8 weights, dynamic activation quant, MXU int32 accumulation).

Timing is the chained method (docs/performance.md): each
dispatch's input depends on the previous output's value, so the final
fetch cannot complete before every step executed.

    python tools/quant_perf.py              # batch 128, 16 steps
    QP_BATCH=256 QP_STEPS=20 python tools/quant_perf.py
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(batch=128, steps=16, depth=50, image=224, classes=1000):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.models.resnet import ResNet
    from bigdl_tpu.nn.quantized import model_bytes, quantize
    from bigdl_tpu.optim.train_step import make_eval_step

    dev = jax.devices()[0]
    model = ResNet(depth=depth, class_num=classes)
    model.build(jax.ShapeDtypeStruct((batch, image, image, 3), jnp.bfloat16))
    model.evaluate()

    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal((batch, image, image, 3)),
                     jnp.bfloat16)
    results = {"platform": dev.platform, "batch": batch, "steps": steps}

    def bench(tag, step_fn, params, mstate):
        fn = jax.jit(lambda p, s, x: step_fn(p, s, x))
        out = fn(params, mstate, x0)                      # compile+warm
        float(out.ravel()[0].astype(jnp.float32))
        x = x0
        t0 = time.perf_counter()
        for _ in range(steps):
            out = fn(params, mstate, x)
            # chain: next input depends on this output's value
            x = x0 + (out.ravel()[0] * 0).astype(x0.dtype)
        float(out.ravel()[0].astype(jnp.float32))         # drain
        dt = time.perf_counter() - t0
        rec = {"tag": tag, "sec_per_step": round(dt / steps, 5),
               "imgs_per_sec": round(batch * steps / dt, 1),
               "param_bytes": model_bytes(params)}
        results[tag] = rec
        print(json.dumps(rec), flush=True)
        return rec

    eval_step = make_eval_step(model, compute_dtype=jnp.bfloat16)
    params, mstate = model.parameters()[0], model.state()
    # a real bf16 server pre-casts weights ONCE; timing the fp32->bf16
    # cast (and fp32 HBM reads) every step would inflate int8's speedup
    params16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, params)
    b = bench("bf16", lambda p, s, x: eval_step(p, s, x), params16, mstate)

    # capture BEFORE quantize(): the rewrite mutates the param dicts in
    # place, so `params` aliases the int8 tree afterwards
    fp32_bytes = model_bytes(params)
    quantize(model)                                       # in-place rewrite
    qparams, qmstate = model.parameters()[0], model.state()
    q = bench("int8", lambda p, s, x: model.apply(
        p, s, x, training=False, rng=None)[0], qparams, qmstate)

    results["speedup"] = round(b["sec_per_step"] / q["sec_per_step"], 3)
    # reference Fig. 10 compares the full-precision MODEL FILE to int8
    # (~4x); the served bf16 weights are already half of fp32, so the
    # serving-memory ratio is ~2x
    results["size_ratio_vs_fp32"] = round(fp32_bytes / q["param_bytes"], 2)
    results["size_ratio_vs_bf16"] = round(
        b["param_bytes"] / q["param_bytes"], 2)
    print(json.dumps({"summary": results}), flush=True)
    return results


def main():
    from bigdl_tpu.utils.config import enable_compilation_cache
    enable_compilation_cache()
    run(batch=int(os.environ.get("QP_BATCH", "128")),
        steps=int(os.environ.get("QP_STEPS", "16")),
        depth=int(os.environ.get("QP_DEPTH", "50")),
        image=int(os.environ.get("QP_IMAGE", "224")))


if __name__ == "__main__":
    main()
