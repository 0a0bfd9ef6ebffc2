"""Lint-style audit of the compiled train step's HLO.

Builds each driver's jitted train step on a tiny synthetic model,
AOT-compiles it once, and reports what the optimized program actually
says (``bigdl_tpu/utils/hlo.py``):

- ``input_output_alias`` coverage -- which large param/opt-state planes
  are donated (aliased in-place) vs silently double-buffered,
- the dtype of the dot/conv path (an f32 matmul in a step that claims
  bf16 is half the MXU),
- collective and fusion counts.

Exit status is the GATE: nonzero when any audited driver leaves a large
float leaf of an expected-donated plane (params / opt-state) without an
input/output alias.  CI runs the fast local-driver smoke
(tests/test_hlo_audit.py); the full sweep covers all three drivers::

    python -m tools.hlo_audit                     # all drivers, JSON
    python -m tools.hlo_audit --driver local      # fast smoke
    python -m tools.hlo_audit --format text

The same donation/dtype/collective summary (from the cheap lowering
text, no second compile) is stamped on every telemetry run header by
``StepTelemetry.attach_cost`` -- see docs/observability.md, "Compiled
step audit".
"""

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:                       # python tools/hlo_audit.py
    sys.path.insert(0, REPO)

DRIVERS = ("local", "distri", "tp")

#: per-driver (arg labels, expected-donated planes)
_LABELS = {
    "local": (("params", "mstate", "opt_state", "input", "target", "rng"),
              ("params", "opt_state")),
    "distri": (("params_flat", "mstate", "opt_state", "input", "target",
                "rng"),
               ("params_flat", "opt_state")),
    "tp": (("params", "opt_state", "input", "target", "rng"),
           ("params", "opt_state")),
}


def _mlp(hidden=32):
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu.utils.random_generator import RNG

    RNG.set_seed(0)
    m = (nn.Sequential().add(nn.Linear(16, hidden)).add(nn.ReLU())
         .add(nn.Linear(hidden, 4)))
    m.build(jax.ShapeDtypeStruct((8, 16), jnp.float32))
    return m


def _batch(n=8):
    import numpy as np
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((n, 16)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
    return x, y


def audit_local(min_bytes, donate=True):
    """The LocalOptimizer step: jit(make_train_step, donate 0,1,2).
    ``donate=False`` is the self-test hook proving the gate trips."""
    import jax
    import jax.numpy as jnp

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.optim.train_step import make_train_step
    from bigdl_tpu.utils import hlo

    model = _mlp()
    method = optim.SGD(learning_rate=0.05, momentum=0.9, dampening=0.0)
    params, mstate = model.parameters()[0], model.state()
    opt_state = method.init_state(params)
    step = make_train_step(model, nn.CrossEntropyCriterion(), method,
                           compute_dtype=jnp.bfloat16)
    jitted = jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())
    x, y = _batch()
    labels, expected = _LABELS["local"]
    summary = hlo.audit_step(
        jitted, params, mstate, opt_state, x, y, jax.random.key(0),
        arg_labels=labels, min_bytes=min_bytes)
    return summary, expected


def audit_distri(min_bytes):
    """The DistriOptimizer dp+ZeRO-1 step over the available devices."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.optim.distri_optimizer import make_distri_train_step
    from bigdl_tpu.parallel.zero import FlatParamSpace
    from bigdl_tpu.utils import hlo
    from bigdl_tpu.utils.engine import Engine

    mesh = Engine.build_mesh()
    n_dev = mesh.size
    model = _mlp()
    method = optim.SGD(learning_rate=0.05, momentum=0.9, dampening=0.0)
    params_tree = model.parameters()[0]
    flat_space = FlatParamSpace(params_tree, n_dev)
    params_flat = flat_space.flatten(params_tree)
    opt_state_eval = jax.eval_shape(
        method.init_state,
        jax.ShapeDtypeStruct((flat_space.padded_size,), jnp.float32))
    opt_shardings = jax.tree.map(
        lambda l: NamedSharding(mesh, P("data") if l.ndim >= 1 else P()),
        opt_state_eval)
    opt_state = jax.jit(method.init_state, out_shardings=opt_shardings)(
        jnp.zeros((flat_space.padded_size,), jnp.float32))
    _, wrap = make_distri_train_step(
        model, nn.CrossEntropyCriterion(), method, flat_space, mesh,
        compute_dtype=jnp.bfloat16)
    step = wrap(opt_state_eval)
    x, y = _batch(n=8 * n_dev)
    sharding = NamedSharding(mesh, P("data"))
    x, y = jax.device_put(x, sharding), jax.device_put(y, sharding)
    labels, expected = _LABELS["distri"]
    summary = hlo.audit_step(
        step, params_flat, model.state(), opt_state, x, y,
        jax.random.key(0), arg_labels=labels, min_bytes=min_bytes)
    return summary, expected


def audit_tp(min_bytes):
    """The StrategyOptimizer tensor-parallel step (a tiny TransformerLM
    over a data x model mesh; degenerates to (1, 1) on one device)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bigdl_tpu.nn as nn
    from bigdl_tpu import optim
    from bigdl_tpu.parallel.tp import (TRANSFORMER_TP_RULES,
                                       init_opt_state_sharded,
                                       make_tp_train_step, shard_params)
    from bigdl_tpu.utils import hlo
    from bigdl_tpu.utils.engine import Engine
    from bigdl_tpu.utils.random_generator import RNG

    n_dev = len(jax.devices())
    model_deg = 2 if n_dev % 2 == 0 else 1
    mesh = Engine.build_mesh((n_dev // model_deg, model_deg),
                             ("data", "model"))
    RNG.set_seed(0)
    model = nn.TransformerLM(64, 32, 2, 2, max_len=16)
    model.build(jax.ShapeDtypeStruct((2 * mesh.shape["data"], 8),
                                     jnp.int32))
    params_tree = model.parameters()[0]
    crit = nn.TimeDistributedCriterion(
        nn.FusedSoftmaxCrossEntropyCriterion())
    method = optim.Adam(learning_rate=1e-3)
    step = make_tp_train_step(model, crit, method, mesh,
                              rules=TRANSFORMER_TP_RULES)(params_tree)
    params = shard_params(params_tree, mesh, TRANSFORMER_TP_RULES)
    opt_state = init_opt_state_sharded(method, params, mesh,
                                       TRANSFORMER_TP_RULES)
    rng = np.random.default_rng(0)
    from jax.sharding import NamedSharding, PartitionSpec as P
    sharding = NamedSharding(mesh, P("data"))
    x = jax.device_put(
        jnp.asarray(rng.integers(0, 64, (2 * mesh.shape["data"], 8)),
                    jnp.int32), sharding)
    y = jax.device_put(
        jnp.asarray(rng.integers(0, 64, (2 * mesh.shape["data"], 8)),
                    jnp.int32), sharding)
    labels, expected = _LABELS["tp"]
    summary = hlo.audit_step(
        step, params, opt_state, x, y, jax.random.key(0),
        arg_labels=labels, min_bytes=min_bytes)
    return summary, expected


def run_audits(drivers, min_bytes=2048, donate=True, gate_drivers=None):
    """-> (report dict, gate_ok).  ``report["drivers"][name]`` is the
    hlo summary plus its per-driver gate verdict.  The EXIT gate spans
    ``gate_drivers`` (default: every audited driver) -- per-driver
    verdicts are always reported either way."""
    from bigdl_tpu.utils import hlo

    fns = {"local": lambda: audit_local(min_bytes, donate=donate),
           "distri": lambda: audit_distri(min_bytes),
           "tp": lambda: audit_tp(min_bytes)}
    gate_drivers = drivers if gate_drivers is None else gate_drivers
    report = {"min_bytes": min_bytes, "drivers": {}}
    failed = []
    for name in drivers:
        summary, expected = fns[name]()
        bad = hlo.undonated_planes(summary, expected=expected)
        summary["gate"] = {
            "expected_donated": list(expected),
            "undonated_planes": [
                {"plane": label, "leaves": leaves} for label, leaves in bad],
            "ok": not bad,
        }
        report["drivers"][name] = summary
        if bad and name in gate_drivers:
            failed.append(name)
    report["gate"] = {"failed": failed, "ok": not failed,
                      "gated_drivers": [d for d in drivers
                                        if d in gate_drivers]}
    return report, not failed


def format_text(report):
    from bigdl_tpu.utils import hlo

    out = []
    for name, s in report["drivers"].items():
        out.append(f"== {name} train step ({s['source']} audit) ==")
        out.extend(hlo.format_summary_lines(s))
        g = s["gate"]
        out.append("  gate: " + ("OK" if g["ok"] else "FAIL ("
                   + ", ".join(p["plane"]
                               for p in g["undonated_planes"]) + ")"))
    out.append("gate: " + ("OK" if report["gate"]["ok"] else
                           "FAIL " + str(report["gate"]["failed"])))
    return "\n".join(out)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--driver", action="append", choices=DRIVERS + ("all",),
                    help="driver step(s) to audit (default: all)")
    ap.add_argument("--min-bytes", type=int, default=2048,
                    help="smallest float leaf the donation gate cares "
                         "about (scalar counters are not leaks)")
    ap.add_argument("--format", choices=("json", "text"), default="json",
                    help="json (default; strict, machine-checkable) or "
                         "text")
    ap.add_argument("--no-donate", action="store_true",
                    help="self-test hook: build the local step WITHOUT "
                         "donation -- the gate must fail")
    ap.add_argument("--gate", default="local,distri,tp",
                    help="comma list of drivers whose verdicts set the "
                         "exit status (default: all audited; every "
                         "driver's verdict is reported regardless)")
    args = ap.parse_args(argv)
    drivers = args.driver or ["all"]
    if "all" in drivers:
        drivers = list(DRIVERS)
    gate_drivers = [g.strip() for g in args.gate.split(",") if g.strip()]
    unknown = sorted(set(gate_drivers) - set(DRIVERS))
    if unknown:
        # a typo'd gate entry must not silently ungate a driver
        ap.error(f"--gate names unknown drivers {unknown}; "
                 f"valid: {list(DRIVERS)}")

    report, ok = run_audits(drivers, min_bytes=args.min_bytes,
                            donate=not args.no_donate,
                            gate_drivers=gate_drivers)
    if args.format == "json":
        print(json.dumps(report, indent=2, allow_nan=False))
    else:
        print(format_text(report))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
