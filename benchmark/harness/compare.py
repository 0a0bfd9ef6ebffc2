"""The arithmetic of ``correct``: norms by comparison unit, the worst
unit's gap, and the check records a run prints."""

import jax
import jax.numpy as jnp
import numpy as np


def default_unit_sq_norms(tree, other=None):
    """Squared norm of every leaf (of ``tree - other`` when given)."""
    if other is None:
        return jax.tree.map(lambda a: jnp.sum(jnp.square(
            a.astype(jnp.float32))), tree)
    return jax.tree.map(lambda a, b: jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32))), tree, other)


def flatten_units(tree):
    """``{unit name: norm}`` from a tree of squared norms (leaves may be
    arrays: one unit per element), on the host, in float64."""
    out = {}
    for path, leaf in jax.tree.flatten_with_path(tree)[0]:
        name = ".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        arr = np.sqrt(np.asarray(leaf, np.float64))
        if arr.ndim == 0:
            out[name] = float(arr)
        else:
            for idx in np.ndindex(arr.shape):
                out[name + str(list(idx))] = float(arr[idx])
    return out


def worst_unit_gap(program, reference, keep=None):
    """The gap between the program's norm and the reference's (NOT the
    norm of their difference), by the worst unit, against the reference's
    norm of that unit or of the median unit, whichever is larger.
    Returns (gap, unit name)."""
    names = [n for n in reference if keep is None or keep[n]]
    if not names:
        return 0.0, None
    ref = np.array([reference[n] for n in names])
    got = np.array([program[n] for n in names])
    scale = np.maximum(ref, np.median(ref))
    gaps = np.abs(got - ref) / np.maximum(scale, 1e-300)
    gaps = np.where(np.isfinite(gaps), gaps, np.inf)
    i = int(np.argmax(gaps))
    return float(gaps[i]), names[i]


def moving_units(ref_grad_norms, floor=1e-3):
    """Units whose reference gradient is not nought to rounding: at least
    ``floor`` of the median unit's.  The others move under Adam by
    round-off alone and are left out of the change comparison."""
    med = float(np.median(list(ref_grad_norms.values())))
    return {n: v >= floor * med for n, v in ref_grad_norms.items()}


def check(name, value, limit):
    """One number compared beside its limit.  A number with no limit in
    the cell's file is printed and not judged."""
    value = float(value)
    ok = True if limit is None else bool(np.isfinite(value)
                                         and value <= limit)
    return {"name": name, "value": value, "limit": limit, "ok": ok}


def all_ok(checks):
    return bool(checks) and all(c["ok"] for c in checks)
