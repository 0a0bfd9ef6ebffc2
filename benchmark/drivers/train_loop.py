"""Driver of every training mix: ``Optimizer(...).optimize()`` once, from
the seed through its first steps (which are compared with the plain
reference) and on, in the SAME call with the same compiled step and state,
through the measured window.

What it takes from the program: ``bigdl_tpu.optim.Optimizer`` and the
dataset classes, the optimizer's ``metrics`` counters (``data_wait_s``,
``device_s``), and the live parameter and optimizer-state trees, which it
is handed through the checkpoint seam (``set_checkpoint`` fires
``self._checkpoint(params, mstate, opt_state)``; the driver puts its own
reader there, so nothing is written).  The loop itself is the program's,
unfenced: the driver's triggers only read the clock.
"""

import gc
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, ref_optim, traffic as gen
from harness.trace import start_trace

PROGRAM_MOMENT = {"adam": "m", "sgd": "velocity"}


def _dtype(name):
    return {"bfloat16": jnp.bfloat16, "float32": None, None: None}[name]


class Session:
    """One run's objects, so that the readings tool can drive the same
    path with other seeds and windows."""

    def __init__(self, cell, seed, rehearse=False, sizes=None):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.cfg, self.mix = cell.sized(rehearse, sizes)
        self.model_mod = cell.model
        self.batch = int(self.mix["batch"])
        self.first_steps = int(self.mix["first_steps"])
        self.hp = self.mix["optimizer"]
        self.data = None
        self.captured = {}
        self.losses = []

    # ------------------------------------------------------------------ #
    def make_data(self):
        """The run's inputs from the seed.  ``self.first``: the rows of the
        first steps as the step sees them, for the reference."""
        d, b = self.mix["data"], self.batch
        n = b * int(d["batches"])
        if d["kind"] == "markov_tokens":
            x, y = gen.markov_tokens(self.seed, n, int(d["seq_len"]),
                                     self.cfg["vocab_size"],
                                     int(d.get("branch", 4)))
            self.data = {"kind": d["kind"], "x": x, "y": y}
            self.first = [(x[i * b:(i + 1) * b], y[i * b:(i + 1) * b])
                          for i in range(self.first_steps)]
            self.row_shape = x.shape[1:]
        elif d["kind"] == "image_pool":
            pool, labels = gen.uniform_images(
                self.seed, n, self.cfg["image_size"],
                self.cfg["image_channels"], self.cfg["num_classes"])
            # the first epoch takes the pool's rows in order (all
            # different); the program's data set reshuffles every epoch
            mean = np.asarray(d["mean"], np.float32)
            std = np.asarray(d["std"], np.float32)
            self.data = {"kind": d["kind"], "pool": pool, "labels": labels,
                         "mean": mean, "std": std}
            self.first = [((pool[i * b:(i + 1) * b] - mean) / std,
                           labels[i * b:(i + 1) * b])
                          for i in range(self.first_steps)]
            self.row_shape = pool.shape[1:]
        else:
            raise ValueError(f"unknown data kind {d['kind']!r}")
        return self.data

    def program_dataset(self):
        """The data set as a user of the program builds it."""
        from bigdl_tpu.dataset import (LocalDataSet, MiniBatch,
                                       SampleToMiniBatch, array_dataset)
        from bigdl_tpu.dataset.transformer import Transformer

        d = self.data
        if d["kind"] == "markov_tokens":
            return array_dataset(d["x"], d["y"]) \
                >> SampleToMiniBatch(self.batch)
        from bigdl_tpu.dataset.native_loader import NativeBatcher

        batcher = NativeBatcher(d["pool"], d["labels"],
                                mean=list(d["mean"]), std=list(d["std"]))
        size = self.batch

        class AssembleBatches(Transformer):
            """Row indices in, assembled MiniBatches out: the user's own
            stage around ``NativeBatcher.batch`` (gather + normalise)."""

            def apply(self, it):
                rows = []
                for i in it:
                    rows.append(i)
                    if len(rows) == size:
                        yield MiniBatch(*batcher.batch(np.asarray(rows)))
                        rows = []

        return LocalDataSet(list(range(len(d["pool"])))) >> AssembleBatches()

    def unit_sq_norms(self, tree, other=None):
        fn = getattr(self.model_mod, "unit_sq_norms",
                     compare.default_unit_sq_norms)
        return fn(tree, other)

    # ------------------------------------------------------------------ #
    def build(self):
        """The program's objects, as a user builds them."""
        from bigdl_tpu import optim
        from bigdl_tpu.utils.random_generator import RNG

        RNG.set_seed(self.seed & 0x7FFFFFFF)
        if self.data is None:
            self.make_data()
        params = self.model_mod.make_params(self.cfg, self.seed)
        x0 = self.first[0][0]
        spec = jax.ShapeDtypeStruct(x0.shape, x0.dtype)
        model = self.model_mod.program_model(self.cfg, params, spec)
        criterion, method = self.model_mod.program_training(self.cfg,
                                                            self.mix)
        dataset = self.program_dataset()
        opt = optim.Optimizer(model=model, dataset=dataset,
                              criterion=criterion, optim_method=method)
        opt.set_compute_dtype(_dtype(self.mix.get("compute_dtype")))
        self.model, self.opt = model, opt
        del params
        return opt

    # ------------------------------------------------------------------ #
    def _capture(self, params, mstate, opt_state):
        """Stands where the program's checkpoint writer stands: reads the
        live trees after step 1 (the first gradient, from the optimizer's
        first moment) and after the first steps (the parameters' change
        from the seed's weights), as norms, on the device."""
        done = self.opt.driver_state["neval"] - 1
        if done == 1:
            moment = opt_state[PROGRAM_MOMENT[self.hp["name"]]]
            grad = ref_optim.first_gradient(self.hp, moment)
            self.captured["grad"] = compare.flatten_units(
                jax.jit(self.unit_sq_norms)(grad))
        if done == self.first_steps:
            self.captured["change"] = self.change_from_seed(params)
            self._open_window()

    def change_from_seed(self, params):
        """Norms by unit of ``params`` minus the seed's weights, which are
        drawn again inside the same program (the key is an argument: no
        seed is compiled in, and no second copy of the weights is kept)."""
        cfg, mod = self.cfg, self.model_mod
        return compare.flatten_units(jax.jit(
            lambda p, key: self.unit_sq_norms(p, mod.draw_params(cfg, key)))(
            params, gen.prng_key(self.seed)))

    def _open_window(self):
        if self.trace_dir:
            start_trace(self.trace_dir)
            self.tracing = True
        m = self.opt.metrics.to_dict()
        self.counters0 = {k: m.get(k, {}).get("sum", 0.0)
                          for k in ("data_wait_s", "device_s")}
        self.t_open = time.perf_counter()
        self.t_last = self.t_open
        self.deadline = self.t_open + self.seconds

    def _after_step(self, state):
        """The checkpoint trigger: evaluated once after every completed
        step, after the loop's own loss sync."""
        now = time.perf_counter()
        done = state["neval"] - 1
        if done <= self.first_steps:
            if done == 1:
                self.t_first = now
            self.losses.append(float(state["loss"]))
            return done == 1 or done == self.first_steps
        self.t_last = now
        self.window_steps = done - self.first_steps
        return False

    def _ended(self, state):
        """The end trigger.  Stateless on purpose: the loop also asks it
        about the step in flight, to decide on prefetching the next
        batch, and a trigger marked stateful would switch that off."""
        return (self.t_open is not None and self.window_steps >= 1
                and time.perf_counter() >= self.deadline)

    # ------------------------------------------------------------------ #
    def run(self, seconds, trace_dir=None):
        """``optimize()``: first steps, then the window.  Returns the
        window's numbers."""
        from bigdl_tpu.optim.trigger import _Lambda

        self.seconds = float(seconds)
        self.trace_dir, self.tracing = trace_dir, False
        self.t_open, self.window_steps = None, 0
        opt = self.opt
        opt.set_end_when(_Lambda(self._ended))
        opt.set_checkpoint(os.devnull, _Lambda(self._after_step))
        opt._checkpoint = self._capture
        try:
            opt.optimize()
        finally:
            if self.tracing:
                jax.profiler.stop_trace()
        m = opt.metrics.to_dict()
        counters = {k: m.get(k, {}).get("sum", 0.0) - self.counters0[k]
                    for k in self.counters0}
        window_s = self.t_last - self.t_open
        return {"window_s": window_s, "steps": self.window_steps,
                "train_step_ms": 1e3 * window_s / self.window_steps,
                "data_wait_s": counters["data_wait_s"],
                "wall_s": counters["data_wait_s"] + counters["device_s"],
                "required_flops_per_step": self.required_flops(),
                "rows_per_step": self.batch,
                "setup_s": self.t_open}

    def required_flops(self):
        return float(self.model_mod.train_step_flops(
            self.cfg, self.batch, *self.row_shape[:1]))

    def free(self):
        """Drop everything the program holds on the device."""
        self.opt = self.model = None
        gc.collect()

    # ------------------------------------------------------------------ #
    def reference(self, mode="f32", fault=None):
        """The plain reference through the same first steps on the same
        rows: float32, gradients accumulated over blocks of rows so that
        it fits.  ``mode`` is the control's precision; ``fault`` plants
        one of the faults a training cell can have."""
        mod, cfg, hp = self.model_mod, self.cfg, self.hp
        rows = int(self.mix.get("reference_rows", self.batch))
        params = mod.make_params(cfg, self.seed)
        moments = ref_optim.init(hp, params)

        @jax.jit
        def block_grad(p, xb, yb):
            return jax.value_and_grad(mod.reference_loss)(
                p, (xb, yb), cfg, mode)

        def add(acc, g, w):
            return jax.tree.map(lambda a, b: a + w * b, acc, g)

        add = jax.jit(add, donate_argnums=0)

        apply = jax.jit(
            lambda acc, moments, p, t: ref_optim.update(hp, acc, moments, p,
                                                        t),
            static_argnums=3, donate_argnums=(1, 2))
        losses, out = [], {}
        for k, (x, y) in enumerate(self.first):
            if fault == "half_batch":
                x, y = x[:len(x) // 2], y[:len(y) // 2]
            blocks = max(1, len(x) // rows)
            acc, loss = None, 0.0
            for i in range(blocks):
                xb = jnp.asarray(x[i * rows:(i + 1) * rows])
                yb = jnp.asarray(y[i * rows:(i + 1) * rows])
                l, g = block_grad(params, xb, yb)
                acc = jax.tree.map(lambda a: a / blocks, g) if acc is None \
                    else add(acc, g, 1.0 / blocks)
                loss += float(l) / blocks
            losses.append(loss)
            if fault == "state_unchanged":
                continue
            params, moments = apply(acc, moments, params, k + 1)
            if k == 0:
                moment = moments[PROGRAM_MOMENT[hp["name"]]]
                out["grad"] = compare.flatten_units(jax.jit(
                    lambda m: self.unit_sq_norms(
                        ref_optim.first_gradient(hp, m)))(moment))
        out["change"] = self.change_from_seed(params)
        out.setdefault("grad", {n: 0.0 for n in out["change"]})
        out["losses"] = losses
        return out

    def program_reading(self):
        return {"losses": list(self.losses), **self.captured}

    def compare(self, got, ref):
        """The numbers compared, each beside its limit."""
        limits = self.mix.get("limits", {})
        checks = []
        for k, (a, b) in enumerate(zip(got["losses"], ref["losses"]), 1):
            checks.append(compare.check(
                f"loss{k}", abs(a - b) / abs(b), limits.get(f"loss{k}")))
        gap, unit = compare.worst_unit_gap(got["grad"], ref["grad"])
        checks.append({**compare.check("grad_norm", gap,
                                       limits.get("grad_norm")),
                       "unit": unit})
        keep = compare.moving_units(ref["grad"])
        gap, unit = compare.worst_unit_gap(got["change"], ref["change"],
                                           keep)
        checks.append({**compare.check("change_norm", gap,
                                       limits.get("change_norm")),
                       "unit": unit,
                       "left_out": sum(not v for v in keep.values())})
        return checks


def run(ctx):
    """One run of the cell: the contract's result, as a dict."""
    s = Session(ctx.cell, ctx.seed, ctx.rehearse)
    s.make_data()
    ctx.mark("data_made")
    s.build()
    ctx.mark("program_built")
    trace_s = float(s.mix.get("trace_seconds", 8))
    seconds = min(ctx.seconds, trace_s) if ctx.trace else ctx.seconds
    window = s.run(seconds, ctx.trace_dir if ctx.trace else None)
    window["setup_s"] = s.t_open - ctx.t_start
    ctx.timeline.update(first_step_done=round(
        s.t_first - ctx.t_start, 3), window_open=round(
        window["setup_s"], 3))
    memory_peak = ctx.memory_peak()
    got = s.program_reading()
    s.free()
    t0 = time.perf_counter()
    ref = s.reference()
    checks = s.compare(got, ref)
    window["reference_s"] = time.perf_counter() - t0
    return {"attempted": window["steps"], "failed": 0, "checks": checks,
            "end_to_end": {"train_step_ms": window["train_step_ms"],
                           "setup_s": window["setup_s"]},
            "counters": window, "memory_peak_bytes": memory_peak,
            "config": s.cfg, "mix": s.mix}
