"""Driver of every closed-loop generation mix: ``clients`` threads on
``ServingEngine.generate()``, each reading ``stream()`` to the end and
sending its next request at once.  Every stamp is the client's own
(``time.perf_counter()`` at submit and at each streamed token): the
program is asked for nothing but tokens.

Set-up builds the engine on the benchmark's weights, drives every
admission rung of the chunk-prefill program and the decode program once
with real requests (the engine's own ``precompile()`` would hold a second
pool), lets the loop run for ``ramp_seconds`` so that the slots are out of
step with each other, and only then opens the window.  Once it has
closed, requests in flight are read to their end (late is late, not
wrong); then the engine is closed, its memory freed, and a sample of the
finished requests is held to the plain reference.
"""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, stats, traffic as gen
from harness.trace import start_trace


class Record:
    """One request as its client saw it."""

    __slots__ = ("index", "prompt", "asked", "t_submit", "stamps",
                 "tokens", "error")

    def __init__(self, index, prompt, asked):
        self.index, self.prompt, self.asked = index, prompt, asked
        self.t_submit, self.stamps, self.tokens = None, [], []
        self.error = None


class Session:
    def __init__(self, cell, seed, rehearse=False, sizes=None):
        self.cell, self.seed, self.rehearse = cell, int(seed), rehearse
        self.cfg, self.mix = cell.sized(rehearse, sizes)
        self.model_mod = cell.model
        self.requests = gen.generate_requests(
            self.seed, self.mix["requests"], self.cfg["vocab_size"])
        self.records = []
        self._next = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()

    # ------------------------------------------------------------------ #
    def build(self):
        from bigdl_tpu.serving import ServingEngine
        from bigdl_tpu.utils.random_generator import RNG

        RNG.set_seed(self.seed & 0x7FFFFFFF)
        params = self.model_mod.make_params(self.cfg, self.seed)
        spec = jax.ShapeDtypeStruct((1, self.cfg["n_positions"]), jnp.int32)
        self.model = self.model_mod.program_model(self.cfg, params, spec)
        del params
        self.engine = ServingEngine(self.model, **self.mix["engine"])
        return self.engine

    def warm(self, compiles):
        """Every shape the window can meet, through the entry point: for
        each admission rung ``k``, ``k`` requests at once whose prompts
        span several chunks, so that ``k`` rows prefill together.  Passes
        are repeated until one compiles nothing."""
        w = self.mix["warmup"]
        rng = gen.rng_for(self.seed, 4)
        rungs = [int(r) for r in
                 self.engine._generation().batch_ladder.rungs]
        for _ in range(3):
            before = compiles.compiles + compiles.hits
            for k in rungs:
                futs = [self.engine.generate(
                    rng.integers(0, self.cfg["vocab_size"],
                                 int(w["prompt_tokens"])).astype(np.int32),
                    max_new_tokens=int(w["new_tokens"])) for _ in range(k)]
                for f in futs:
                    f.result(timeout=float(w["timeout"]))
            if compiles.compiles + compiles.hits == before:
                break

    # ------------------------------------------------------------------ #
    def _client(self):
        while not self._stop.is_set():
            with self._lock:
                i = self._next
                self._next += 1
            prompt, asked = self.requests[i % len(self.requests)]
            rec = Record(i, prompt, asked)
            with self._lock:
                self.records.append(rec)
            rec.t_submit = time.perf_counter()
            try:
                fut = self.engine.generate(prompt, max_new_tokens=asked)
                for tok in fut.stream(timeout=self.stream_timeout):
                    rec.stamps.append(time.perf_counter())
                    rec.tokens.append(int(tok))
            except Exception as e:          # counted as failed, reported
                rec.error = repr(e)

    def run(self, seconds, t_start, trace_dir=None):
        """Ramp, window, drain.  Returns the window's numbers."""
        self.stream_timeout = float(self.mix["drain_seconds"]) + seconds + 60
        threads = [threading.Thread(target=self._client, daemon=True,
                                    name=f"bench-client-{i}")
                   for i in range(int(self.mix["clients"]))]
        for t in threads:
            t.start()
        time.sleep(float(self.mix["ramp_seconds"]))
        if trace_dir:
            start_trace(trace_dir)
        t_open = time.perf_counter()
        time.sleep(seconds)
        t_close = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
        self._stop.set()
        for t in threads:
            t.join(timeout=float(self.mix["drain_seconds"]))
        hung = sum(t.is_alive() for t in threads)
        self.window = (t_open, t_close)
        return self.reduce(t_open, t_close, hung, t_open - t_start)

    def reduce(self, t_open, t_close, hung, setup_s):
        """All the window's work over all its time; tails over all its
        requests and all its gaps."""
        recs = list(self.records)
        inside = lambda t: t_open <= t <= t_close
        started = [r for r in recs if inside(r.t_submit)]
        failed = [r for r in started
                  if r.error or len(r.tokens) != r.asked]
        tokens = sum(inside(t) for r in recs for t in r.stamps)
        ttft = [1e3 * (r.stamps[0] - r.t_submit) for r in started
                if r.stamps]
        gaps = [1e3 * (b - a) for r in recs
                for a, b in zip(r.stamps, r.stamps[1:]) if inside(b)]
        window_s = t_close - t_open
        # what the traced window processed, for step_mfu.serve: every
        # output token stamped in it (context: its prompt and the tokens
        # before it) and every prompt whose first token was stamped in it
        ctx = []
        for r in recs:
            p = len(r.prompt)
            for i, t in enumerate(r.stamps):
                if inside(t):
                    ctx.extend(range(1, p + 1) if i == 0 else (p + i,))
        flops = self.model_mod.forward_flops(self.cfg, ctx) if ctx else 0.0
        return {
            "window_s": window_s, "setup_s": setup_s,
            "requests_started": len(started), "requests_failed":
            len(failed) + hung, "tokens": tokens,
            "ttft_samples": len(ttft), "itl_samples": len(gaps),
            "serve_tokens_per_s": tokens / window_s,
            "serve_ttft_p90_ms": stats.percentile(ttft, 90),
            "serve_ttft_p50_ms": stats.percentile(ttft, 50),
            "serve_itl_p95_ms": stats.percentile(gaps, 95),
            "serve_itl_p50_ms": stats.percentile(gaps, 50),
            "required_flops": float(flops),
            "errors": [r.error for r in failed if r.error][:3]}

    def free(self):
        self.engine.close()
        self.engine = self.model = None
        gc.collect()

    # ------------------------------------------------------------------ #
    def sample(self):
        """The requests held to the reference: finished ones that started
        in the window, drawn from the seed, the longest among them."""
        t_open, t_close = self.window
        done = [r for r in self.records
                if t_open <= r.t_submit <= t_close and not r.error
                and len(r.tokens) == r.asked]
        if not done:
            return []
        k = min(int(self.mix["check_requests"]), len(done))
        longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
        rng = gen.rng_for(self.seed, 5)
        rest = [r for r in done if r is not longest]
        pick = [rest[i] for i in rng.permutation(len(rest))[:k - 1]]
        return [longest] + pick

    def reference_gaps(self, sample, control=None):
        """For every served token of the sample: how far its logit lies
        below the reference's best at its position (float32, precision
        ``highest``, one plain forward over prompt + served tokens).  With
        ``control`` the token judged is not the served one but the one the
        lower precision puts first at the same position."""
        mod, cfg = self.model_mod, self.cfg
        params = mod.make_params(cfg, self.seed)
        width = int(cfg["n_positions"])

        @jax.jit
        def gaps_of(p, row, judged):
            logits = mod.reference_logits(p, row[None], cfg)[0]
            best = logits.max(-1)
            at = jnp.take_along_axis(logits, judged[:, None], -1)[:, 0]
            return best - at

        @jax.jit
        def control_tokens(p, row):
            return mod.reference_logits(p, row[None], cfg,
                                        control)[0].argmax(-1)

        out = []
        for r in sample:
            p, n = len(r.prompt), len(r.tokens)
            row = np.zeros(width, np.int32)
            row[:p] = r.prompt
            row[p:p + n - 1] = r.tokens[:-1]
            # position i's logits judge the token at i + 1
            judged = np.zeros(width, np.int32)
            judged[p - 1:p - 1 + n] = r.tokens
            if control:
                judged = np.asarray(control_tokens(params,
                                                   jnp.asarray(row)))
            g = np.asarray(gaps_of(params, jnp.asarray(row),
                                   jnp.asarray(judged, jnp.int32)))
            out.append(g[p - 1:p - 1 + n])
        return np.concatenate(out) if out else np.zeros(0)

    def compare(self, gaps, window):
        limits = self.mix.get("limits", {})
        checks = []
        if not len(gaps):
            return [compare.check("served_tokens_checked", np.inf, 0.0)]
        checks.append(compare.check("logit_gap_max", gaps.max(),
                                    limits.get("logit_gap_max")))
        checks.append(compare.check("logit_gap_mean", gaps.mean(),
                                    limits.get("logit_gap_mean")))
        checks.append(compare.check("requests_failed",
                                    window["requests_failed"], 0))
        return checks


def run(ctx):
    from harness.device import CompileCount

    s = Session(ctx.cell, ctx.seed, ctx.rehearse)
    s.build()
    ctx.mark("engine_built")
    meter = CompileCount()
    s.warm(meter)
    ctx.mark("warmed")
    trace_s = float(s.mix.get("trace_seconds", 8))
    seconds = min(ctx.seconds, trace_s) if ctx.trace else ctx.seconds
    before = meter.compiles + meter.hits
    window = s.run(seconds, ctx.t_start,
                   ctx.trace_dir if ctx.trace else None)
    window["programs_loaded_after_warmup"] = \
        meter.compiles + meter.hits - before
    memory_peak = ctx.memory_peak()
    sample = s.sample()
    s.free()
    t0 = time.perf_counter()
    gaps = s.reference_gaps(sample)
    window["reference_s"] = time.perf_counter() - t0
    window["tokens_checked"] = int(len(gaps))
    checks = s.compare(gaps, window)
    e2e = {k: window[k] for k in ("serve_tokens_per_s", "serve_ttft_p90_ms",
                                  "serve_itl_p95_ms", "setup_s")
           if window[k] is not None}
    return {"attempted": window["requests_started"],
            "failed": window["requests_failed"], "checks": checks,
            "end_to_end": e2e, "counters": window,
            "memory_peak_bytes": memory_peak, "config": s.cfg,
            "mix": s.mix}
