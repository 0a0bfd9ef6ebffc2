#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py               # one TPU chip: three phases
    python3 chip_smoke.py --chips 4     # four chips: dp + ZeRO-1 only

One process drives the main path once through the entry points a user
calls, at the full width of the models the repo trains and serves, with
data and weights made from ``--seed``:

- ``train_resnet50``: ``ResNet(depth=50)``, 224x224x3, batch 128, bf16
  compute, SGD + momentum, 5 iterations through ``Optimizer.optimize()``;
  batches assembled by ``dataset.native_loader.NativeBatcher``.
- ``train_lm``: ``transformer_lm("medium", 32000, max_len=2048)`` (hidden
  1024, 16 heads, 24 scan-stacked layers), 2048-token sequences, fused
  cross-entropy, Adam, default ``use_flash="auto"``, 3 iterations.
- ``serve_lm``: the same model behind ``ServingEngine`` with its default
  options and ``decode_max_len=2048``: 8 concurrent ``generate()`` calls,
  prompts of 64-1024 tokens, 32 greedy tokens each, through the paged KV
  cache.  One request is held to a plain full-recompute argmax loop (no cache, ``use_flash="never"``),
  everything under matmul precision ``highest``.

``--chips 4`` runs only the path across chips and what it is compared
with: ``DistriOptimizer`` (data-parallel, ZeRO-1, SyncBN) on ResNet-50 at
batch 128 per chip, against the single-device ``LocalOptimizer``
first-step loss on the same seed and global batch.

Each phase's line names the Pallas kernels in the programs it really
compiled, read from JAX's own dump of every module on its way to the
compiler (``chiprun_out/chip_smoke/programs/``, emptied as it is read).

Any phase that raises, a non-finite loss, a token mismatch, or a platform
other than ``tpu`` ends the run with a non-zero exit code and without the
result line.  On success the LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--rehearse`` runs the same phases at toy sizes on whatever platform JAX
finds (the CPU, in the sandbox) to check paths and control flow before a
chip run.  It never prints the result line.
"""

import argparse
import functools
import gc
import json
import os
import re
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

import bigdl_tpu.nn as nn
from bigdl_tpu import optim
from bigdl_tpu.dataset import (FnTransformer, LocalDataSet, MiniBatch,
                               SampleToMiniBatch, array_dataset)
from bigdl_tpu.dataset.native_loader import NativeBatcher
from bigdl_tpu.models.resnet import ResNet
from bigdl_tpu.models.transformer import synthetic_corpus, transformer_lm
from bigdl_tpu.observability import StepTelemetry
from bigdl_tpu.optim import Trigger
from bigdl_tpu.serving import ServingEngine
from bigdl_tpu.utils.config import (compilation_cache_status,
                                    enable_compilation_cache)
from bigdl_tpu.utils.engine import Engine
from bigdl_tpu.utils.random_generator import RNG

HERE = os.path.dirname(os.path.abspath(__file__))

#: the sizes the contract names; ``lm_batch`` is the largest batch whose
#: train step the v5e compiler fits into 16 GB (12.9 GiB by its memory
#: analysis; 16 does not leave room for the allocator)
FULL = dict(image=224, classes=1000, resnet_batch=128, resnet_iters=5,
            lm_size="medium", vocab=32000, seq=2048, lm_batch=12,
            lm_iters=3, requests=8, prompt_min=64, prompt_max=1024,
            new_tokens=32, dp_iters=3, dp_loss_rtol=1e-3)
#: ``--rehearse``: same code paths, toy sizes.  BatchNorm over the few
#: values a toy batch leaves per channel amplifies bf16 rounding (10% at
#: 32x32 and 16 images, 0.4% at 64x64 and 32), hence the looser bound
TINY = dict(image=64, classes=10, resnet_batch=8, resnet_iters=2,
            lm_size="tiny", vocab=512, seq=128, lm_batch=2, lm_iters=2,
            requests=3, prompt_min=8, prompt_max=64, new_tokens=4,
            dp_iters=2, dp_loss_rtol=2e-2)

#: first-step logits, engine path against the plain reference, fp32 at
#: matmul precision "highest": absolute, on logits of order 1
LOGIT_ATOL = 2e-3
#: ``dp_loss_rtol`` bounds the dp + ZeRO-1 first-step loss against the
#: single-device loss.  The CPU fp32 dryrun agrees to ~1e-7; bf16 compute
#: (8 mantissa bits, eps 3.9e-3) under a different reduction order over
#: four chips is what loosens it: the same comparison gave 5.5e-4 on four
#: virtual CPU devices at batch 64 and 128x128 images, and 4.5e-6 on four
#: v5e chips at the full size.


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class CompileMeter:
    """Seconds JAX spent in backend compiles (or in fetching them from
    the persistent cache), and how many of them were cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, mark):
        return {"compile_seconds": round(self.seconds - mark[0], 2),
                "compiles": self.compiles - mark[1],
                "cache_hits": self.cache_hits - mark[2]}


class ProgramLog:
    """The programs this process really ran, as JAX dumps them: with
    ``jax_dump_ir_to`` set, the module of every program is written out on
    its way to the compiler (or to the persistent cache), and a Pallas
    kernel in it is a ``tpu_custom_call`` that carries its
    ``kernel_name``.  So which kernels a phase took is read off what it
    compiled, not asked of the gates a second time."""

    def __init__(self, directory):
        self.dir = directory
        shutil.rmtree(directory, ignore_errors=True)
        os.makedirs(directory)
        jax.config.update("jax_dump_ir_to", directory)

    def drain(self):
        """``{module name: {kernel name: count}}`` for the programs dumped
        since the last call; same-named modules (one step at several
        batch rungs) are merged."""
        out = {}
        for name in sorted(os.listdir(self.dir)):
            path = os.path.join(self.dir, name)
            module = re.fullmatch(r"jax_ir\d+_(.*)_compile\.mlir", name)
            if module:
                with open(path) as f:
                    text = f.read()
                kernels = out.setdefault(module[1], {})
                for k in re.findall(r'kernel_name = "([^"]+)"', text):
                    kernels[k] = kernels.get(k, 0) + 1
            os.remove(path)
        return out

    def kernels(self):
        """Of the programs dumped since the last call, those that hold
        Pallas kernels, and the names of all kernels among them."""
        programs = self.drain()
        if not programs:
            raise RuntimeError("no program was dumped: nothing to read "
                               "the phase's kernels from")
        held = {m: k for m, k in programs.items() if k}
        return held, {k for ks in held.values() for k in ks}


def path_taken(kernels, *names):
    """Which of the named Pallas kernels were taken, or ``xla``."""
    return "+".join(n for n in names if n in kernels) or "xla"


ATTENTION_KERNELS = ("flash_attention", "attention_bwd",
                     "flash_paged_prefill_attention",
                     "flash_paged_decode_attention")


def memory(dev):
    stats = dev.memory_stats() or {}
    return {"bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def run_optimizer(opt, iters, run_dir):
    """``opt.optimize()`` for ``iters`` iterations with a telemetry
    recorder attached; returns the per-step losses and wall seconds."""
    events = []
    tel = StepTelemetry(run_dir, trace=False)
    tel.add_observer(events.append)
    opt.set_telemetry(tel)
    opt.set_end_when(Trigger.max_iteration(iters))
    try:
        opt.optimize()
    finally:
        tel.close()
    steps = [e for e in events if e.get("kind") == "step"]
    losses = [float(e["loss"]) for e in steps]
    if len(losses) != iters or not np.all(np.isfinite(losses)):
        raise RuntimeError(f"expected {iters} finite losses, got {losses}")
    return losses, [round(e["wall_s"], 3) for e in steps]


def checksum(tree):
    return float(sum(np.abs(np.asarray(leaf, np.float64)).sum()
                     for leaf in jax.tree.leaves(tree)[:8]))


def assert_on(tree, platform, what):
    for leaf in jax.tree.leaves(tree):
        got = {d.platform for d in leaf.devices()}
        if got != {platform}:
            raise RuntimeError(f"{what} lives on {got}, not {platform}")


def image_batches(cfg, batch, n_batches, seed):
    """Index batches >> NativeBatcher: gather + per-channel normalize from
    a synthetic pool, the native assembler when it builds."""
    rng = np.random.default_rng(seed)
    n = batch * 2
    s = cfg["image"]
    pool = rng.random((n, s, s, 3), dtype=np.float32)
    labels = rng.integers(0, cfg["classes"], n).astype(np.int32)
    batcher = NativeBatcher(pool, labels, mean=[0.485, 0.456, 0.406],
                            std=[0.229, 0.224, 0.225])
    index = [rng.permutation(n)[:batch] for _ in range(n_batches)]
    ds = LocalDataSet(index, shuffle_on_epoch=False) >> FnTransformer(
        lambda idx: MiniBatch(*batcher.batch(idx)))
    return ds, "native" if batcher.lib is not None else "numpy"


# --------------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------------- #


def resnet_optimizer(cfg, seed, batch, n_batches, distributed, remat=False):
    """ResNet-50, bf16 compute, SGD + momentum, behind ``Optimizer``: from
    one seed the same weights and the same batches, whatever the layout."""
    RNG.set_seed(seed)
    ds, assembler = image_batches(cfg, batch, n_batches, seed)
    model = ResNet(depth=50, class_num=cfg["classes"], remat=remat)
    model.build(jax.ShapeDtypeStruct(
        (batch, cfg["image"], cfg["image"], 3), jnp.float32))
    opt = optim.Optimizer(
        model=model, dataset=ds, criterion=nn.CrossEntropyCriterion(),
        optim_method=optim.SGD(learning_rate=0.1, momentum=0.9,
                               dampening=0.0), distributed=distributed)
    opt.set_compute_dtype(jnp.bfloat16)
    return model, opt, assembler


def phase_train_resnet50(cfg, seed, dev, run_dir, log):
    batch = cfg["resnet_batch"]
    model, opt, assembler = resnet_optimizer(
        cfg, seed, batch, cfg["resnet_iters"], distributed=False)
    before = checksum(model.parameters()[0])
    losses, walls = run_optimizer(opt, cfg["resnet_iters"], run_dir)
    params = model.parameters()[0]
    assert_on(params, dev.platform, "trained ResNet-50 parameters")
    if checksum(params) == before:
        raise RuntimeError("ResNet-50 parameters did not change")
    return {"batch": batch, "image": cfg["image"], "losses": losses,
            "step_wall_seconds": walls, "assembler": assembler,
            "pallas_kernels": log.kernels()[0], "attention": "none"}


def build_lm(cfg):
    return transformer_lm(cfg["lm_size"], vocab_size=cfg["vocab"],
                          max_len=cfg["seq"], scan_layers=True)


def phase_train_lm(cfg, seed, dev, run_dir, log):
    RNG.set_seed(seed)
    batch, seq = cfg["lm_batch"], cfg["seq"]
    x, y = synthetic_corpus(batch * 2, seq, cfg["vocab"], seed=seed)
    model = build_lm(cfg)
    model.build(jax.ShapeDtypeStruct((batch, seq), jnp.int32))
    before = checksum(model.parameters()[0])
    # the criterion models/run.py's transformer-train builds
    crit = nn.TimeDistributedCriterion(nn.FusedSoftmaxCrossEntropyCriterion())
    opt = optim.Optimizer(
        model=model, dataset=array_dataset(x, y) >> SampleToMiniBatch(batch),
        criterion=crit, optim_method=optim.Adam(learning_rate=1e-4))
    opt.set_compute_dtype(jnp.bfloat16)
    losses, walls = run_optimizer(opt, cfg["lm_iters"], run_dir)
    params = model.parameters()[0]
    assert_on(params, dev.platform, "trained LM parameters")
    if checksum(params) == before:
        raise RuntimeError("LM parameters did not change")
    # the flash kernel's backward shows under its own name, attention_bwd
    held, names = log.kernels()
    return {"batch": batch, "seq": seq, "losses": losses,
            "step_wall_seconds": walls, "pallas_kernels": held,
            "attention": path_taken(names, *ATTENTION_KERNELS),
            "cross_entropy": path_taken(
                names, "fused_softmax_cross_entropy",
                "fused_softmax_cross_entropy_grad")}


def reference_greedy(model, params, prompt, new_tokens):
    """Plain full recompute: every token re-runs the whole forward over a
    fixed-size buffer (causal, so positions past the frontier are inert)
    with no cache and ``use_flash="never"``.  Returns the first-step
    logits, the greedy tokens, and each step's top-2 logit gap."""
    total = -(-(len(prompt) + new_tokens) // 128) * 128
    total = min(total, model.max_len)
    buf = np.zeros((1, total), np.int32)
    buf[0, :len(prompt)] = prompt
    saved = [b.attn.use_flash for b in model.blocks]
    for b in model.blocks:
        b.attn.use_flash = "never"
    try:
        @jax.jit
        def reference_step(p, tokens, at):
            logits, _ = model.apply(p, (), tokens)
            return logits[0, at]

        tokens, gaps, first = [], [], None
        for i in range(new_tokens):
            at = len(prompt) + i - 1
            row = np.asarray(reference_step(params, jnp.asarray(buf), at))
            if first is None:
                first = row
            top2 = np.sort(row)[-2:]
            gaps.append(float(top2[1] - top2[0]))
            tokens.append(int(row.argmax()))
            buf[0, at + 1] = tokens[-1]
    finally:
        for b, mode in zip(model.blocks, saved):
            b.attn.use_flash = mode
    return first, tokens, gaps


def engine_first_logits(model, params, prompt, sched):
    """First-step logits by the model call the scheduler's prefill step
    makes.  The step itself hands back tokens, never logits, so this is
    the one check that cannot go through the entry point; every size in
    it is read from the live scheduler: it prefills in
    ``prefill_chunk``-token chunks through a block table
    ``max_blocks_per_seq`` wide over ``block_size``-token blocks, into a
    pool of the scheduler's storage type."""
    n = len(prompt)
    bs, tc, mb = (sched.block_size, sched.prefill_chunk,
                  sched.max_blocks_per_seq)
    table = np.full((1, mb), mb, np.int32)          # block mb: the trash
    table[0, :-(-n // bs)] = np.arange(-(-n // bs))

    @functools.partial(jax.jit, donate_argnums=1)
    def first_logits(p, pool, tokens, start, lens):
        return model.apply_paged(p, tokens, pool, table, pos=start,
                                 lengths=lens)

    pool = model.init_paged_cache(mb, bs, sched._cache_dtype)
    for start in range(0, n, tc):
        chunk = prompt[start:start + tc]
        tokens = np.zeros((1, tc), np.int32)
        tokens[0, :len(chunk)] = chunk
        logits, pool = first_logits(
            params, pool, tokens, np.full(1, start, np.int32),
            np.full(1, len(chunk), np.int32))
    return np.asarray(logits[0, len(chunk) - 1])


def serve_once(model, params, cfg, prompts, held, dev, log):
    """One engine's life: build it, take the held request's first-step
    logits with its scheduler's sizes, serve every request, close it.
    Returns the tokens and what was observed on the way."""
    eng = ServingEngine(model, decode_max_len=cfg["seq"])
    try:
        sched = eng._generation()
        first = engine_first_logits(model, params, prompts[held], sched)
        steps = {"prefill": sched._chunk_fn, "decode": sched._decode_fn}
        log.drain()
        t0 = time.perf_counter()
        futs = [eng.generate(p, max_new_tokens=cfg["new_tokens"])
                for p in prompts]
        outs = [list(map(int, f.result(timeout=900))) for f in futs]
        secs = round(time.perf_counter() - t0, 2)
        cache_bytes = sched.cache_bytes()
        held_bytes = memory(dev)["bytes_in_use"]
    finally:
        eng.close()
    programs = log.drain()
    paths = {}
    for role, fn in steps.items():
        module = f"jit_{fn.__name__}"
        if module not in programs:
            raise RuntimeError(
                f"the engine compiled no program {module!r} "
                f"(it compiled {sorted(programs)})")
        paths[role] = {"program": module, "attention": path_taken(
            programs[module], *ATTENTION_KERNELS)}
    seen = {"serve_seconds": secs, "attention": paths,
            "cache_bytes": cache_bytes,
            "bytes_in_use_serving": held_bytes,
            # no collection in between: close() gives the cache back
            "bytes_in_use_closed": memory(dev)["bytes_in_use"]}
    return outs, first, seen


def phase_serve_lm(cfg, seed, dev, run_dir, log):
    RNG.set_seed(seed)
    rng = np.random.default_rng(seed)
    lengths = rng.integers(cfg["prompt_min"], cfg["prompt_max"] + 1,
                           cfg["requests"])
    prompts = [rng.integers(0, cfg["vocab"], int(n)).astype(np.int32)
               for n in lengths]
    model = build_lm(cfg)
    model.build(jax.ShapeDtypeStruct((1, cfg["seq"]), jnp.int32))
    params = model.parameters()[0]
    assert_on(params, dev.platform, "served LM parameters")
    held = 0                               # the request held to the reference
    out = {"prompt_lengths": [int(n) for n in lengths],
           "new_tokens": cfg["new_tokens"], "held_request": held}

    # the scheduler's dispatcher thread traces the steps, and a `with
    # jax.default_matmul_precision(...)` is thread-local: set it globally
    saved = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        ref_first, ref_tokens, gaps = reference_greedy(
            model, params, prompts[held], cfg["new_tokens"])
        tokens, first, seen = serve_once(
            model, params, cfg, prompts, held, dev, log)
        if any(len(t) != cfg["new_tokens"] for t in tokens):
            raise RuntimeError(
                f"wrong token counts {[len(t) for t in tokens]}")
        got = tokens[held]
        err = float(np.max(np.abs(first - ref_first)))
        out.update(first_logits_max_abs_err=err,
                   tokens_equal=got == ref_tokens, **seen)
        if not np.all(np.isfinite(first)) or err > LOGIT_ATOL:
            raise RuntimeError(
                f"first-step logits differ from the reference by {err} "
                f"(tolerance {LOGIT_ATOL})")
        if got != ref_tokens:
            at = next(i for i, (a, b) in enumerate(zip(got, ref_tokens))
                      if a != b)
            raise RuntimeError(
                f"greedy tokens part from the reference at step {at} "
                f"(engine {got[at]}, reference {ref_tokens[at]}; the "
                f"reference's top-2 logit gap there is {gaps[at]:.3e})")
    finally:
        jax.config.update("jax_default_matmul_precision", saved)
    out["reference_min_top2_gap"] = min(gaps)
    return out


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #


def phase_dp_zero1(cfg, seed, dev, run_dir, log, chips=4):
    per_chip = cfg["resnet_batch"]
    batch = per_chip * chips

    # what it is compared with: one LocalOptimizer step on one device, the
    # same seed and the same global batch.  remat=True changes neither the
    # initialisation nor the math; it is what lets batch 512 fit one chip
    _, ref, assembler = resnet_optimizer(
        cfg, seed, batch, 1, distributed=False, remat=True)
    ref_loss = run_optimizer(ref, 1, os.path.join(run_dir, "ref"))[0][0]
    log.drain()

    mesh = Engine.init().mesh()
    if mesh.devices.size != chips:
        raise RuntimeError(
            f"Engine.init() built a mesh of {mesh.devices.size} devices, "
            f"not {chips}")
    model, opt, _ = resnet_optimizer(cfg, seed, batch, 1, distributed=True)
    opt.set_sync_batchnorm()
    iters = cfg["dp_iters"]
    losses, walls = run_optimizer(opt, iters, os.path.join(run_dir, "dp"))

    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    if rel > cfg["dp_loss_rtol"]:
        raise RuntimeError(
            f"dp+ZeRO-1 first-step loss {losses[0]} != single-device "
            f"{ref_loss} (rel diff {rel:.3e} > {cfg['dp_loss_rtol']})")

    def placement(leaf):
        shards = leaf.addressable_shards
        return {"size": int(leaf.size),
                "shard_sizes": [int(s.data.size) for s in shards],
                "devices": sorted(s.device.id for s in shards)}

    # the trained parameters: every leaf whole on each of the chips
    params = [placement(leaf)
              for leaf in jax.tree.leaves(model.parameters()[0])]
    # the optimizer's own state after the last step: every leaf over the
    # flat parameter plane cut into one piece per chip (ZeRO-1)
    state = [placement(leaf) for leaf in jax.tree.leaves(opt.opt_state)
             if leaf.ndim]
    if not state:
        raise RuntimeError("the optimizer state holds no plane")
    for what, planes, pieces in (("parameters", params, 1),
                                 ("optimizer state", state, chips)):
        for p in planes:
            if len(set(p["devices"])) != chips or any(
                    n * pieces != p["size"] for n in p["shard_sizes"]):
                raise RuntimeError(
                    f"{what}: a leaf of {p['size']} elements sits as "
                    f"{p['shard_sizes']} on devices {p['devices']}, not "
                    f"in {pieces} piece(s) over {chips} distinct chips")
    in_use = {str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()}
    if dev.platform == "tpu" and not all(in_use.values()):
        raise RuntimeError(f"a device holds nothing: {in_use}")
    return {"chips": chips, "batch_per_chip": per_chip,
            "global_batch": batch, "losses": losses,
            "step_wall_seconds": walls,
            "single_device_first_loss": ref_loss,
            "first_loss_rel_diff": rel, "tolerance": cfg["dp_loss_rtol"],
            "parameter_leaves": len(params),
            "parameter_devices": params[0]["devices"],
            "optimizer_state_planes": state,
            "bytes_in_use_per_device": in_use, "assembler": assembler,
            "pallas_kernels": log.kernels()[0], "attention": "none"}


# --------------------------------------------------------------------------- #


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any platform; never prints the "
                         "result line")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if not args.rehearse and dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this script proves nothing off the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2

    cfg = TINY if args.rehearse else FULL
    enable_compilation_cache()
    meter = CompileMeter()
    run_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    log = ProgramLog(os.path.join(run_dir, "programs"))
    say("start", device=device, seed=args.seed, rehearsal=args.rehearse,
        jax=jax.__version__, compilation_cache=compilation_cache_status())

    if args.chips == 4:
        phases = [("dp_zero1_resnet50", phase_dp_zero1)]
    else:
        phases = [("train_resnet50", phase_train_resnet50),
                  ("train_lm", phase_train_lm),
                  ("serve_lm", phase_serve_lm)]
    for name, phase in phases:
        mark, t0 = meter.mark(), time.perf_counter()
        try:
            result = phase(cfg, args.seed, dev, os.path.join(run_dir, name),
                           log)
        except BaseException:
            print(f"chip_smoke: phase {name} failed; device memory "
                  f"{memory(dev)}", file=sys.stderr)
            raise
        # the memory line counts what the phase leaves behind for good,
        # not what waits for a collection
        gc.collect()
        say(name, seconds=round(time.perf_counter() - t0, 2),
            **meter.since(mark), **result, **memory(dev))

    say("end", compilation_cache=compilation_cache_status())
    if args.rehearse:
        print(json.dumps({"rehearsal": "passed", "device": device}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
