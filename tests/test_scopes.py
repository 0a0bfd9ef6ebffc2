"""The programs name their blocks: every step and serving program the
benchmark's cells run carries the one vocabulary of ``jax.named_scope``s
(PERF.md section 3, "The program's scopes"), where the compiler keeps
them -- inside the scanned layer body and inside ``jax.checkpoint`` --
and a scope changes no instruction.

Each program is built at its cell's toy sizes as the benchmark builds it,
compiled on the CPU, and read the way the benchmark reads a device trace:
``compiled.as_text()``'s ``op_name`` is the path the profiler records as
``tf_op``, parsed by the benchmark's own ``harness/trace_meta.py``.
"""

import contextlib
import functools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import resolve, trace_meta  # noqa: E402

from bigdl_tpu.nn.generation_state import has_slot_state  # noqa: E402
from bigdl_tpu.optim.train_step import make_train_step  # noqa: E402
from bigdl_tpu.serving.generation import (paged_generate_steps,  # noqa: E402
                                          speculative_verify_step)

LAYER = ("embed", "attention", "mlp", "head")
EXPERTS = ("moe", "moe_router", "moe_dispatch", "moe_experts", "moe_combine")
TRAINING = ("loss", "optimizer")

#: program -> (its cell, the scopes PERF.md's table gives it)
PROGRAMS = {
    "gpt2.train_step": ("gpt2-medium.train.seq1024", LAYER + TRAINING),
    "lfm2.train_step": ("lfm2-8b-a1b.train.seq4096",
                        LAYER + ("state_mixer",) + EXPERTS + TRAINING),
    "gpt2.chunk_prefill": ("gpt2-medium.serve.batch-generate",
                           LAYER + ("sampler",)),
    "gpt2.decode": ("gpt2-medium.serve.batch-generate",
                    LAYER + ("sampler",)),
    "gpt2.verify": ("gpt2-medium.serve.batch-generate",
                    LAYER + ("sampler",)),
    "ling.chunk_prefill": ("ling-3.0-flash-vl.serve.long-decode",
                           LAYER + ("state_mixer", "moe_shared", "sampler")
                           + EXPERTS),
    "ling.decode": ("ling-3.0-flash-vl.serve.long-decode",
                    LAYER + ("state_mixer", "moe_shared", "sampler")
                    + EXPERTS),
    "kanana.chunk_prefill": ("kanana-2-30b-a3b.serve.long-prompt",
                             LAYER + ("moe_shared", "moe_weights", "sampler")
                             + EXPERTS),
    "kanana.decode": ("kanana-2-30b-a3b.serve.long-prompt",
                      LAYER + ("moe_shared", "moe_weights", "sampler")
                      + EXPERTS),
    "granite.chunk_prefill": ("granite-4.0-h-micro.serve.short-chat",
                              LAYER + ("state_mixer", "sampler")),
    "granite.decode": ("granite-4.0-h-micro.serve.short-chat",
                       LAYER + ("state_mixer", "sampler")),
    "gigachat.chunk_prefill": ("gigachat3.5-432b-a28b.serve.long-reasoning",
                               LAYER + ("state_mixer", "moe_shared",
                                        "sampler") + EXPERTS),
    "gigachat.decode": ("gigachat3.5-432b-a28b.serve.long-reasoning",
                        LAYER + ("state_mixer", "moe_shared", "sampler")
                        + EXPERTS),
}
TRAIN_STEPS = [p for p in PROGRAMS if p.endswith("train_step")]


@functools.lru_cache(maxsize=None)
def program_model(cell_name):
    """The cell's model at its toy sizes with the benchmark's weights, as
    ``benchmark/drivers`` build it, and the cell."""
    cell = resolve.Cell(cell_name)
    cfg, mix = cell.sized(True)
    ref = cell.model
    length = mix["data"]["seq_len"] if "data" in mix else cfg["n_positions"]
    spec = jax.ShapeDtypeStruct((2, length), jnp.int32)
    model = ref.program_model(cfg, ref.make_params(cfg, 7), spec)
    return cell, cfg, mix, model, spec


def lower(program):
    """``jax.stages.Lowered`` of the program, traced anew on every call
    (nothing of an earlier trace is reused: the scopes are read while
    tracing)."""
    cell, cfg, mix, model, spec = program_model(PROGRAMS[program][0])
    kind = program.split(".")[1]
    if kind == "train_step":
        criterion, method = cell.model.program_training(cfg, mix)
        step = make_train_step(model, criterion, method,
                               compute_dtype=jnp.bfloat16)
        params = model.weights()
        tokens = np.zeros(spec.shape, np.int32)
        return jax.jit(step).lower(params, model.state(),
                                   method.init_state(params), tokens,
                                   tokens, jax.random.key(0))
    params = model.weights()
    slotted = has_slot_state(model.paged_state_spec())
    pool = model.init_paged_cache(8, 16, jnp.float32,
                                  **({"slots": 4} if slotted else {}))
    for cached in ("_compiled_paged_steps", "_compiled_spec_steps"):
        model.__dict__.pop(cached, None)
    knobs = lambda n: (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
                       np.ones((n,), np.float32), np.zeros((n,), np.int32))
    z = lambda *shape: np.zeros(shape, np.int32)
    slots = lambda n: (np.full((n,), 4, np.int32),) if slotted else ()
    tables = lambda n: np.full((n, 4), 8, np.int32)
    if kind == "verify":
        verify = speculative_verify_step(model, jnp.float32, 2)
        return verify.lower(params, pool, z(4), [z(4), z(4)], z(4),
                            tables(4), *knobs(4))
    chunk, decode, _ = paged_generate_steps(model, jnp.float32)
    if kind == "decode":
        return decode.lower(params, pool, z(4), z(4), tables(4), *knobs(4),
                            *slots(4))
    return chunk.lower(params, pool, z(2, 16), z(2), np.ones((2,), np.int32),
                       tables(2), *knobs(2), *slots(2))


#: instructions that are no operation of a trace (they run nothing) or
#: that only contain others (``harness.trace.CONTAINERS``)
PLUMBING = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "while", "conditional", "call")


def executed_instructions(hlo_text):
    """``[(opcode, op_name or None)]`` of the instructions a run of the
    program executes one by one, which are what a device trace holds an
    event for: those of the entry computation and, from there, of every
    loop body and condition, branch and called computation.  The inside
    of a fusion (one event, named by its root) and a reduction's little
    region are not walked."""
    comps, entry, name = {}, None, None
    for line in hlo_text.splitlines():
        head = re.match(r"(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(2)
            comps[name] = []
            entry = name if head.group(1) else entry
        elif line.startswith("}"):
            name = None
        elif name is not None and " = " in line:
            comps[name].append(line)
    seen, todo, out = set(), [entry], []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            # the first lower-case ``word(`` right of the ``=``: a type
            # (``f32[2]{0}``, a tuple of them) holds none
            opcode = re.search(r" ([a-z][\w\-]*)\(", line.split(" = ", 1)[1])
            opcode = opcode.group(1) if opcode else "?"
            if opcode in ("while", "conditional", "call"):
                for ref in re.findall(
                        r"(?:body|condition|to_apply|true_computation|"
                        r"false_computation)=%?([\w.\-]+)", line):
                    todo.append(ref)
                branches = re.search(r"branch_computations=\{([^}]*)\}", line)
                if branches:
                    todo.extend(b.strip().lstrip("%")
                                for b in branches.group(1).split(","))
            path = re.search(r'op_name="([^"]*)"', line)
            out.append((opcode, path.group(1) if path else None))
    return out


@functools.lru_cache(maxsize=None)
def compiled_text(program):
    return lower(program).compile().as_text()


def paths(program):
    """The ``op_name`` of every executed instruction of the compiled
    program that lies below ``jit(...)`` (the compiler's own
    instructions carry none, or a parameter's)."""
    found = executed_instructions(compiled_text(program))
    out = [path for opcode, path in found if opcode not in PLUMBING
           and path is not None and path.startswith("jit(")]
    assert len(out) > 20, "the compiled text carries no op_name"
    return out


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_every_scope_of_the_program_appears(program):
    # inside fusions too: a fusion is named by its root alone, and at toy
    # sizes a whole scope (the combine) can fuse into its consumer
    seen = {s for p in re.findall(r'op_name="(jit\([^"]*)"',
                                  compiled_text(program))
            for s in trace_meta.scopes_on(p)}
    missing = set(PROGRAMS[program][1]) - seen
    assert not missing, (missing, sorted(seen))
    assert seen <= set(trace_meta.VOCABULARY)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_nine_tenths_of_the_instructions_lie_under_a_scope(program):
    all_paths = paths(program)
    bare = [p for p in all_paths if trace_meta.scope_of(p) is None]
    share = 1.0 - len(bare) / len(all_paths)
    worst = sorted(set(bare), key=bare.count, reverse=True)[:8]
    assert share >= 0.9, (share, [(bare.count(p), p) for p in worst])


@pytest.mark.parametrize("program", TRAIN_STEPS)
def test_a_training_program_holds_every_phase(program):
    """Backward, recompute and the optimizer are in the paths themselves,
    inside the scanned, checkpointed layer too."""
    all_paths = paths(program)
    phases = {trace_meta.phase_of(p) for p in all_paths}
    assert phases == {"forward", "recompute", "backward", "optimizer"}
    for marker in ("transpose(jvp(", "rematted_computation", "optimizer"):
        assert any(marker in p for p in all_paths), marker
    # a layer's scopes are kept in all three passes
    layer = {(trace_meta.scope_of(p), trace_meta.phase_of(p))
             for p in all_paths}
    for scope in ("attention", "mlp"):
        for phase in ("forward", "recompute", "backward"):
            assert (scope, phase) in layer, (scope, phase)
    # the optimizer's operations are the optimizer's whatever else the
    # path says, and the loss has a backward of its own
    assert ("optimizer", "optimizer") in layer
    assert ("loss", "backward") in layer and ("head", "backward") in layer


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_a_scope_changes_no_instruction(program, monkeypatch):
    """The lowered text (no debug info) is the same program's with every
    scope patched to a null context."""
    with_scopes = lower(program).as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = lower(program)
    assert with_scopes == without.as_text()
    bare = re.findall(r'op_name="(jit\([^"]*)"', without.compile().as_text())
    assert bare and not any(trace_meta.scope_of(p) for p in bare)


@pytest.mark.parametrize("program", list(PROGRAMS))
def test_the_matmul_helper_changes_no_instruction(program, monkeypatch):
    """``nn.linear.matmul_t`` takes its bfloat16 branch only for a served
    bfloat16 weight beside a float32 input (ISSUE 40): the training steps
    cast both sides, the ``ServedLM`` models feed bfloat16 activations to
    bfloat16 weights, and the CPU serves GPT-2's float32 weights as they
    are, so every program lowers as it did with the expression the helper
    replaced."""
    import bigdl_tpu.nn.attention as attention
    import bigdl_tpu.nn.linear as linear

    with_helper = lower(program).as_text()
    before = lambda x, w: x @ w.astype(x.dtype).T
    monkeypatch.setattr(linear, "matmul_t", before)
    monkeypatch.setattr(attention, "matmul_t", before)
    assert with_helper == lower(program).as_text()


@pytest.mark.parametrize("path,scope,phase", [
    ("jit(train_step)/jvp()/while/body/closed_call/attention/dot_general",
     "attention", "forward"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", "attention", "recompute"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "mlp/dot_general", "mlp", "backward"),
    ("jit(train_step)/transpose(jvp(loss))/jit(log_softmax)/mul", "loss",
     "backward"),
    ("jit(train_step)/optimizer/transpose(jvp())/sub", "optimizer",
     "optimizer"),
    ("jit(train_step)/jvp(operator)/state_mixer/conv_general_dilated:",
     "state_mixer", "forward"),
    ("jit(decode)/moe_weights/while/body/moe/moe_experts/dot_general",
     "moe_experts", "forward"),
    ("jit(decode)/moe_weights/while/body/dynamic_slice", "moe_weights",
     "forward"),
    ("jit(decode)/jit(flash_attention)/pallas_call", None, "forward"),
    ("", None, "no-path"),
    (None, None, "no-path"),
])
def test_scope_and_phase_of_a_path(path, scope, phase):
    assert trace_meta.scope_of(path) == scope
    assert trace_meta.phase_of(path) == phase
