"""What the program called each operation of a device trace.

``harness/trace.py`` keeps an operation's HLO text, start and duration.
The trace holds more: every ``XLA Ops`` event points at an
``XEventMetadata`` whose stats carry the ``program_id`` of the compiled
program it belongs to, its ``hlo_category`` and, where the compiler kept
one, ``tf_op``: JAX's ``op_name`` path, e.g.

    jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/rematted_computation/attention/dot_general

so the scope the program opened (``jax.named_scope``), the phase
(forward, recompute, backward) and the program are all there.
``jax.profiler.ProfileData`` exposes an event's own stats, not its
metadata's, so this module decodes those fields of the ``*.xplane.pb``
itself: plain Python over the protobuf wire format.  It imports neither
TensorFlow nor the program (``harness/trace.py``'s rule: no PR to the
program changes how a device number is made).

A fusion has ONE ``tf_op``, its root's: an operation fused into a
consumer of another scope is counted under the consumer's scope.  An
operation the compiler made itself (a copy, a weight's convert hoisted
out of a loop, ``copy-done``) may carry no ``tf_op`` at all; it is then
under no scope and in phase ``no-path``.
"""

import os
import re

import numpy as np

from harness import trace

#: the scopes the program opens (PERF.md section 3, "The program's
#: scopes"); ``moe`` is the expert layer's outer scope (its norm and
#: residual), the ``moe_*`` its parts
VOCABULARY = ("embed", "attention", "state_mixer", "mlp", "moe",
              "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
              "moe_shared", "moe_weights", "head", "loss", "optimizer",
              "sampler")
PHASES = ("forward", "recompute", "backward", "optimizer", "no-path")
#: the stats of an ``XEventMetadata`` that are kept
KEPT_STATS = ("tf_op", "program_id", "hlo_category")

_PART = re.compile(r"[/()]")


def scopes_on(path):
    """The vocabulary names on an ``op_name`` path, in order.  A scope
    opened under a transformation is written inside its parentheses
    (``transpose(jvp(loss))/mul``), so ``/``, ``(`` and ``)`` all part
    names."""
    return [p for p in _PART.split(path) if p in VOCABULARY]


def scope_of(path):
    """The LAST vocabulary name on the path (the innermost scope), or
    None."""
    found = scopes_on(path or "")
    return found[-1] if found else None


def phase_of(path):
    """``optimizer`` under that scope whatever else the path says, else
    ``recompute`` if the path holds ``rematted_computation`` (the forward
    that ``jax.checkpoint`` runs again; it runs in the backward pass, so
    its path holds ``transpose(`` too), else ``backward`` if it holds
    ``transpose(``, else ``forward``; no path: ``no-path``."""
    if not path:
        return "no-path"
    if "optimizer" in scopes_on(path):
        return "optimizer"
    if "rematted_computation" in path:
        return "recompute"
    if "transpose(" in path:
        return "backward"
    return "forward"


def in_scopes(scope, wanted):
    """Whether an operation's scope is one of ``wanted``; ``moe`` there
    stands for the expert layer whole: itself and every ``moe_*``."""
    if scope is None:
        return False
    return scope in wanted or ("moe" in wanted and scope.startswith("moe_"))


# --------------------------------------------------------------------- #
# the wire format (https://protobuf.dev/programming-guides/encoding/)
# --------------------------------------------------------------------- #

def _uvarint(data, off):
    shift = n = 0
    while True:
        b = data[off]
        off += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, off
        shift += 7


def _fields(data):
    """``(field number, wire type, value)`` of one message: a varint as
    an int, a length-delimited field as a memoryview, fixed ones as
    bytes."""
    off, end = 0, len(data)
    while off < end:
        key, off = _uvarint(data, off)
        wire = key & 7
        if wire == 0:
            val, off = _uvarint(data, off)
        elif wire == 2:
            ln, off = _uvarint(data, off)
            val = data[off:off + ln]
            off += ln
        elif wire == 1:
            val = bytes(data[off:off + 8])
            off += 8
        elif wire == 5:
            val = bytes(data[off:off + 4])
            off += 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _map_entry(data):
    """``(key, value bytes)`` of a ``map<int64, Message>`` entry."""
    key, value = 0, b""
    for f, w, v in _fields(data):
        if f == 1 and w == 0:
            key = v
        elif f == 2 and w == 2:
            value = v
    return key, value


def _id_and_name(data):
    """``(id, name)`` of an ``XEventMetadata`` or ``XStatMetadata``, and
    the raw ``stats`` of the first."""
    ident, name, stats = 0, "", []
    for f, w, v in _fields(data):
        if f == 1 and w == 0:
            ident = v
        elif f == 2 and w == 2:
            name = _text(v)
        elif f == 5 and w == 2:
            stats.append(v)
    return ident, name, stats


def _stat(data):
    """``(metadata_id, value)`` of an ``XStat``: an integer, a string, or
    ``("ref", id)`` for a string kept in ``stat_metadata``."""
    ident, value = 0, None
    for f, w, v in _fields(data):
        if f == 1 and w == 0:
            ident = v
        elif f in (3, 4) and w == 0:        # uint64_value, int64_value
            value = v
        elif f in (5, 6) and w == 2:        # str_value, bytes_value
            value = _text(v)
        elif f == 7 and w == 0:             # ref_value
            value = ("ref", v)
    return ident, value


def _line(data):
    """``(name, timestamp_ns, [(metadata_id, offset_ps, duration_ps)])``."""
    name, stamp, events = "", 0, []
    for f, w, v in _fields(data):
        if f == 2 and w == 2:
            name = _text(v)
        elif f == 3 and w == 0:
            stamp = v
        elif f == 4 and w == 2:
            mid = off = dur = 0
            for f2, w2, v2 in _fields(v):
                if w2 != 0:
                    continue
                if f2 == 1:
                    mid = v2
                elif f2 == 2:
                    off = v2
                elif f2 == 3:
                    dur = v2
            events.append((mid, off, dur))
    return name, stamp, events


class Operation:
    """One ``XEventMetadata`` of a device plane with the kept stats, and
    what they say: scope, phase, program, and whether it is a leaf."""

    __slots__ = ("name", "tf_op", "program_id", "hlo_category", "scope",
                 "phase", "leaf", "program")

    def __init__(self, name, tf_op, program_id, hlo_category):
        self.name = name                    # the HLO text, as trace.py's
        self.tf_op = tf_op                  # the op_name path or None
        self.program_id = program_id        # int or None
        self.hlo_category = hlo_category    # str or None
        self.scope = scope_of(tf_op)
        self.phase = phase_of(tf_op)
        self.leaf = trace.op_kind(trace.short_name(name)) \
            not in trace.CONTAINERS
        self.program = None                 # its program's name, once known

    def matches(self, scopes=None, phases=None, module=None, named=False):
        """Every filter given: scope in ``scopes`` (``moe`` stands for
        every ``moe_*`` too), phase in ``phases``, program name matching
        the compiled ``module``; ``named``: under any vocabulary scope."""
        return ((scopes is None or in_scopes(self.scope, scopes))
                and (phases is None or self.phase in phases)
                and (module is None or (self.program is not None and bool(
                    module.search(self.program))))
                and (not named or self.scope is not None))


class MetaTrace:
    """The first device plane: ``op[i]``, ``start[i]`` and ``dur[i]`` (ns)
    of every LEAF operation's event (``harness.trace.CONTAINERS`` left
    out), beside the plane's programs and busy time."""

    def __init__(self, plane_name, metadata, ops, modules):
        self.name = plane_name
        #: every XEventMetadata of the plane, ``{id: Operation}``
        self.metadata = metadata
        #: ``{program_id: program name}`` from the ``XLA Modules`` events
        #: (``jit_train_step(2707457242856997706)``)
        self.programs = {}
        self.module_runs = {}               # program name -> [(start, end)]
        for mid, start, dur in modules:
            text = metadata[mid].name if mid in metadata else ""
            m = re.match(r"(.*)\((\d+)\)$", text)
            name = m.group(1) if m else text
            if m:
                self.programs[int(m.group(2))] = name
            self.module_runs.setdefault(name, []).append((start, start + dur))
        for op in metadata.values():
            op.program = self.programs.get(op.program_id)
        starts = np.array([s for _, s, _ in ops], np.float64)
        durs = np.array([d for _, _, d in ops], np.float64)
        #: the union of ALL operations' intervals, containers included
        #: (they add nothing to it): ``DeviceTrace.busy_ns``
        self.busy_ns = trace._union_ns(starts, starts + durs)
        # an event that points at no metadata says nothing of itself
        leaf = [i for i, (mid, _, _) in enumerate(ops)
                if mid in metadata and metadata[mid].leaf]
        self.op = [metadata[ops[i][0]] for i in leaf]
        self.start = starts[leaf]
        self.dur = durs[leaf]

    def select(self, scopes=None, phases=None, module=None, named=False):
        """Indices of the leaf events whose operation matches every
        filter given (``Operation.matches``; ``module`` a regular
        expression on the program's name).  An operation is judged once
        however many events it has."""
        reg = re.compile(module) if module else None
        verdict = {id(op): op.matches(scopes, phases, reg, named)
                   for op in self.metadata.values()}
        return [i for i, op in enumerate(self.op) if verdict[id(op)]]

    def has_paths(self):
        return any(op.tf_op for op in self.metadata.values())


def _plane(data):
    """A device plane's name, raw lines, event metadata and stat
    metadata, or None for a plane that is no TPU device."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for f, w, v in _fields(data):
        if w != 2:
            continue
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    if not re.match(r"/device:TPU:\d+", name):
        return None
    return name, lines, event_meta, stat_meta


def load(path):
    """The first device plane of the trace file (or of the newest file
    under a directory) as a ``MetaTrace``; None if it has no device
    plane or the plane holds no operation."""
    if os.path.isdir(path):
        path = trace.newest_xplane(path)
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for f_no, w, v in _fields(space):
        if f_no == 1 and w == 2:
            plane = _plane(v)
            if plane is not None:
                planes.append(plane)
    if not planes:
        return None
    name, lines, event_meta, stat_meta = min(planes, key=lambda p: p[0])
    stat_names = {}
    for entry in stat_meta:
        key, value = _map_entry(entry)
        ident, text, _ = _id_and_name(value)
        stat_names[ident or key] = text
    metadata = {}
    for entry in event_meta:
        key, value = _map_entry(entry)
        ident, text, stats = _id_and_name(value)
        kept = dict.fromkeys(KEPT_STATS)
        for raw in stats:
            sid, val = _stat(raw)
            stat = stat_names.get(sid)
            if stat in kept:
                if isinstance(val, tuple):
                    val = stat_names.get(val[1])
                kept[stat] = val
        if kept["program_id"] is not None:
            kept["program_id"] = int(kept["program_id"])
        metadata[ident or key] = Operation(text, **kept)
    ops, modules = [], []
    for raw in lines:
        line_name, stamp, events = _line(raw)
        if line_name == "XLA Ops":
            into = ops
        elif line_name == "XLA Modules":
            into = modules
        else:
            continue
        # as ProfileData gives them: the line's timestamp plus the
        # event's offset, in nanoseconds
        into.extend((mid, stamp + off / 1000.0, dur / 1000.0)
                    for mid, off, dur in events)
    if not ops:
        return None
    return MetaTrace(name, metadata, ops, modules)


def of_cell(cell_name):
    """The ``MetaTrace`` of a cell's traced run: its newest
    ``*.xplane.pb`` under ``<ROOT>/.bench_tmp/<cell name>``, which is
    still on disk when the readers run; None if there is none."""
    from harness import resolve

    try:
        return load(os.path.join(resolve.ROOT, ".bench_tmp", cell_name))
    except FileNotFoundError:
        return None
