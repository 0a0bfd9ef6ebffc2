"""From a profiler trace (``*.xplane.pb``) to numbers.

The reduction follows the program's ``utils/xplane.py`` (``device_busy``,
``op_breakdown``) but reads the file with ``jax.profiler.ProfileData`` and
lives here so that no PR to the program can change how a device number is
made.  A device plane is ``/device:TPU:<n>``; its line ``XLA Modules`` has
one event per execution of a compiled program and ``XLA Ops`` one per HLO
operation (a ``while`` or ``conditional`` event spans its children, which
are events too).  Times are nanoseconds on the device's clock.
"""

import glob
import os
import re

import numpy as np

#: operations that only contain other operations: counted in the union of
#: busy time (they add nothing to it) but never as an operation of their own
CONTAINERS = ("while", "conditional", "call")


def start_trace(trace_dir):
    """Start the profiler for the device's own timeline only: the host
    tracers are off (with them on, a cell whose input pipeline runs native
    threads wrote 419 MB and ran three times slower while traced)."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


class DeviceTrace:
    """One device plane: parallel arrays for ops and for modules."""

    def __init__(self, name, op_names, op_start, op_dur, mod_names,
                 mod_start, mod_dur):
        self.name = name
        self.op_names = op_names          # list of str, the HLO text
        self._short = None
        self.op_start = np.asarray(op_start, np.float64)
        self.op_dur = np.asarray(op_dur, np.float64)
        self.mod_names = mod_names
        self.mod_start = np.asarray(mod_start, np.float64)
        self.mod_dur = np.asarray(mod_dur, np.float64)

    # ------------------------------------------------------------------ #
    def window(self):
        """(start, end) of everything that ran on the device, ns."""
        if not len(self.op_start):
            return None
        return (float(self.op_start.min()),
                float((self.op_start + self.op_dur).max()))

    def busy_ns(self, start=None, end=None):
        """Length of the union of the operations' intervals, clipped to
        ``[start, end]``."""
        return _union_ns(self.op_start, self.op_start + self.op_dur,
                         start, end)

    def short_names(self):
        if self._short is None:
            self._short = [short_name(n) for n in self.op_names]
        return self._short

    def seconds_by_op(self):
        """``{short op name: seconds}`` over leaf operations."""
        out = {}
        for name, dur in zip(self.short_names(), self.op_dur):
            if op_kind(name) in CONTAINERS:
                continue
            out[name] = out.get(name, 0.0) + dur * 1e-9
        return out

    def matching(self, patterns):
        """Indices of leaf operations whose own name (``flash_attention.14``
        of ``%flash_attention.14 = ...``; a Pallas kernel's instruction
        carries the kernel's name) matches any of the regular expressions.
        The operands' names are not searched: a slice OF a kernel's
        result is not the kernel."""
        regs = [re.compile(p) for p in patterns]
        return [i for i, n in enumerate(self.short_names())
                if any(r.search(n) for r in regs)
                and op_kind(n) not in CONTAINERS]

    def module_runs(self, pattern):
        """(start, end) ns of every execution of the programs whose name
        matches, in order of start."""
        reg = re.compile(pattern)
        idx = [i for i, n in enumerate(self.mod_names) if reg.search(n)]
        idx.sort(key=lambda i: self.mod_start[i])
        return [(float(self.mod_start[i]),
                 float(self.mod_start[i] + self.mod_dur[i])) for i in idx]

    def idle_gaps(self, top=10):
        """The longest stretches in which nothing ran on the device, named
        by the programs on either side: ``[[name, seconds], ...]``."""
        if not len(self.mod_start):
            return []
        order = np.argsort(self.mod_start)
        gaps = []
        reach = self.mod_start[order[0]] + self.mod_dur[order[0]]
        prev = order[0]
        for i in order[1:]:
            gap = self.mod_start[i] - reach
            if gap > 0:
                gaps.append((gap * 1e-9, _mod_short(self.mod_names[prev]),
                             _mod_short(self.mod_names[i])))
            if self.mod_start[i] + self.mod_dur[i] > reach:
                reach = self.mod_start[i] + self.mod_dur[i]
                prev = i
        total = {}
        for secs, a, b in gaps:
            key = f"{a}->{b}"
            total[key] = total.get(key, 0.0) + secs
        return [[k, v] for k, v in sorted(total.items(),
                                          key=lambda kv: -kv[1])[:top]]


def _mod_short(name):
    return re.sub(r"\(\d+\)$", "", name)


def short_name(hlo_text):
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0].strip()
    return head.lstrip("%")


def op_kind(short):
    """``fusion.12`` -> ``fusion``; ``while`` -> ``while``."""
    return re.sub(r"[.\d]+$", "", short)


def _union_ns(starts, ends, lo=None, hi=None):
    if not len(starts):
        return 0.0
    if lo is not None:
        starts = np.maximum(starts, lo)
    if hi is not None:
        ends = np.minimum(ends, hi)
    keep = ends > starts
    starts, ends = starts[keep], ends[keep]
    if not len(starts):
        return 0.0
    order = np.argsort(starts)
    starts, ends = starts[order], ends[order]
    reach = np.maximum.accumulate(ends)
    # a new island starts where a start lies past everything before it
    new = np.concatenate(([True], starts[1:] > reach[:-1]))
    island_start = starts[new]
    island_end = np.concatenate((reach[:-1][new[1:]], reach[-1:]))
    return float((island_end - island_start).sum())


def newest_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path):
    """Every device plane of the trace file (or of the newest file under
    a directory) as a ``DeviceTrace``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = newest_xplane(path)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not re.match(r"/device:TPU:\d+", plane.name):
            continue
        ops, mods = ([], [], []), ([], [], [])
        for line in plane.lines:
            if line.name == "XLA Ops":
                into = ops
            elif line.name == "XLA Modules":
                into = mods
            else:
                continue
            for ev in line.events:
                into[0].append(ev.name)
                into[1].append(ev.start_ns)
                into[2].append(ev.duration_ns)
        out.append(DeviceTrace(plane.name, *ops, *mods))
    return out


def summary(devices, top=10):
    """What the result line carries of a trace: ``busy_s`` and ``window_s``
    averaged over the chips, and the ``breakdown``."""
    used = [d for d in devices if d.window() is not None]
    if not used:
        return None
    busy = float(np.mean([d.busy_ns() for d in used])) * 1e-9
    window = float(np.mean([d.window()[1] - d.window()[0]
                            for d in used])) * 1e-9
    first = used[0]
    ops = sorted(first.seconds_by_op().items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy, "window_s": window,
            "breakdown": {"device_ops": [[k, float(v)] for k, v in ops],
                          "idle_gaps": first.idle_gaps(top)}}
