#!/usr/bin/env python3
"""Look at one trace by hand: planes, lines, programs, and the operations
that took most time, with their full HLO text.

    python3 benchmark/tools/trace_names.py <trace dir or .xplane.pb> [pattern]
"""

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import trace  # noqa: E402


def main(path, pattern=None):
    from jax.profiler import ProfileData

    file = trace.newest_xplane(path) if os.path.isdir(path) else path
    print("file", file, os.path.getsize(file), "bytes")
    for plane in ProfileData.from_file(file).planes:
        print("plane", plane.name,
              [(l.name, sum(1 for _ in l.events)) for l in plane.lines])
    for dev in trace.load(file):
        lo, hi = dev.window()
        print(dev.name, "window_s", (hi - lo) * 1e-9, "busy_s",
              dev.busy_ns() * 1e-9)
        mods = {}
        for n, d in zip(dev.mod_names, dev.mod_dur):
            k = re.sub(r"\(\d+\)$", "", n)
            c, s = mods.get(k, (0, 0.0))
            mods[k] = (c + 1, s + d * 1e-9)
        for k, (c, s) in sorted(mods.items(), key=lambda kv: -kv[1][1]):
            print("  program", k, "runs", c, "seconds", round(s, 4))
        total = {}
        text = {}
        for n, d in zip(dev.op_names, dev.op_dur):
            k = trace.short_name(n)
            total[k] = total.get(k, 0.0) + d * 1e-9
            text[k] = n
        for k, s in sorted(total.items(), key=lambda kv: -kv[1])[:40]:
            print("  op %.4f s  %s" % (s, text[k][:400]))
        if pattern:
            reg = re.compile(pattern)
            for k in sorted(total):
                if reg.search(text[k]):
                    print("  match %.4f s  %s" % (total[k], text[k][:600]))


if __name__ == "__main__":
    main(*sys.argv[1:3])
