"""The busiest group's rows over the mean group's, in percent, where the
mean is under one row (serving: a decode tick brings 128 held experts 64
rows, and a whole-row mean reads nought).  Over the program's own spans
``args["span"]`` that started in the traced window: the sum of the
attribute ``args["peak"]`` (the busiest group's rows, summed over the
layers of a tick) over the sum of ``args["total"]`` (all groups' rows)
divided by the groups, whose number is the second entry of the
configuration's ``args["groups"]`` (``experts_held`` = first, count); 100
is an even spread.  No such span, no clock: no metric."""

from harness import spans


def read(env, args):
    found = spans.window(env, args["fence"])
    if found is None:
        return None
    recs, (lo, hi), _offset = found
    attrs = [r.attrs or {} for r in spans.named(recs, args["span"])
             if lo <= r.start_ns <= hi]
    peak = sum(a.get(args["peak"], 0) for a in attrs)
    total = sum(a.get(args["total"], 0) for a in attrs)
    groups = int(env["config"][args["groups"]][1])
    return 100.0 * groups * peak / total if total else None
