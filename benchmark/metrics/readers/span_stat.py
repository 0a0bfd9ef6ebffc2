"""A number out of the program's own spans (``harness/spans.py``), clipped
to the traced window.  ``args["reduce"]`` is one of:

- ``median_ms``: the median duration, in milliseconds, of the spans called
  ``span`` that lie wholly inside the window; with ``minus`` each is taken
  less its direct children of those names (a tick less its device calls).
- ``share``: the time in the spans called ``numerator`` over the time in
  those called ``denominator``, in percent, each span cut to the window.
- ``ratio``: over the spans called ``span``, the sum of the attributes
  ``numerator`` over the sum of the attributes ``denominator`` (lists of
  attribute names), in percent.  A span counts when its start lies inside
  the window, or with ``at`` (a list of attributes, nanoseconds) when its
  start plus those does: a request counts by its first token.

``fence`` names the host span and the device program that check the
clock.  Nothing to read (no recorder, no clock, no such span): None.
"""

from harness import spans
from harness.stats import median


def _clip_ns(r, lo, hi):
    return max(0.0, min(r.end_ns, hi) - max(r.start_ns, lo))


def reduce(recs, lo, hi, args):
    how = args["reduce"]
    if how == "median_ms":
        kids = spans.children_of(recs)
        minus = set(args.get("minus", ()))
        values = [
            (r.end_ns - r.start_ns) - sum(
                k.end_ns - k.start_ns for k in kids.get(r.span_id, ())
                if k.name in minus)
            for r in spans.named(recs, args["span"])
            if lo <= r.start_ns and r.end_ns <= hi]
        return median(values) * 1e-6 if values else None
    if how == "share":
        num = sum(_clip_ns(r, lo, hi)
                  for r in spans.named(recs, args["numerator"]))
        den = sum(_clip_ns(r, lo, hi)
                  for r in spans.named(recs, args["denominator"]))
        return 100.0 * num / den if den else None
    if how == "ratio":
        num = den = 0.0
        for r in spans.named(recs, args["span"]):
            a = r.attrs or {}
            at = r.start_ns + sum(a.get(k, 0) for k in args.get("at", ()))
            if lo <= at <= hi:
                num += sum(a.get(k, 0) for k in args["numerator"])
                den += sum(a.get(k, 0) for k in args["denominator"])
        return 100.0 * num / den if den else None
    raise ValueError(f"unknown reduction {how!r}")


def read(env, args):
    found = spans.window(env, args["fence"])
    if found is None:
        return None
    recs, (lo, hi), _offset = found
    return reduce(recs, lo, hi, args)
