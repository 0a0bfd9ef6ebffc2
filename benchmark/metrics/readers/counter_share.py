"""A share of two of the window's counters, in percent."""


def read(env, args):
    c = env["counters"]
    num, den = c.get(args["numerator"]), c.get(args["denominator"])
    if num is None or not den:
        return None
    return 100.0 * num / den
