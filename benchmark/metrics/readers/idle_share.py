"""1 - union of the device's operation intervals over the traced window,
in percent (the mean over the chips used)."""


def read(env, args):
    s = env["summary"]
    if not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
