"""Mean device-idle time between consecutive decode programs, per decode
step: what the host loop (argmax, the token's copy, dispatch) costs the
device."""

from counts import idle_between


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    steps = [m for m in tr.module_events if "decode_step" in m[0]]
    if len(steps) < 2 or not tr.busy_intervals:
        return None
    idle = sum(idle_between(tr.busy_intervals[0], a[2], b[1])
               for a, b in zip(steps, steps[1:]) if b[1] > a[2])
    return idle / 1e6 / (len(steps) - 1)
