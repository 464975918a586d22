"""Median host time of one decode step: the jitted step, the argmax and the
token's copy to the host (the benchmark's ``bench.decode`` span)."""

import statistics


def read(ctx):
    steps = [t1 - t0 for name, t0, t1 in ctx["run"].spans
             if name == "bench.decode" and t0 >= ctx["run"].t_window]
    return statistics.median(steps) * 1e3 if steps else None
