"""Seconds of the GA stage per DSE job (the program's ``dse.ga`` span; a
sweep's one batched GA dispatch is shared by its lanes)."""


def read(ctx):
    reqs = [r for r in ctx["layer"].get("requests", []) if "ga" in r]
    if not reqs:
        return None
    return sum(r["ga"] for r in reqs) / sum(r["lanes"] for r in reqs)
