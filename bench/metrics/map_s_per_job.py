"""Host seconds of the MaP solution pool per DSE job (``DSEResult.timings``,
the program's ``dse.map`` span)."""


def read(ctx):
    reqs = [r for r in ctx["layer"].get("requests", []) if "map" in r]
    if not reqs:
        return None
    return sum(r["map"] for r in reqs) / sum(r["lanes"] for r in reqs)
