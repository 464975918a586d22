"""Share of the serving window in which no operation ran on the device."""

from counts import idle_share


def read(ctx):
    return idle_share(ctx)
