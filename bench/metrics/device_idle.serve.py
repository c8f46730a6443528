"""Device: share of the traced window in which no operation ran (%)."""

from bench.readers import idle_pct


def read(run):
    return idle_pct(run)
