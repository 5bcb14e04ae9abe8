"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of operation intervals / window), in percent."""
from harness.readers import idle_pct


def read(run):
    return idle_pct(run)
