"""Percent of the traced window with no operation on the device (batch)."""
from harness.readers import idle_share


def read(run):
    return idle_share(run)
