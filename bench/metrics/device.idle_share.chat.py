"""Percent of the traced window with no operation on the device (chat)."""
from harness.readers import idle_share


def read(run):
    return idle_share(run)
