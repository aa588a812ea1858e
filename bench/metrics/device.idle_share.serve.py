"""Percent of the traced stretch of a serving window with no operation on
the chip."""
from benchlib.readers import idle_share


def read(run):
    return idle_share(run)
