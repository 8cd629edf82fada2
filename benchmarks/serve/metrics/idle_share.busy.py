"""Device: share of the time inside the traced segment's engine steps in
which no operation ran on the chip."""
from runlib import step_idle_share


def read(run):
    return step_idle_share(run)
