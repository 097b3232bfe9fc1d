"""Device: share of the traced window in which no operation ran on the
chip, averaged over the chips, in percent."""


def read(run):
    return run.idle_pct()
