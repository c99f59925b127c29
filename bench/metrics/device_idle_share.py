"""Device: share of the traced window in which no operation ran on the
chip, 1 - union of the device-op intervals / traced window."""


def read(run):
    if run.window_s <= 0 or run.busy_s <= 0:
        return None
    return 1.0 - run.busy_s / run.window_s
