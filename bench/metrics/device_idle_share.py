"""Share of the window in which the device ran no operation, in %."""


def read(rec):
    if rec.window_s <= 0 or not rec.busy:
        return None
    return 100.0 * (1.0 - rec.busy_s / rec.window_s)
