"""XLA module executions on the device per tick of the window."""


def read(rec):
    if not rec.ticks:
        return None
    w0, w1 = rec.window
    n = sum(1 for s, _e, _name, dev in rec.modules
            if dev == 0 and w0 <= s <= w1)
    return n / len(rec.ticks) if n else None
