"""Device time of the solve's XLA modules (by the module names the
traffic gives, `solve_modules`) per best response, in ms: their summed
device time in the window over the `clear_rounds` the program counted
in the window's ticks. A program that counts no rounds gives nothing."""
from harness import program_spans


def read(rec):
    ticks = program_spans.window_ticks(rec)
    rounds = sum(t.count("clear_rounds") for t in ticks or ())
    if not rounds:
        return None
    names = tuple(rec.info["solve_modules"])
    w0, w1 = rec.window
    ns = sum(e - s for s, e, name, dev in rec.modules
             if dev == 0 and w0 <= s <= w1 and name.startswith(names))
    return 1e-6 * ns / rounds if ns else None
