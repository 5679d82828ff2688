"""Device time per tick of the solve's XLA modules, in ms, found by the
jitted function's module name that the traffic names (`solve_modules`),
not by HLO numbering."""


def read(rec):
    if not rec.ticks:
        return None
    names = tuple(rec.info["solve_modules"])
    w0, w1 = rec.window
    ns = sum(e - s for s, e, name, dev in rec.modules
             if dev == 0 and w0 <= s <= w1 and name.startswith(names))
    if not ns:
        return None
    return 1e-6 * ns / len(rec.ticks)
