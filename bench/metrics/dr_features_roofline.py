"""Share of the dr_features kernel's device time that the chip needs at
its roofline for the work the algorithm asks of it, in %."""
from harness import work


def read(rec):
    calls = [o for o in rec.ops if o.kernel == "dr_features" and o.device == 0]
    if not calls:
        return None
    i = rec.info
    flops, nbytes = work.dr_features(i["W"], i["T"])
    least, _bound = work.least_time(flops, nbytes,
                                    work.peaks(i["device_kind"]))
    spent = sum(o.end - o.start for o in calls) * 1e-9
    return 100.0 * least * len(calls) / spent
