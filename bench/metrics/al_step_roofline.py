"""Share of the fused al_step kernel's device time that the chip needs at
its roofline for the work the algorithm asks of it, in %."""
from harness import work


def read(rec):
    calls = [o for o in rec.ops if o.kernel == "al_step" and o.device == 0]
    if not calls or not rec.ticks:
        return None
    i = rec.info
    steps = len(rec.ticks) * i["warm_steps"] / len(calls)
    flops, nbytes = work.al_step(i["W"] - i["W_batch"], i["W_batch"], i["T"],
                                 steps, per_row_carbon=i["R"] > 1)
    least, _bound = work.least_time(flops, nbytes,
                                    work.peaks(i["device_kind"]))
    spent = sum(o.end - o.start for o in calls) * 1e-9
    return 100.0 * least * len(calls) / spent
