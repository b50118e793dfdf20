"""batch_occupancy.tok_s: mean share of the decode batch's slots in use
per decode step over the window (scheduler counters occupancy_sum and
decode_steps)."""


def read(run):
    b, a = run.sched["before"], run.sched["after"]
    steps = a["decode_steps"] - b["decode_steps"]
    if steps <= 0:
        return None
    occ = a["occupancy_sum"] - b["occupancy_sum"]
    return 100.0 * occ / steps / run.max_batch
