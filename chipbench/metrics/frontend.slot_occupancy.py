"""Decode tokens delivered per decode step and slot in the window, in %:
how full the scheduler keeps the slots. Steps are the scheduler's burst
count times the burst length."""


def read(record):
    steps = record["bursts"] * record["burst"] * record["slots"]
    if not steps:
        return None
    decoded = 0
    for _, _, events in record["tracks"]:
        prev = 0
        for t, n in events:
            if record["t0"] < t <= record["t_end"]:
                # a request's first token comes from its prefill, not a step
                decoded += n - max(prev, 1)
            prev = n
    return 100.0 * decoded / steps
