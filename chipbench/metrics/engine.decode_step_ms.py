"""Device time of the decode-burst program per decode step, from the
profiler trace (the jitted program ``decode_burst``)."""


def read(record):
    trace = record.get("trace") or {}
    runs, seconds = trace.get("programs", {}).get("decode_burst", (0, 0.0))
    if not runs:
        return None
    return 1e3 * seconds / (runs * record["burst"])
