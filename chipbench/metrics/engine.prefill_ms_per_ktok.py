"""Device time of the chunked-prefill programs (``chunk`` and ``admit``) per
1000 prompt rows the scheduler ran in the window."""


def read(record):
    programs = (record.get("trace") or {}).get("programs", {})
    seconds = sum(programs.get(p, (0, 0.0))[1] for p in ("chunk", "admit"))
    if not record["prefill_rows"] or not seconds:
        return None
    return 1e3 * seconds / (record["prefill_rows"] / 1000.0)
