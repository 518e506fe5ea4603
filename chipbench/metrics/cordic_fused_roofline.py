"""Share of its roofline that the fused CORDIC dot+AF kernel reached, in %:
the least time of every call in the window (its operations at the chip's
int8 peak, or its bytes at the HBM peak, whichever is longer; weights counted
at 1 byte) over the device time the calls took, together with the time of
the operations that staged their weights into on-chip memory (a slice or a
copy of a ``(k, n)`` weight, in any layout): a call reads its weight from
there, and the read from HBM is part of its work."""
from chipbench import trace, work

KERNEL = "fused_dot_af"  # the Pallas call's name in the program's HLO


def read(record):
    red = record.get("trace") or {}
    calls = [c for c in red.get("kernels", []) if c[0] == KERNEL]
    spent = sum(seconds for _, seconds, _ in calls)
    if not calls or spent <= 0:
        return None
    weights = {(k, n) for _, _, (_, k, n) in calls}
    spent += sum(seconds for _, seconds, dims in red.get("staged", [])
                 if any(trace.splits_as(dims, k, n) for k, n in weights))
    least = sum(work.least_seconds(work.fused_call_work(*shape), record["peak"])
                for _, _, shape in calls)
    return 100.0 * least / spent
