"""Share of the HBM roofline that the decode steps reached, in %, for a
Zamba2 hybrid: the least bytes of the window's decode steps
(``work_hybrid.decode_bytes``: the int8 weights once per step, each decoded
token's slot mixer state read and written once, its K/V rows up to its live
context read once) at the chip's HBM peak, over the device seconds of the
decode-burst program (``decode_burst``) in the trace. Steps are the
scheduler's bursts times the burst length; tokens come from the tracks."""
from chipbench import work_hybrid


def read(record):
    cfg = record["cfg"]
    runs, seconds = (record.get("trace") or {}).get("programs", {}).get(
        "decode_burst", (0, 0.0))
    if "layers_block_type" not in cfg or not runs or seconds <= 0:
        return None
    contexts = []
    for spec, _, events in record["tracks"]:
        plen, prev = len(spec.prompt), 0
        for t, n in events:
            if record["t0"] < t <= record["t_end"]:
                # a request's first token comes from its prefill, not a step
                contexts += [plen + j for j in range(max(prev, 1), n)]
            prev = n
    steps = record["bursts"] * record["burst"]
    if not steps:
        return None
    least = (work_hybrid.decode_bytes(cfg, steps, contexts)
             / record["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
