"""Model FLOPs served in the window over the window's seconds and the chip's
int8 peak, in %. Counted per token: two FLOPs for every matmul parameter
(the layers' projections and the output head) and the attention over its
context; a prompt counts when its prefill finished in the window, a decoded
token when it arrived. Both served dots feed the MXU int8."""
from chipbench import work


def read(record):
    cfg, t0, t_end = record["cfg"], record["t0"], record["t_end"]
    flops, prompts = 0.0, []
    for spec, _, events in record["tracks"]:
        plen, prev = len(spec.prompt), 0
        for t, n in events:
            if t0 < t <= t_end:
                if prev == 0:
                    prompts.append(plen)
                for j in range(max(prev, 1), n):
                    flops += work.token_flops(cfg, plen + j)
            prev = n
    flops += work.prompt_flops(cfg, prompts)
    if not flops:
        return None
    return 100.0 * flops / record["window_s"] / record["peak"]["int8_ops"]
