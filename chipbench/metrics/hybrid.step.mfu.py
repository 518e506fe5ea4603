"""Model FLOPs served in the window over the window's seconds and the chip's
int8 peak, in %, counted for a Zamba2 hybrid (``chipbench/work_hybrid.py``):
per token its projections (mixers, shared-block calls on the concatenated
input, adapters, ``linear``, head), each mixer's conv and state update, and
the hybrid layers' attention over its context. A prompt counts when its
prefill finished in the window, a decoded token when it arrived."""
from chipbench import work_hybrid


def read(record):
    cfg, t0, t_end = record["cfg"], record["t0"], record["t_end"]
    if "layers_block_type" not in cfg:
        return None
    flops, prompts = 0.0, []
    for spec, _, events in record["tracks"]:
        plen, prev = len(spec.prompt), 0
        for t, n in events:
            if t0 < t <= t_end:
                if prev == 0:
                    prompts.append(plen)
                for j in range(max(prev, 1), n):
                    flops += work_hybrid.token_flops(cfg, plen + j)
            prev = n
    flops += work_hybrid.prompt_flops(cfg, prompts)
    if not flops:
        return None
    return 100.0 * flops / record["window_s"] / record["peak"]["int8_ops"]
