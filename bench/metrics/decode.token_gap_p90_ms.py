"""Paged decode: 90th percentile (nearest rank) of the time between
consecutive tokens of a batch on device 0, ms: from the end of one
``jit_decode_step_paged`` program to the end of the next, the first step
of a batch measured from the end of the program that recovered or
prefilled it. A trace whose programs carry none of these names gives no
reading."""
import math

DECODE = "jit_decode_step_paged"
#: the programs that produce a batch's first token
FIRST = ("jit_collective_recover", "jit_serial_recover", "jit_prefill",
         "jit_prefix_extend")


def read(run):
    tr = run.reduced
    if tr is None:
        return None
    w0, w1 = tr.window
    gaps, last = [], None
    for name, s, e in tr.module_events:
        if name in FIRST:
            last = e
        elif name == DECODE:
            if last is not None and w0 <= s and e <= w1:
                gaps.append(e - last)
            last = e
    if not gaps:
        return None
    gaps.sort()
    return gaps[max(0, math.ceil(0.9 * len(gaps)) - 1)] * 1e-6
