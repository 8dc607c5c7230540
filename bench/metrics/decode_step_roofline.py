"""Paged decode: share of the decode steps' roofline, in percent.

The least time of a step is the larger of its operations over the peak
bf16 rate and its bytes over the HBM bandwidth (``bench.roofline``:
every matmul weight of the cut model, the batch's KV read up to the
step's position and the new token's KV written). The device time is the
union of device operations inside the ``decode`` spans, over the steps
taken."""
from bench import roofline


def read(run):
    tr = run.reduced
    batches = [b for b in run.window_batches() if b.outputs is not None]
    if tr is None or not tr.busy or not batches:
        return None
    busy = tr.busy_in("decode")
    if busy <= 0:
        return None
    pk = roofline.peaks(run.device_kind)
    G, least = run.traffic["gen_len"], 0.0
    for b in batches:
        for t in range(1, G):
            f, by = roofline.decode_step_cost(run.dims, b.n, b.prompt_len + t)
            least += max(f / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])
    return least / busy * 100.0
