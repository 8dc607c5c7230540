"""Device: operations the algorithm must do in the window over the chip's
peak bf16 rate for the window's length, in percent (``bench.roofline``
counts them from the shapes served; skipped and repeated work is not
counted)."""
from bench import roofline


def read(run):
    batches = [b for b in run.window_batches() if b.outputs is not None]
    if not batches or run.device_kind is None:
        return None
    pk = roofline.peaks(run.device_kind)
    flops = sum(roofline.batch_flops(run.dims, run.traffic, b.n, b.prompt_len,
                                     b.kind) for b in batches)
    t0, t1 = run.window
    return flops / ((t1 - t0) * pk["bf16_flops"]) * 100.0
