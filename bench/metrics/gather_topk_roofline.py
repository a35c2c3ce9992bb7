"""Share of its roofline the gather top-k kernel
(``kernels/distance_topk.py``) reaches over the window, in %.

The work is the algorithm's, not the kernel body's: 2·d operations for
every (query, key) pair of every scheduled (R tile, S tile) pair, and
the bytes of the scheduled S tiles, the query tiles and the output runs
(a 4-byte distance and a 4-byte id per query and rank). The least time
is the larger of the operations over the bf16 matrix peak (the only
published matrix peak; this f32 work at ``precision=HIGHEST`` takes
several passes, so the share cannot come near 100) and the bytes over
the HBM bandwidth; the share is that time over the kernel's device time
in the trace.

The kernel has no name of its own in the trace: Mosaic's call is named
after the jitted program around it (``%_megastep.1 = ... custom-call``,
``custom_call_target="tpu_custom_call"``). The fused megastep's one
Mosaic call is the gather kernel, so the reduction takes the
``tpu_custom_call`` ops of the window.
"""
KERNEL = r'custom_call_target="tpu_custom_call"'


def work(per_batch, bn: int, dim: int, k: int) -> tuple[float, float]:
    """(operations, bytes) of the scheduled tile pairs of the batches,
    each given as [visited, total, bm, r_tiles]."""
    flops = bytes_ = 0.0
    for visited, _total, bm, r_tiles in per_batch:
        flops += 2.0 * dim * visited * bm * bn
        bytes_ += 4.0 * visited * bn * dim + r_tiles * bm * (4.0 * dim
                                                             + 8.0 * k)
    return flops, bytes_


def read(run):
    if run.trace is None or not run.tiles or not run.peaks:
        return None
    ns, n = run.trace.op_ns(KERNEL)
    if not n or ns <= 0:
        return None
    flops, bytes_ = work(run.tiles["per_batch"], run.tiles["bn"],
                         int(run.ctx.data.shape[1]), int(run.ctx.cfg["k"]))
    least = max(flops / run.peaks["bf16_flops_per_s"],
                bytes_ / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns / 1e9)
