"""The score kernel's share of its memory roofline, in percent.

The least time is the window's logical histogram work (``bench/work.py``:
4 bytes per neighbour slot read, 4*K bytes per vertex row written, per job)
over the chip's HBM bandwidth; the kernel time is the summed device time of
the kernel's events in the trace, on the device that spent the most. The
VPU has no published compare peak, so the bound is memory alone."""
from bench.work import peaks, score_histogram_bytes

# the Pallas kernel's events are named after its jitted entry point
KERNEL_NAMES = ("%fennel_scores_sharded_pallas",)


def read(run):
    kernel_s = run.trace.max_op_seconds(KERNEL_NAMES)
    if kernel_s is None:
        return None
    g = run.job.graph
    work = len(run.records) * score_histogram_bytes(
        g.num_vertices, g.indices.shape[0], run.job.k
    )
    least_s = work / peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / kernel_s
