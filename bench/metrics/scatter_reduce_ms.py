"""Device milliseconds per PageRank iteration in the segment reduce (the
scatter-add of edge messages into vertex sums), on the device that spent the
most, from the trace. XLA fuses the scatter; the job names the fused ops
whose source op is the scatter-add (``bench.trace.hlo_ops_from``)."""


def read(run):
    needles = tuple(f"%{name} = " for name in run.job.scatter_ops)
    secs = run.trace.max_op_seconds(needles) if needles else None
    if secs is None:
        return None
    return secs / (len(run.records) * run.job.iters) * 1e3
