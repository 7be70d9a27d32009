"""Device milliseconds per PageRank iteration in the halo all-to-all, on the
device that spent the most, from the trace."""


def read(run):
    secs = run.trace.max_op_seconds((" all-to-all(",))
    if secs is None:
        return None
    return secs / (len(run.records) * run.job.iters) * 1e3
