"""Device milliseconds per PageRank iteration in the edge gather (the message
of every state slot, from its state and degree, then one gather of it per
edge), on the device that spent the most, from the trace. The program
names these ops with the scope ``vp.gather``; the compiled program's
metadata maps the scope to the instructions (``bench.trace.hlo_ops_from``).
None where the program names no such scope or the trace holds none of its
ops.

JAX's persistent compilation cache leaves metadata out of its key, so a
program served from the cache carries the op names of whichever program
filled the entry: one whose HLO is the same but for its metadata, such as
the same program without the scopes. Where the executable names no
``vp.gather`` op, the program is compiled once more past the cache, after
the window; the instruction names of equal HLO are the same."""
from bench.trace import hlo_ops_from

SCOPE = "vp.gather"


def _compiled_fresh(job):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        fn, _ = job.engine.build_sharded(job.mesh, iters=job.iters)
        return fn.lower(job.state0, *job.arrays).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def read(run):
    job = run.job
    if not run.trace.devices or getattr(job, "compiled", None) is None:
        return None
    names = hlo_ops_from(job.compiled.as_text(), SCOPE)
    if not names:
        names = hlo_ops_from(_compiled_fresh(job).as_text(), SCOPE)
    needles = tuple(f"%{name} = " for name in names)
    secs = run.trace.max_op_seconds(needles) if needles else None
    if secs is None:
        return None
    return secs / (len(run.records) * job.iters) * 1e3
