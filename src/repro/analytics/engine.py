"""Partition-aware vertex-program engine.

Two execution modes sharing one per-device step:

  * ``simulated`` - the K devices live on the leading axis of every array on
    a single real device; the halo all-to-all is an axis transpose. Used for
    unit tests and CPU benchmarks.
  * ``shard_map`` - the K devices are a real 1-D JAX mesh axis ``"w"``; the
    halo exchange is ``jax.lax.all_to_all`` over ICI. This is what runs on a
    pod, and what the dry-run lowers.

The engine's communication volume is *exactly* the paper's λ_CV·K·|V| when
counting true (unpadded) messages - partition quality translates directly
into collective bytes.

Both modes name the step's phases with ``jax.named_scope``, which reaches
the compiled program only as ``op_name`` metadata, so a profiler trace finds
each phase's ops by name: ``vp.send`` (the ghost-table gather of the states
to send), ``vp.exchange`` (the halo all-to-all), ``vp.gather`` (the message
of every state slot, then one gather of it per edge), ``vp.reduce`` (the
segment reduce, the only scatter) and ``vp.apply``.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analytics.localize import LocalizedGraph
from repro.analytics.programs import VertexProgram
from repro.compat import shard_map


@dataclasses.dataclass
class RunStats:
    iterations: int
    true_halo_messages_per_iter: int
    padded_halo_elements_per_iter: int
    bytes_per_iter_true: int
    bytes_per_iter_padded: int
    max_local_edges: int
    mean_local_edges: float


def _segment_reduce(msgs, rows, out_len, kind: str, identity: float):
    if kind == "sum":
        return jnp.zeros(out_len, msgs.dtype).at[rows].add(msgs)
    return jnp.full(out_len, identity, msgs.dtype).at[rows].min(msgs)


def _local_step(
    local_state,  # [v_max]
    recv,  # [k, h_max] ghost states as laid out in the ghost table
    rows,  # [e_max]
    cols,  # [e_max]
    deg_full,  # [state_len]
    program: VertexProgram,
    ctx: dict,
    v_max: int,
):
    with jax.named_scope("vp.gather"):
        identity = jnp.asarray(program.identity, local_state.dtype)
        full = jnp.concatenate([local_state, recv.reshape(-1), identity[None]])
        # message depends on the source slot alone (``VertexProgram``), so
        # it is applied per slot and gathered once per edge
        msgs = program.message(full, deg_full)[cols]
    with jax.named_scope("vp.reduce"):
        agg = _segment_reduce(msgs, rows, v_max + 1, program.reduce_kind, program.identity)
    with jax.named_scope("vp.apply"):
        return program.apply(local_state, agg[:v_max], ctx)


class GraphEngine:
    def __init__(self, lg: LocalizedGraph, program: VertexProgram, ctx: dict | None = None):
        self.lg = lg
        self.program = program
        self.ctx = dict(ctx or {})
        self.ctx.setdefault("num_vertices", lg.num_vertices)

    def graph_arrays(self) -> tuple[np.ndarray, ...]:
        """``(rows, cols, degrees_full, send_gather)``: the local graph as
        the step functions take it. It is passed as arguments, not folded
        into the program as constants, so the program's size does not grow
        with the graph."""
        lg = self.lg
        return lg.rows, lg.cols, lg.degrees_full, lg.send_gather

    # ------------------------------------------------------------ simulated
    @functools.cached_property
    def _sim_step(self):
        lg, program, ctx = self.lg, self.program, self.ctx
        k = lg.k

        local = functools.partial(
            _local_step, program=program, ctx=ctx, v_max=lg.v_max
        )
        vstep = jax.vmap(local)

        @jax.jit
        def step(state, rows, cols, deg_full, send_gather):  # state: [k, v_max]
            with jax.named_scope("vp.send"):
                send = state[jnp.arange(k)[:, None, None], send_gather]  # [k,k,h]
            with jax.named_scope("vp.exchange"):
                recv = jnp.transpose(send, (1, 0, 2))  # all-to-all
            return vstep(state, recv, rows, cols, deg_full)

        return step

    def run_simulated(self, iters: int, timings: dict | None = None) -> np.ndarray:
        """Run ``iters`` steps in simulated mode; returns the state by global
        vertex id. The step is compiled before the first iteration, and
        ``timings`` (if given) receives ``compile_s`` and ``seconds``, the
        wall time of the iterations alone, ended with ``block_until_ready``."""
        state = jnp.asarray(self.program.init_state(self.lg, self.ctx))
        arrays = tuple(jnp.asarray(a) for a in self.graph_arrays())
        t0 = time.perf_counter()
        step = self._sim_step.lower(state, *arrays).compile()
        t1 = time.perf_counter()
        for _ in range(iters):
            state = step(state, *arrays)
        state.block_until_ready()
        if timings is not None:
            timings.update(compile_s=t1 - t0, seconds=time.perf_counter() - t1)
        return self.gather_global(np.asarray(state))

    # ------------------------------------------------------------ shard_map
    def build_sharded(self, mesh: Mesh, axis: str = "w", iters: int = 1):
        """Returns ``(fn, sharding)``. ``fn(state, *graph_arrays)`` runs
        ``iters`` iterations under ``shard_map`` on ``mesh`` (one device per
        partition along ``axis``); every argument is placed with
        ``sharding``."""
        lg, program, ctx = self.lg, self.program, self.ctx
        if mesh.shape[axis] != lg.k:
            raise ValueError(
                f"mesh axis {axis}={mesh.shape[axis]} != k={lg.k} partitions"
            )
        local = functools.partial(
            _local_step, program=program, ctx=ctx, v_max=lg.v_max
        )

        def device_fn(state, rows, cols, deg_full, send_gather):
            # blocks carry a leading device axis of size 1
            state, rows, cols = state[0], rows[0], cols[0]
            deg_full, send_gather = deg_full[0], send_gather[0]

            def one_iter(_, st):
                with jax.named_scope("vp.send"):
                    send = st[send_gather]  # [k, h_max]
                with jax.named_scope("vp.exchange"):
                    recv = jax.lax.all_to_all(
                        send, axis, split_axis=0, concat_axis=0, tiled=True
                    )
                return local(st, recv, rows, cols, deg_full)

            out = jax.lax.fori_loop(0, iters, one_iter, state)
            return out[None]

        spec = P(axis)
        fn = jax.jit(
            shard_map(
                device_fn,
                mesh=mesh,
                in_specs=(spec, spec, spec, spec, spec),
                out_specs=spec,
            )
        )
        return fn, NamedSharding(mesh, spec)

    def run_sharded(self, mesh: Mesh, iters: int, axis: str = "w") -> np.ndarray:
        fn, sharding = self.build_sharded(mesh, axis=axis, iters=iters)
        state = jax.device_put(self.program.init_state(self.lg, self.ctx), sharding)
        arrays = [jax.device_put(a, sharding) for a in self.graph_arrays()]
        out = fn(state, *arrays)
        return self.gather_global(np.asarray(out))

    def lower_sharded(self, mesh: Mesh, iters: int, axis: str = "w"):
        """Lower (no execution) for dry-run/roofline inspection; needs only
        the mesh's device descriptions, not the devices."""
        fn, sharding = self.build_sharded(mesh, axis=axis, iters=iters)
        state_spec = jax.ShapeDtypeStruct(
            (self.lg.k, self.lg.v_max), jnp.float32, sharding=sharding
        )
        specs = [
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
            for a in self.graph_arrays()
        ]
        return fn.lower(state_spec, *specs)

    # -------------------------------------------------------------- helpers
    def gather_global(self, state_kv: np.ndarray) -> np.ndarray:
        out = np.zeros(self.lg.num_vertices, dtype=state_kv.dtype)
        for p in range(self.lg.k):
            c = int(self.lg.local_count[p])
            out[self.lg.local_to_global[p, :c]] = state_kv[p, :c]
        return out

    def stats(self, iters: int, bytes_per_elem: int = 4) -> RunStats:
        lg = self.lg
        true_m = lg.true_halo_messages()
        padded = lg.padded_halo_elements_per_iter()
        edges_per_dev = (lg.rows != lg.v_max).sum(axis=1)
        return RunStats(
            iterations=iters,
            true_halo_messages_per_iter=true_m,
            padded_halo_elements_per_iter=padded,
            bytes_per_iter_true=true_m * bytes_per_elem,
            bytes_per_iter_padded=padded * bytes_per_elem,
            max_local_edges=int(edges_per_dev.max()),
            mean_local_edges=float(edges_per_dev.mean()),
        )
