"""XLA collective backend: a group IS a jax process world.

Group creation runs jax.distributed.initialize over the member processes
(coordinator = rank 0, address exchanged through the group's rendezvous
actor), materializing one global device world; every op then compiles to
the corresponding XLA collective (psum / all_gather / psum_scatter) via
shard_map over a Mesh spanning the group — on TPU these lower to ICI
collectives, on the CPU test world to the Gloo cross-process backend.

This is the retargeting SURVEY.md §5 prescribes for the reference's
NCCL/gloo groups (nccl_collective_group.py: communicator per group,
rendezvous via named actor): the "communicator" is the compiled program's
collective, the rendezvous carries only the coordinator address.

p2p send/recv are not SPMD ops (only two ranks participate) and ride the
host mailbox plane — same split as the reference, whose p2p also bypasses
collective rings (collective.py:531 send / :594 recv are point-to-point).

Not run on a chip: a process that touches JAX takes every local chip, so
one jax.distributed process per rank needs one host per rank (or per-chip
isolation the runtime does not have); it runs on CPU process worlds only.
"""
from __future__ import annotations

import numpy as np

_OP_TO_LAX = ("sum", "product", "min", "max")


class XlaGroup:
    """Membership of this process in a jax.distributed world."""

    def __init__(self, name: str, world_size: int, rank: int,
                 coordinator: str):
        import jax

        self.name = name
        self.world_size = world_size
        self.rank = rank
        # One jax.distributed world per process (jax constraint); a second
        # xla group in the same process reuses it and must have the same
        # membership shape.
        if world_size > 1 and not jax.distributed.is_initialized():
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=world_size,
                process_id=rank,
            )
        if jax.process_count() not in (1, world_size):
            raise RuntimeError(
                f"xla group {name!r}: process already in a "
                f"{jax.process_count()}-process world, cannot host a "
                f"{world_size}-rank group")
        self._jax = jax
        self._mesh = None
        self._fns: dict = {}

    # -- mesh / compiled-op cache ------------------------------------------

    def _ensure_mesh(self):
        if self._mesh is None:
            jax = self._jax
            # one device per rank keeps the group axis == process axis
            devs = []
            by_proc: dict[int, list] = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, []).append(d)
            for p in sorted(by_proc):
                devs.append(sorted(by_proc[p], key=lambda d: d.id)[0])
            self._mesh = jax.sharding.Mesh(np.array(devs), ("ranks",))
        return self._mesh

    def _global_array(self, arr, mesh=None, axis: str = "ranks",
                      world: int | None = None):
        """Stack this rank's array as its shard of a leading group axis.
        Works for numpy AND device-resident jax arrays (device_put moves
        device-to-device, no host staging); the pair-mesh p2p path reuses
        it with axis="pair", world=2."""
        jax = self._jax
        if mesh is None:
            mesh = self._ensure_mesh()
        if world is None:
            world = self.world_size
        spec = jax.sharding.PartitionSpec(axis, *([None] * arr.ndim))
        sharding = jax.sharding.NamedSharding(mesh, spec)
        local_dev = [d for d in mesh.devices.flat
                     if d.process_index == jax.process_index()][0]
        shard = jax.device_put(arr[None, ...], local_dev)
        return jax.make_array_from_single_device_arrays(
            (world,) + tuple(arr.shape), sharding, [shard]), sharding

    def _compiled(self, kind: str, op: str, shape, dtype):
        key = (kind, op, shape, dtype)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        mesh = self._ensure_mesh()
        ndim = len(shape)
        in_spec = P("ranks", *([None] * ndim))

        def reduce_term(x):
            # x: (1, *shape) block on this rank
            if op == "sum":
                return lax.psum(x, "ranks")
            if op == "max":
                return lax.pmax(x, "ranks")
            if op == "min":
                return lax.pmin(x, "ranks")
            # product via exp/log is lossy; use all_gather + prod
            g = lax.all_gather(x[0], "ranks")        # (world, *shape)
            return jax.numpy.prod(g, axis=0)[None]

        if kind == "allreduce":
            body = reduce_term
            out_spec = in_spec
        elif kind == "reducescatter":
            def body(x):
                r = reduce_term(x)[0]                # (*shape,)
                return lax.dynamic_slice_in_dim(
                    r, lax.axis_index("ranks") * (shape[0] //
                                                  self.world_size),
                    shape[0] // self.world_size, axis=0)[None]
            out_spec = in_spec
        elif kind == "allgather":
            def body(x):
                return lax.all_gather(x[0], "ranks")[None]
            out_spec = in_spec
        else:
            raise ValueError(kind)

        sm = jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                           out_specs=out_spec)
        fn = jax.jit(sm)
        self._fns[key] = fn
        return fn

    def _compiled_broadcast(self, src: int, shape, dtype):
        """Binomial-tree broadcast over ppermute: ⌈log2(N)⌉ steps, total
        payload moved ≈ N-1 copies (a psum-of-zeros "broadcast" moves
        2(N-1)/N of an allreduce — this is the real thing)."""
        key = ("broadcast", src, shape, dtype)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        mesh = self._ensure_mesh()
        N = self.world_size
        in_spec = P("ranks", *([None] * len(shape)))

        def body(x):
            # x holds the payload only on src; zero elsewhere
            idx = lax.axis_index("ranks")
            x = jax.numpy.where(idx == src, x, jax.numpy.zeros_like(x))
            have = 1            # effective ranks 0..have-1 hold the data
            while have < N:
                pairs = []
                for e in range(have):
                    te = e + have
                    if te < N:
                        pairs.append(((e + src) % N, (te + src) % N))
                recv = lax.ppermute(x, "ranks", perm=pairs)
                x = x + recv    # recv is zero except at the new holders
                have *= 2
            return x

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                   out_specs=in_spec))
        self._fns[key] = fn
        return fn

    # -- device-resident p2p ------------------------------------------------

    def _rank_device(self, rank: int):
        for d in self._jax.devices():
            if d.process_index == rank:
                return d
        raise RuntimeError(f"no device for rank {rank}")

    def _pair_fn(self, src: int, dst: int, shape, dtype):
        """Compiled 2-device ppermute over a SUB-mesh of the world: only
        the endpoints enter the program, so send/recv stays a
        point-to-point exchange (NCCL-send/recv analog) — on TPU the
        transfer rides ICI/DCN links, never the host mailbox plane."""
        key = ("p2p", src, dst, shape, dtype)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        from jax import lax
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        mesh = Mesh(
            np.array([self._rank_device(src), self._rank_device(dst)]),
            ("pair",))
        in_spec = P("pair", *([None] * len(shape)))

        def body(x):
            return lax.ppermute(x, "pair", perm=[(0, 1)])

        fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                                   out_specs=in_spec))
        self._fns[key] = (fn, mesh)
        return self._fns[key]

    def send_device(self, arr, dst: int):
        """Called on the SOURCE rank; pairs with recv_device(dst side).
        Device-resident inputs never stage through the host (device_put
        is device-to-device). Blocks until the transfer program ran
        (matched-call contract, same as NCCL send/recv)."""
        jax = self._jax
        if not self._is_device_array(arr):
            arr = np.asarray(arr)
        dtype = str(jax.numpy.dtype(arr.dtype))   # canonical (bfloat16!)
        fn, mesh = self._pair_fn(self.rank, dst, tuple(arr.shape), dtype)
        garr, _ = self._global_array(arr, mesh=mesh, axis="pair", world=2)
        jax.block_until_ready(fn(garr))

    def recv_device(self, shape, dtype, src: int):
        """Called on the DESTINATION rank; returns the payload as a
        device-resident jax array."""
        jax = self._jax
        dt = jax.numpy.dtype(dtype)   # resolves "bfloat16" via ml_dtypes
        fn, mesh = self._pair_fn(src, self.rank, tuple(shape), str(dt))
        zeros = np.zeros(tuple(shape), dt)
        garr, _ = self._global_array(zeros, mesh=mesh, axis="pair",
                                     world=2)
        out = fn(garr)
        return out.addressable_shards[0].data[0]

    # -- ops ----------------------------------------------------------------
    # Device residency: jax-array inputs stay on device end-to-end — the
    # result is returned as a jax array (no host round-trip); numpy inputs
    # round-trip through the host as before. One device per process carries
    # the group axis; an actor owning several chips spreads *data* over them
    # through the Train stack's global mesh, not through this per-rank API.

    def _is_device_array(self, arr) -> bool:
        return isinstance(arr, self._jax.Array)

    def _run(self, kind: str, arr, op: str = "sum"):
        keep_on_device = self._is_device_array(arr)
        if not keep_on_device:
            arr = np.asarray(arr)
        garr, _ = self._global_array(arr)
        fn = self._compiled(kind, op, tuple(arr.shape), str(arr.dtype))
        out = fn(garr)
        local = out.addressable_shards[0].data[0]
        if keep_on_device:
            return local
        return np.asarray(local)

    def allreduce(self, arr, op, seq):
        if self.world_size == 1:
            return arr if self._is_device_array(arr) else np.asarray(arr)
        return self._run("allreduce", arr, op)

    def reduce(self, arr, dst, op, seq):
        out = self.allreduce(arr, op, seq)
        return out if self.rank == dst else arr

    def broadcast(self, arr, src, seq):
        if self.world_size == 1:
            return arr if self._is_device_array(arr) else np.asarray(arr)
        keep = self._is_device_array(arr)
        if not keep:
            arr = np.asarray(arr)
        garr, _ = self._global_array(arr)
        fn = self._compiled_broadcast(src, tuple(arr.shape),
                                      str(arr.dtype))
        out = fn(garr)
        local = out.addressable_shards[0].data[0]
        return local if keep else np.asarray(local)

    def allgather(self, arr, seq) -> list:
        if self.world_size == 1:
            return [arr if self._is_device_array(arr) else np.asarray(arr)]
        stacked = self._run("allgather", arr)
        return [stacked[i] for i in range(self.world_size)]

    def reducescatter(self, arr, op, seq):
        if self.world_size == 1:
            return arr if self._is_device_array(arr) else np.asarray(arr)
        dim0 = arr.shape[0]
        if dim0 % self.world_size:
            # uneven leading dim: fall back to allreduce + local slice
            out = self._run("allreduce", arr, op)
            if self._is_device_array(out):
                splits = np.cumsum([len(s) for s in np.array_split(
                    np.empty(dim0), self.world_size)])[:-1]
                start = 0 if self.rank == 0 else int(splits[self.rank - 1])
                stop = int(splits[self.rank]) if self.rank < len(splits) \
                    else dim0
                return out[start:stop]
            return np.array_split(out, self.world_size, axis=0)[self.rank]
        return self._run("reducescatter", arr, op)

    def barrier(self, seq):
        self.allreduce(np.zeros((1,), np.float32), "sum", seq)

    def close(self):
        pass  # the jax.distributed world outlives individual groups
