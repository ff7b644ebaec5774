"""Collective communication API.

Reference architecture (SURVEY.md §2.9, §3.5): python paddle.distributed.* →
communication/stream/* → pybind → ProcessGroupNCCL → NCCLCommContext →
ncclAllReduce, with TCPStore bootstrap and per-ring comm contexts.

TPU-native redesign: the transport is XLA collectives over ICI/DCN. A Group is
a 1-D device mesh axis; each eager collective jit-compiles a shard_map whose
body is the XLA collective (psum/all_gather/ppermute/all_to_all) — the
ProcessGroup/CommContext/NCCL stack collapses into the compiler's collective
emission, and the executable cache plays the role of the comm-op cache.

Two execution modes, auto-detected from ``jax.process_count()``:

* **Single-controller** (1 process, N devices): a tensor participating in an
  eager collective is RANK-STACKED — dim 0 indexes the group's ranks (the
  analog of each rank's local tensor in the reference's multi-process world;
  the reference's own single-host multi-rank tests, test/collective/, are the
  model).
* **Multi-process** (a real ``jax.distributed`` world, rank == process, as
  bootstrapped by ``init_parallel_env`` from the launcher's env): tensors are
  PROCESS-LOCAL, exactly the reference's semantics
  (``process_group.h:47`` — each rank passes its local tensor and receives
  its local result). The same shard_map bodies run over a one-device-per-
  process mesh; XLA's CPU Gloo / TPU ICI transport carries the bytes.

In-graph (jit/TrainStep) code should instead rely on sharding annotations,
where GSPMD inserts collectives automatically.
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from .auto_parallel import ProcessMesh

def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


#: jaxpr primitive names that are cross-rank collectives. This is the
#: canonical set the static analyzer keys on (analysis/dataflow.py rule
#: DF004, collective-ordering lint): every mesh axis must observe an
#: identical sequence of these primitives on all ranks or the mesh
#: deadlocks. Keep in sync with the lax collectives the eager API below
#: emits through its shard_map bodies.
COLLECTIVE_PRIMITIVES = frozenset({
    "psum", "pmax", "pmin", "ppermute", "pshuffle", "all_gather",
    "all_to_all", "psum_scatter", "reduce_scatter", "pbroadcast",
})


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def _mp() -> bool:
    """True in a real multi-process world (rank == process, reference
    semantics); False under the single-controller rank-stacked convention."""
    return jax.process_count() > 1


class Group:
    """Process group = 1-D mesh axis (process_group.h:47 analog)."""

    _next_id = [0]

    def __init__(self, ranks: List[int], mesh: ProcessMesh, axis_name: str):
        self.ranks = list(ranks)
        self.nranks = len(ranks)
        self.mesh = mesh
        self.axis_name = axis_name
        self.id = Group._next_id[0]
        Group._next_id[0] += 1
        self._eager_mesh = None

    @property
    def world_size(self):
        return self.nranks

    @property
    def rank(self):
        if _mp():
            return self.get_group_rank(jax.process_index())
        return 0  # single-controller SPMD: one logical program

    @property
    def rank_in_group(self):
        return self.rank

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def _collective_mesh(self):
        """Mesh the eager collectives run over.

        Multi-process: one device per member process (rank == process, as the
        reference's ProcessGroup does); only member processes participate.
        Single-controller: the group's full device mesh.
        """
        if not _mp():
            return self.mesh.jax_mesh
        if self._eager_mesh is None:
            by_proc = {}
            for d in jax.devices():
                by_proc.setdefault(d.process_index, d)
            devs = np.array([by_proc[r] for r in self.ranks], dtype=object)
            self._eager_mesh = jax.sharding.Mesh(devs, (self.axis_name,))
        return self._eager_mesh

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_WORLD: List[Optional[Group]] = [None]


_BOOTSTRAP = {"store": None}


def _maybe_init_multihost():
    """Multi-host bootstrap (parallel.py:943's TCPStore + comm-context
    creation, TPU-shaped): when the launcher's env says this is a
    multi-process job, initialize the PJRT distributed runtime (ICI/DCN
    plane) and open the TCPStore control plane (barriers, elastic,
    checkpoint coordination) against rank 0."""
    import os
    nnodes = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    coord = os.environ.get("PADDLE_MASTER",
                           os.environ.get("MASTER_ENDPOINT"))
    if nnodes <= 1 or not coord or _BOOTSTRAP["store"] is not None:
        return
    rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
    # the launcher normalizes PADDLE_MASTER to an http:// KV endpoint and
    # publishes the real gRPC coordinator as JAX_COORDINATOR_ADDRESS
    # (launch/controllers.py) — strip the scheme for our own parsing
    coord = coord.split("://", 1)[-1]
    if ":" not in coord:
        raise ValueError(f"PADDLE_MASTER must be host:port, got {coord!r}")
    host, port = coord.rsplit(":", 1)
    coord_addr = os.environ.get("JAX_COORDINATOR_ADDRESS",
                                f"{host}:{int(port) + 1}")
    try:
        # num_processes/process_id must be explicit: jax only reads the
        # coordinator address from env, not the process counts
        jax.distributed.initialize(coordinator_address=coord_addr,
                                   num_processes=nnodes, process_id=rank)
    except RuntimeError as e:
        if "already" not in str(e).lower():
            raise  # real failure: do NOT proceed as N separate jobs
    from ..core.native import TCPStore
    # control plane: master+2 (master = launcher KV, master+1 = PJRT
    # coordinator, see launch/main.py port layout)
    store = TCPStore(host, int(port) + 2, is_master=(rank == 0),
                     world_size=nnodes)
    # publish only once the whole world has arrived — a failed barrier must
    # not leave a half-initialized bootstrap behind
    store.barrier("init_parallel_env", world_size=nnodes)
    _BOOTSTRAP["store"] = store


def get_bootstrap_store():
    """The job-wide TCPStore (None in single-process runs)."""
    return _BOOTSTRAP["store"]


def init_parallel_env(strategy=None) -> Optional[Group]:
    """distributed.init_parallel_env (parallel.py:943 analog). Builds the
    world group over all visible devices (ICI-connected on a TPU slice);
    multi-host jobs additionally bootstrap the PJRT distributed runtime and
    the TCPStore control plane from the launcher's env."""
    if _WORLD[0] is None:
        _maybe_init_multihost()
        n = len(jax.devices())
        mesh = ProcessMesh(np.arange(n), ["world"])
        if _mp():
            # rank == process (reference trainer semantics); the mesh still
            # spans every device for in-graph GSPMD use
            ranks = list(range(jax.process_count()))
        else:
            ranks = list(range(n))
        _WORLD[0] = Group(ranks, mesh, "world")
        if _mp() and os.environ.get("PADDLE_COLLECTIVE_WATCHDOG") == "1":
            # opt-in auto-arm (launcher propagates env to every rank):
            # desync diagnosis without touching user code
            from .watchdog import enable_collective_watchdog
            enable_collective_watchdog(timeout=float(os.environ.get(
                "PADDLE_COLLECTIVE_WATCHDOG_TIMEOUT", "300")))
    return _WORLD[0]


def is_initialized() -> bool:
    return _WORLD[0] is not None


def _world() -> Group:
    if _WORLD[0] is None:
        init_parallel_env()
    return _WORLD[0]


def get_world_size(group: Optional[Group] = None) -> int:
    return (group or _world()).nranks


def get_rank(group: Optional[Group] = None) -> int:
    if group is not None:
        return group.rank if _mp() else jax.process_index()
    return jax.process_index()


def new_group(ranks: Optional[List[int]] = None, backend=None,
              timeout=None) -> Group:
    """distributed.new_group (collective.py:180 analog)."""
    if ranks is None:
        # multi-process: rank space is processes, not devices
        ranks = list(range(jax.process_count() if _mp()
                           else len(jax.devices())))
    mesh = ProcessMesh(np.asarray(ranks), ["g"])
    return Group(ranks, mesh, "g")


def destroy_process_group(group=None):
    if group is None or group is _WORLD[0]:
        _WORLD[0] = None



_COLL_METRICS = [None]  # lazy (calls, bytes, seconds) families


def _coll_metrics():
    fams = _COLL_METRICS[0]
    if fams is None:
        from ..observability.metrics import get_registry
        reg = get_registry()
        fams = (
            reg.counter("collective_calls_total",
                        "collective invocations by op", labelnames=("op",)),
            reg.counter("collective_bytes_total",
                        "tensor payload bytes entering collectives by op",
                        labelnames=("op",)),
            reg.histogram("collective_seconds",
                          "collective wall time by op (host-side, includes "
                          "dispatch + any blocking)", labelnames=("op",)),
        )
        _COLL_METRICS[0] = fams
    return fams


def _watched(name):
    """Wrap a collective entry point with telemetry (per-op call/bytes
    counters + latency histogram, always on) and the desync watchdog
    (no-op — one attribute read — unless enable_collective_watchdog
    armed it)."""
    import functools
    import time as _time

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            calls, bytes_c, seconds = _coll_metrics()
            calls.labels(op=name).inc()
            t = next((a for a in args if hasattr(a, "shape")), None)
            nb = 0
            if t is not None:
                nb = getattr(getattr(t, "_data", t), "nbytes", 0)
                if nb:
                    bytes_c.labels(op=name).inc(int(nb))
            # per-mesh-axis twins, ONLY under an armed mesh.axis_scope:
            # single-process output stays byte-identical (the twin
            # families are never even created without a scope)
            from .mesh import current_axis_label
            axis = current_axis_label()
            if axis is not None:
                from ..observability.metrics import get_registry
                reg = get_registry()
                reg.counter("collective_axis_calls_total",
                            "collective invocations by op and mesh axis",
                            labelnames=("op", "axis")).labels(
                                op=name, axis=axis).inc()
                if nb:
                    reg.counter(
                        "collective_axis_bytes_total",
                        "tensor payload bytes entering collectives by op "
                        "and mesh axis",
                        labelnames=("op", "axis")).labels(
                            op=name, axis=axis).inc(int(nb))
            from ..observability import fleet as _fleet
            # fleet enter BEFORE the fault point: a kill_rank here leaves
            # the enter-without-exit signature in the victim's shard/ring
            tok = _fleet.on_collective_enter(name)
            from ..resilience.chaos import fault_point
            fault_point("collective.enter")  # chaos drills; no-op unarmed
            t0 = _time.perf_counter()
            try:
                from . import watchdog as _wd
                if _wd.get_watchdog() is None:
                    return fn(*args, **kwargs)
                with _wd.watch(name, t):
                    return fn(*args, **kwargs)
            finally:
                seconds.labels(op=name).observe(_time.perf_counter() - t0)
                _fleet.on_collective_exit(tok, name)
        return wrapper
    return deco


@_watched("barrier")
def barrier(group: Optional[Group] = None):
    g = group or _world()
    x = jnp.zeros((1,) if _mp() else (g.nranks,), jnp.int32)
    _stacked(lambda v: jax.lax.psum(v, g.axis_name), g, x,
             cache_key=("barrier",)).block_until_ready()


# -- stacked collective machinery -------------------------------------------

_STACKED_JIT_CACHE: dict = {}


def _stacked(body, group: Group, arr, out_sharded=True, cache_key=None):
    """Run `body` per-rank-shard over the group axis via shard_map.

    Single-controller: `arr` is rank-stacked [nranks, ...]; the stacked
    result comes back. Multi-process: `arr` is this process's LOCAL slot
    [...]; it is lifted to one row of the global array
    (make_array_from_process_local_data), the same body runs SPMD across
    processes, and the local row (or the replicated whole, for
    out_sharded=False) comes back.

    cache_key (hashable, identifying the body's semantics) lets repeat eager
    collectives reuse one jitted callable instead of re-wrapping a fresh
    lambda in jax.jit every call (which defeats jit's identity cache)."""
    mesh = group._collective_mesh()
    in_spec = P(group.axis_name)
    out_spec = P(group.axis_name) if out_sharded else P()
    if cache_key is not None:
        key = (mesh, group.axis_name, out_sharded, cache_key)
        fn = _STACKED_JIT_CACHE.get(key)
        if fn is None:
            fn = jax.jit(shard_map(body, mesh, (in_spec,), out_spec))
            _STACKED_JIT_CACHE[key] = fn
    else:
        fn = jax.jit(shard_map(body, mesh, (in_spec,), out_spec))
    sharding = NamedSharding(mesh, in_spec)
    if _mp():
        local = np.asarray(arr)[None]
        gshape = (group.nranks,) + tuple(local.shape[1:])
        garr = jax.make_array_from_process_local_data(sharding, local, gshape)
        out = fn(garr)
        if out_sharded:
            return jnp.asarray(out.addressable_data(0))[0]
        return jnp.asarray(out.addressable_data(0))
    if not isinstance(arr, jax.core.Tracer):
        arr = jax.device_put(arr, sharding)
    return fn(arr)


def _unwrap(t):
    return t._data if isinstance(t, Tensor) else jnp.asarray(t)


def _check_stacked(arr, group, name):
    if _mp():
        return  # process-local tensors; any shape is this rank's own
    if arr.shape[0] != group.nranks:
        raise ValueError(
            f"{name}: single-controller collectives take rank-stacked tensors "
            f"(dim0 == group size {group.nranks}); got shape {tuple(arr.shape)}")


@_watched("all_reduce")
def all_reduce(tensor, op=ReduceOp.SUM, group: Optional[Group] = None,
               sync_op=True):
    """Each rank slot receives the reduction over all slots
    (ProcessGroupNCCL::AllReduce analog, process_group_nccl.h:103)."""
    g = group or _world()
    arr = _unwrap(tensor)
    _check_stacked(arr, g, "all_reduce")
    red = {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
           ReduceOp.MIN: jax.lax.pmin}.get(op)

    if red is not None:
        body = lambda x: red(x, g.axis_name)
    elif op == ReduceOp.AVG:
        body = lambda x: jax.lax.pmean(x, g.axis_name)
    elif op == ReduceOp.PROD:
        # exact product (sign-safe): gather the shards, reduce locally
        body = lambda x: jnp.prod(jax.lax.all_gather(x, g.axis_name), axis=0)
    else:
        raise ValueError(f"unknown reduce op {op}")
    out = _stacked(body, g, arr, cache_key=("all_reduce", op))
    if isinstance(tensor, Tensor):
        tensor._set_data(out)
        return tensor
    return Tensor(out)


@_watched("all_gather")
def all_gather(tensor_list, tensor=None, group: Optional[Group] = None,
               sync_op=True):
    """paddle.distributed.all_gather: append every rank's slice."""
    g = group or _world()
    if tensor is None:
        tensor, tensor_list = tensor_list, None
    arr = _unwrap(tensor)
    _check_stacked(arr, g, "all_gather")
    out = _stacked(
        lambda x: jax.lax.all_gather(x, g.axis_name, axis=0, tiled=True),
        g, arr, out_sharded=False, cache_key=("all_gather",))
    slices = [Tensor(out[i]) for i in range(g.nranks)]
    if tensor_list is not None:
        tensor_list.extend(slices)
        return tensor_list
    return Tensor(out)


_OBJ_SEQ: dict = {}  # per-group sequence: only member ranks advance it


def _obj_store_and_seq(g: Group):
    import pickle  # noqa: F401  (callers use it; import checked here)
    store = get_bootstrap_store()
    if store is None:
        raise RuntimeError(
            "object collectives in a multi-process world need the TCPStore "
            "control plane — launch via paddle_tpu.distributed.launch / "
            "init_parallel_env with PADDLE_MASTER set")
    _OBJ_SEQ[g.id] = _OBJ_SEQ.get(g.id, 0) + 1
    return store, _OBJ_SEQ[g.id]


def _store_all_gather_object(obj, g: Group):
    """Object exchange over the bootstrap TCPStore control plane (the
    reference routes object collectives through tensor serialization +
    NCCL; host-side store exchange is the TPU-shaped equivalent — object
    payloads are control-plane, not ICI-bandwidth, traffic). Keys are
    deleted once the whole group has read them."""
    import pickle
    store, seq = _obj_store_and_seq(g)
    mykey = f"__obj/{g.id}/{seq}/{g.rank}"
    store.set(mykey, pickle.dumps(obj))
    out = []
    for r in range(g.nranks):
        out.append(pickle.loads(store.get(f"__obj/{g.id}/{seq}/{r}")))
    store.barrier(f"__obj/{g.id}/{seq}/done", world_size=g.nranks)
    store.delete_key(mykey)
    return out


def all_gather_object(object_list, obj, group=None):
    g = group or _world()
    if _mp():
        object_list.extend(_store_all_gather_object(obj, g))
        return object_list
    # single controller: every rank slot holds the same object
    object_list.extend([obj] * g.nranks)
    return object_list


@_watched("broadcast")
def broadcast(tensor, src: int = 0, group: Optional[Group] = None,
              sync_op=True):
    g = group or _world()
    arr = _unwrap(tensor)
    _check_stacked(arr, g, "broadcast")
    if src not in g.ranks:
        raise ValueError(f"broadcast: src rank {src} not in group {g.ranks}")
    src_idx = g.get_group_rank(src)

    # close over ints only — a closure over `arr` would pin the first call's
    # device buffer inside the jit cache for process lifetime
    per = 1 if _mp() else arr.shape[0] // g.nranks
    start = src_idx * per

    def body(x, _start=start, _per=per):
        full = jax.lax.all_gather(x, g.axis_name, axis=0, tiled=True)
        return jax.lax.dynamic_slice_in_dim(full, _start, _per, axis=0)

    out = _stacked(body, g, arr,
                   cache_key=("broadcast", src_idx, per))
    if _mp():
        out = out.reshape(arr.shape)
    if isinstance(tensor, Tensor):
        tensor._set_data(out)
        return tensor
    return Tensor(out)


@_watched("reduce")
def reduce(tensor, dst: int = 0, op=ReduceOp.SUM,
           group: Optional[Group] = None, sync_op=True):
    g = group or _world()
    arr = _unwrap(tensor)
    _check_stacked(arr, g, "reduce")
    if dst not in g.ranks:
        raise ValueError(f"reduce: dst rank {dst} not in group {g.ranks}")
    dst_idx = g.get_group_rank(dst)
    if _mp():
        # every member participates in the reduction; only dst keeps it
        summed = all_reduce(Tensor(jnp.asarray(arr)), op, g)
        out = summed._data if g.rank == dst_idx else jnp.asarray(arr)
    else:
        summed = all_reduce(Tensor(arr), op, g).numpy()
        result = np.array(arr)
        result[dst_idx] = summed[dst_idx]
        out = jnp.asarray(result)
    if isinstance(tensor, Tensor):
        tensor._set_data(out)
        return tensor
    return Tensor(out)


@_watched("reduce_scatter")
def reduce_scatter(tensor, tensor_or_tensor_list=None, op=ReduceOp.SUM,
                   group: Optional[Group] = None, sync_op=True):
    """Input stacked [n, n*m, ...]; each rank slot gets its reduced chunk
    [n, m, ...]."""
    g = group or _world()
    if tensor_or_tensor_list is None:
        src = tensor
        out_t = None
    else:
        out_t = tensor
        src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        arr = jnp.stack([_unwrap(t) for t in src], axis=1).reshape(
            (_unwrap(src[0]).shape[0], -1) + tuple(_unwrap(src[0]).shape[2:]))
    else:
        arr = _unwrap(src)
    _check_stacked(arr, g, "reduce_scatter")

    if op == ReduceOp.SUM:
        def body(x):
            return jax.lax.psum_scatter(x[0], g.axis_name,
                                        scatter_dimension=0, tiled=True)[None]
    elif op in (ReduceOp.MAX, ReduceOp.MIN, ReduceOp.AVG):
        red = {ReduceOp.MAX: jax.lax.pmax, ReduceOp.MIN: jax.lax.pmin,
               ReduceOp.AVG: jax.lax.pmean}[op]

        def body(x):
            reduced = red(x[0], g.axis_name)
            chunk = reduced.shape[0] // g.nranks
            idx = jax.lax.axis_index(g.axis_name)
            return jax.lax.dynamic_slice_in_dim(reduced, idx * chunk, chunk,
                                                axis=0)[None]
    else:
        raise ValueError(f"reduce_scatter: unsupported op {op}")

    out = _stacked(body, g, arr, cache_key=("reduce_scatter", op))
    if out_t is not None:
        out_t._set_data(out)
        return out_t
    return Tensor(out)


@_watched("scatter")
def scatter(tensor, tensor_list=None, src: int = 0,
            group: Optional[Group] = None, sync_op=True):
    g = group or _world()
    src_local = g.get_group_rank(src)
    if src_local < 0:
        raise ValueError(f"scatter: src rank {src} not in group {g.ranks}")
    if _mp():
        # tensor = this rank's output buffer; src contributes the real data,
        # everyone else an equal-shaped zero buffer (SPMD participation)
        out_arr = _unwrap(tensor)
        chunk = out_arr.shape[0]
        if g.rank == src_local:
            if tensor_list is None:
                raise ValueError("scatter: the src rank must pass tensor_list")
            contrib = jnp.concatenate([_unwrap(t) for t in tensor_list],
                                      axis=0)
        else:
            contrib = jnp.zeros((g.nranks * chunk,) + tuple(out_arr.shape[1:]),
                                out_arr.dtype)

        def body(x, _s=src_local, _c=chunk):
            full = jax.lax.all_gather(x, g.axis_name, axis=0, tiled=True)
            mine = jax.lax.dynamic_slice_in_dim(full, _s, 1, axis=0)[0]
            idx = jax.lax.axis_index(g.axis_name)
            return jax.lax.dynamic_slice_in_dim(mine, idx * _c, _c,
                                                axis=0)[None]

        out = _stacked(body, g, contrib,
                       cache_key=("scatter_mp", src_local, chunk))
        out = out.reshape(out_arr.shape)
        if isinstance(tensor, Tensor):
            tensor._set_data(out)
            return tensor
        return Tensor(out)
    if tensor_list is not None:
        data = jnp.stack([_unwrap(t)[src_local] for t in tensor_list], axis=0)
    else:
        arr = _unwrap(tensor)
        _check_stacked(arr, g, "scatter")
        chunks = jnp.split(arr[src_local], g.nranks, axis=0)
        data = jnp.stack(chunks, axis=0).reshape(
            (g.nranks,) + tuple(chunks[0].shape))
    if isinstance(tensor, Tensor):
        tensor._set_data(data.reshape(tensor._data.shape)
                         if data.size == tensor.size else data)
        return tensor
    return Tensor(data)


@_watched("alltoall")
def alltoall(in_tensor_list, out_tensor_list=None,
             group: Optional[Group] = None, sync_op=True):
    """all-to-all: out[i][j] = in[j][i] (EP's global_scatter backbone)."""
    g = group or _world()
    if _mp():
        # local input: n chunks (row j goes to rank j); local output: n
        # chunks (row i came from rank i)
        if isinstance(in_tensor_list, (list, tuple)):
            arr = jnp.stack([_unwrap(t) for t in in_tensor_list], axis=0)
        else:
            arr = _unwrap(in_tensor_list)
        if arr.shape[0] != g.nranks:
            raise ValueError(
                f"alltoall: expected {g.nranks} chunks, got {arr.shape[0]}")

        def body(x):
            return jax.lax.all_to_all(x[0], g.axis_name, split_axis=0,
                                      concat_axis=0, tiled=True)[None]

        out = _stacked(body, g, arr, cache_key=("alltoall_mp",))
        if out_tensor_list is not None:
            out_tensor_list.extend(Tensor(out[i]) for i in range(g.nranks))
            return out_tensor_list
        return Tensor(out)
    if isinstance(in_tensor_list, (list, tuple)):
        arr = jnp.stack([_unwrap(t) for t in in_tensor_list], axis=1)
        # arr: [n, n, ...] — [src, dst, ...]
    else:
        arr = _unwrap(in_tensor_list)
        _check_stacked(arr, g, "alltoall")
        arr = arr.reshape((g.nranks, g.nranks, -1) + tuple(arr.shape[2:]))

    out = _stacked(
        lambda x: jax.lax.all_to_all(x, g.axis_name, split_axis=1,
                                     concat_axis=0, tiled=True),
        g, arr, cache_key=("alltoall",))
    if out_tensor_list is not None:
        out_tensor_list.extend(Tensor(out[:, i]) for i in range(g.nranks))
        return out_tensor_list
    return Tensor(out)


def _p2p_exchange(g: Group, arr, src_idx: int, dst_idx: int):
    """Multi-process p2p over a TWO-device mesh spanning only the endpoints,
    so other group members need not participate (the reference's NCCL p2p
    creates a 2-rank communicator the same way,
    pp_utils/p2p_communication.py:52). Send on src and recv on dst must be
    called in matched order — that pairing IS the program."""
    if src_idx == dst_idx:
        return jnp.asarray(arr)
    by_proc = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, d)
    pair = (g.ranks[src_idx], g.ranks[dst_idx])
    mesh = jax.sharding.Mesh(
        np.array([by_proc[pair[0]], by_proc[pair[1]]], dtype=object),
        (g.axis_name,))
    key = (mesh, "p2p")
    fn = _STACKED_JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(shard_map(
            lambda x: jax.lax.ppermute(x, g.axis_name, [(0, 1)]),
            mesh, (P(g.axis_name),), P(g.axis_name)))
        _STACKED_JIT_CACHE[key] = fn
    sharding = NamedSharding(mesh, P(g.axis_name))
    local = np.asarray(arr)[None]
    garr = jax.make_array_from_process_local_data(
        sharding, local, (2,) + tuple(local.shape[1:]))
    out = fn(garr)
    return jnp.asarray(out.addressable_data(0))[0]


@_watched("send")
def send(tensor, dst: int = 0, group: Optional[Group] = None, sync_op=True):
    """Point-to-point send.

    Multi-process: a ppermute over the group mesh (the matching recv runs
    the same program on the dst rank). Single-controller: data is globally
    addressable, so p2p is a FIFO handoff; in-graph pipeline comm should use
    ppermute (see distributed.ppermute) instead. Matching is FIFO per group —
    ambiguous outstanding sends raise rather than mis-deliver."""
    g = group or _world()
    if dst not in g.ranks:
        raise ValueError(f"send: dst rank {dst} not in group {g.ranks}")
    if _mp():
        _p2p_exchange(g, _unwrap(tensor), g.rank, g.get_group_rank(dst))
        return
    _P2P_BUF.setdefault(g.id, []).append((dst, _unwrap(tensor)))


@_watched("recv")
def recv(tensor, src: int = 0, group: Optional[Group] = None, sync_op=True):
    g = group or _world()
    if src not in g.ranks:
        raise ValueError(f"recv: src rank {src} not in group {g.ranks}")
    if _mp():
        out = _p2p_exchange(g, _unwrap(tensor), g.get_group_rank(src), g.rank)
        tensor._set_data(out.reshape(tensor._data.shape))
        return tensor
    buf = _P2P_BUF.get(g.id, [])
    if not buf:
        raise RuntimeError("recv without matching send")
    if len(buf) > 1:
        raise RuntimeError(
            "ambiguous p2p matching: multiple outstanding sends in this group "
            "under the single-controller FIFO model; use in-graph ppermute "
            "for pipelined p2p schedules")
    _, data = buf.pop(0)
    tensor._set_data(jnp.asarray(data).reshape(tensor._data.shape))
    return tensor


_P2P_BUF: dict = {}

isend = send
irecv = recv


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    for op in p2p_op_list:
        op.op(op.tensor, op.peer, op.group)
    return []


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        tensor._data.block_until_ready()


# -- in-graph primitives (for shard_map'd custom parallel code) -------------

def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    return jax.lax.pmean(x, axis_name)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


@_watched("gather")
def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """paddle.distributed.gather: rank `dst` receives every slice (single
    controller: all_gather then keep; non-dst ranks get an empty list)."""
    g = group or _world()
    slices = all_gather([], tensor, group=g)  # returns the per-rank list
    if gather_list is not None:
        gather_list.extend(slices)
        return gather_list
    return slices


@_watched("alltoall_single")
def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """paddle.distributed.alltoall_single. Equal splits run in both modes;
    RAGGED splits (in/out_split_sizes) run in a real multi-process world
    (_ragged_alltoall_single: pad-to-global-max over the tiled all_to_all)
    — the single-controller rank-stacked convention cannot express
    per-rank sizes and raises."""
    g = group or _world()
    arr = _unwrap(in_tensor)
    n = g.nranks
    if in_split_sizes is not None or out_split_sizes is not None:
        if not _mp():
            raise NotImplementedError(
                "ragged alltoall_single needs a real multi-process world "
                "(per-rank tensor sizes differ; the single-controller "
                "rank-stacked convention cannot express them)")
        return _ragged_alltoall_single(arr, in_tensor, out_tensor,
                                       in_split_sizes, out_split_sizes, g)
    if _mp():
        if arr.shape[0] % n:
            raise ValueError(
                f"alltoall_single: dim0 {arr.shape[0]} not divisible by "
                f"group size {n}")
        chunks = arr.reshape((n, arr.shape[0] // n) + tuple(arr.shape[1:]))

        def body(x):
            return jax.lax.all_to_all(x[0], g.axis_name, split_axis=0,
                                      concat_axis=0, tiled=True)[None]

        out = _stacked(body, g, chunks, cache_key=("alltoall_single_mp",))
        result = Tensor(out.reshape(arr.shape))
        if out_tensor is not None:
            out_tensor._set_data(result._data)
            return out_tensor
        return result
    _check_stacked(arr, g, "alltoall_single")
    arr = arr.reshape((n, n, -1) + tuple(arr.shape[2:]))
    out = _stacked(
        lambda x: jax.lax.all_to_all(x, g.axis_name, split_axis=1,
                                     concat_axis=0, tiled=True),
        g, arr, cache_key=("alltoall_single",))
    result = Tensor(out.reshape(_unwrap(in_tensor).shape))
    if out_tensor is not None:
        out_tensor._set_data(result._data)
        return out_tensor
    return result


def _ragged_alltoall_single(arr, in_tensor, out_tensor, in_split_sizes,
                            out_split_sizes, g: Group):
    """Ragged splits (reference's DCN EP path): every rank pads its send
    chunks to the GLOBAL max split (one tiny pmax exchange), rides the same
    tiled all_to_all, then slices its receive sizes back out."""
    n = g.nranks
    if len(in_split_sizes) != n or len(out_split_sizes) != n:
        raise ValueError("split size lists must have one entry per rank")
    if sum(in_split_sizes) != arr.shape[0]:
        raise ValueError(
            f"in_split_sizes sum {sum(in_split_sizes)} != dim0 "
            f"{arr.shape[0]}")
    local_max = max(list(in_split_sizes) + list(out_split_sizes) + [1])
    m = int(_stacked(lambda x: jax.lax.pmax(x, g.axis_name), g,
                     jnp.asarray([local_max], jnp.int32),
                     cache_key=("ragged_a2a_max",))[0])
    tail = tuple(arr.shape[1:])
    chunks = []
    off = 0
    for size in in_split_sizes:
        c = arr[off:off + size]
        if size < m:
            c = jnp.concatenate(
                [c, jnp.zeros((m - size,) + tail, arr.dtype)], axis=0)
        chunks.append(c)
        off += size
    packed = jnp.stack(chunks, axis=0)  # [n, m, ...]

    def body(x):
        return jax.lax.all_to_all(x[0], g.axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)[None]

    out = _stacked(body, g, packed, cache_key=("ragged_a2a", m))
    rows = out.reshape((n, m) + tail)
    parts = [rows[i, :out_split_sizes[i]] for i in range(n)]
    result = Tensor(jnp.concatenate(parts, axis=0) if parts
                    else jnp.zeros((0,) + tail, arr.dtype))
    if out_tensor is not None:
        out_tensor._set_data(result._data)
        return out_tensor
    return result


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Single controller: rank i's slot is in_object_list[i] (the src list
    is visible to all)."""
    g = group or _world()
    if _mp():
        import pickle
        src_idx = g.get_group_rank(src)
        if src_idx < 0:
            raise ValueError(
                f"scatter_object_list: src rank {src} not in group {g.ranks}")
        store, seq = _obj_store_and_seq(g)
        key = f"__objsc/{g.id}/{seq}"
        if g.rank == src_idx:
            if in_object_list is None or len(in_object_list) != g.nranks:
                raise ValueError(
                    "in_object_list must have one entry per rank")
            store.set(key, pickle.dumps(list(in_object_list)))
        out_object_list.append(pickle.loads(store.get(key))[g.rank])
        store.barrier(f"{key}/done", world_size=g.nranks)
        if g.rank == src_idx:
            store.delete_key(key)
        return out_object_list
    if in_object_list is None:
        raise ValueError("in_object_list required on the src rank")
    if len(in_object_list) != g.nranks:
        raise ValueError("in_object_list must have one entry per rank")
    out_object_list.append(in_object_list[g.rank_in_group])
    return out_object_list


def broadcast_object_list(object_list, src=0, group=None):
    """Multi-process: src's list replaces everyone's (src sets the store key
    once; the others fetch it). Single controller: identity."""
    g = group or _world()
    if _mp():
        import pickle
        src_idx = g.get_group_rank(src)
        if src_idx < 0:
            raise ValueError(
                f"broadcast_object_list: src rank {src} not in group "
                f"{g.ranks}")
        store, seq = _obj_store_and_seq(g)
        key = f"__objbc/{g.id}/{seq}"
        if g.rank == src_idx:
            store.set(key, pickle.dumps(list(object_list)))
        object_list[:] = pickle.loads(store.get(key))
        store.barrier(f"{key}/done", world_size=g.nranks)
        if g.rank == src_idx:
            store.delete_key(key)
    return object_list


class ReduceType:
    """auto-parallel reduce type enum (ref ReduceType for Partial)."""
    kRedSum = 0
    kRedMax = 1
    kRedMin = 2
    kRedProd = 3
    kRedAvg = 4


class ParallelMode:
    """fleet/base/topology.py:33 ParallelMode enum."""
    DATA_PARALLEL = 0
    TENSOR_PARALLEL = 1
    PIPELINE_PARALLEL = 2
    SHARDING_PARALLEL = 3
    SEGMENT_PARALLEL = 4


def is_available():
    """paddle.distributed.is_available."""
    return True


def get_backend(group=None):
    """The communication backend name (XLA collectives over ICI/DCN)."""
    return "XCCL"


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """Host-side (gloo-analog) bootstrap: the TCPStore fills gloo's role
    (SURVEY §2.9 'host barriers via TCPStore')."""
    from ..core.native import TCPStore
    host, port = server_endpoint.rsplit(":", 1)
    is_master = rank_id == 0
    store = TCPStore(host, int(port), is_master=is_master,
                     world_size=rank_num)
    global _GLOO_STORE
    _GLOO_STORE = (store, rank_id, rank_num)


_GLOO_STORE = None
_GLOO_BARRIER_SEQ = [0]


def gloo_barrier():
    if _GLOO_STORE is None:
        raise RuntimeError("call gloo_init_parallel_env first")
    store, rank, n = _GLOO_STORE
    # per-call key: the store's done-flag is sticky, so a reused key would
    # let later barriers pass through without synchronizing
    _GLOO_BARRIER_SEQ[0] += 1
    store.barrier(f"gloo_barrier_{_GLOO_BARRIER_SEQ[0]}", n)


def gloo_release():
    global _GLOO_STORE
    _GLOO_STORE = None
