"""Communication facade — TPU-native analog of ``deepspeed.comm``.

The reference wraps torch.distributed with a dispatcher that adds op-level
profiling and backend selection (``deepspeed/comm/comm.py:112-760``). On TPU
there is no NCCL process-group object: collectives are XLA ops over named mesh
axes, compiled onto ICI/DCN. This module keeps the parts of the facade that
still make sense:

* ``init_distributed()`` — multi-host bring-up (``jax.distributed.initialize``)
  with env discovery, the analog of comm/comm.py:599.
* rank/world-size accessors (process-level and device-level).
* in-jit collective dispatchers (``all_reduce``/``all_gather``/…) usable inside
  ``shard_map`` bodies, dispatching to ``jax.lax`` primitives.
* ``comms_logger`` (the ``comms_logger`` config block; analog of the
  @timed_op decorator, comm/comm.py:112): what each watched program
  moves a step, read off its COMPILED text (``telemetry/compile_watch.py``
  ``movement_table``), so the collectives the partitioner inserted and the
  offload stream's transfers are in it; timing comes from XLA profiles,
  since ops inside jit cannot be individually wall-clocked.
* host-level helpers (``barrier``, ``broadcast_obj``) built on
  ``jax.experimental.multihost_utils``.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deepspeed_tpu.utils.logging import logger

_INITIALIZED = False

# Reduce ops — reference exposes a ReduceOp enum (deepspeed/comm/comm.py).
SUM = "sum"
MAX = "max"
MIN = "min"
AVG = "avg"
PROD = "prod"


class CommsLogger:
    """What the watched programs move: per program and kind of transfer
    (``host_to_device`` / ``device_to_host``, ``all-gather``,
    ``reduce-scatter``, ``all-reduce``, ``all-to-all``, ...) the transfers
    and bytes of ONE execution, from the compile watch's movement table.

    Analog of deepspeed/utils/comms_logging.py. The reference counts calls
    of its own dispatcher; here the exchanges of a ZeRO or tensor-parallel
    step are written by XLA's partitioner and no dispatcher sees them, so
    the compiled program is what is read. Parsed when asked (``summary``,
    ``log_all``), never while a program is set up.
    """

    def __init__(self):
        self.enabled = False
        self.verbose = False

    def configure(self, enabled=False, verbose=False, prof_all=True, debug=False):
        self.enabled = enabled
        self.verbose = verbose

    def summary(self) -> dict:
        """``{program: {kind: {"calls", "bytes"}}}`` a step, for every
        watched program that moves anything."""
        from deepspeed_tpu.telemetry import compile_watch
        out = {}
        for program in compile_watch.watched_programs():
            moved = compile_watch.movement_per_step(program)
            if moved:
                out[program] = moved
        return out

    def log_all(self):
        from deepspeed_tpu.telemetry.compile_watch import movement_per_step
        for program, kinds in sorted(self.summary().items()):
            for kind, rec in sorted(kinds.items()):
                logger.info(f"comm: {program}: {kind}: {rec['calls']} "
                            f"transfers, {rec['bytes']:.0f} bytes a step")
            if self.verbose:
                for (kind, pass_, scope), rec in sorted(
                        movement_per_step(program, detail=True).items(),
                        key=str):
                    logger.info(f"comm: {program}: {kind} | pass {pass_} | "
                                f"scope {scope}: {rec['calls']} transfers, "
                                f"{rec['bytes']:.0f} bytes a step")


comms_logger = CommsLogger()


def configure(deepspeed_config=None, enabled=None, verbose=None, **kwargs):
    if deepspeed_config is not None and getattr(deepspeed_config, "comms_logger", None):
        cl = deepspeed_config.comms_logger
        comms_logger.configure(enabled=cl.enabled, verbose=cl.verbose)
    elif enabled is not None:
        comms_logger.configure(enabled=enabled, verbose=bool(verbose))


# ---------------------------------------------------------------------------
# Initialization (reference: init_distributed, comm/comm.py:599)
# ---------------------------------------------------------------------------

def in_aml() -> bool:
    """AzureML job environment (reference comm.py:708)."""
    return "AZUREML_EXPERIMENT_ID" in os.environ


def in_aws_sm() -> bool:
    """AWS SageMaker job environment (reference comm.py:713)."""
    return os.environ.get("SM_TRAINING_ENV") is not None or \
        "SM_CURRENT_HOST" in os.environ


def in_dlts() -> bool:
    """DLTS cluster environment (reference comm.py:718)."""
    return "DLTS_JOB_ID" in os.environ


def mpi_discovery(coordinator_port: int = 29500,
                  require_addr: bool = True):
    """Derive (coordinator_address, num_processes, process_id) from an
    MPI launcher's environment — the analog of the reference's
    ``mpi_discovery`` (comm.py:664), which uses mpi4py + socket exchange
    to fill MASTER_ADDR/RANK/WORLD_SIZE. Under ``mpirun`` OpenMPI exports
    size/rank without an mpi4py dependency; the coordinator host comes
    from DS_COORDINATOR_ADDR, or the AzureML / SageMaker master-node
    variables when running there (reference in_aml/in_aws_sm patching,
    comm.py:708-760)."""
    env = os.environ

    def master_host():
        addr = env.get("DS_COORDINATOR_ADDR")
        if addr is None and in_aml():
            addr = env.get("AZ_BATCH_MASTER_NODE",
                           env.get("AZ_BATCHAI_MPI_MASTER_NODE"))
            addr = addr.split(":")[0] if addr else None
        if addr is None:
            hosts = sorted(json.loads(env.get("SM_HOSTS", "[]")))
            if hosts:
                addr = hosts[0]
        return addr

    if "OMPI_COMM_WORLD_SIZE" in env:
        size = int(env["OMPI_COMM_WORLD_SIZE"])
        rank = int(env["OMPI_COMM_WORLD_RANK"])
        addr = master_host()
        if addr is None and size > 1 and require_addr:
            raise RuntimeError(
                "mpi_discovery: set DS_COORDINATOR_ADDR to the rank-0 "
                "host (OpenMPI exports no hostlist)")
        return (f"{addr}:{coordinator_port}" if addr else None, size, rank)
    if in_aws_sm():
        hosts = sorted(json.loads(env.get("SM_HOSTS", "[]")))
        cur = env.get("SM_CURRENT_HOST")
        if hosts and cur in hosts:
            return (f"{hosts[0]}:{coordinator_port}", len(hosts),
                    hosts.index(cur))
    return None, None, None


def init_distributed(dist_backend: str = "xla",
                     auto_mpi_discovery: bool = True,
                     coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     **kwargs) -> None:
    """Bring up multi-host JAX if the environment calls for it.

    Single-host runs (or driver-simulated multi-device CPU runs) need no
    rendezvous — jax sees all local devices already. Multi-host TPU pods use
    ``jax.distributed.initialize``, which discovers coordinator/process-count
    from TPU metadata or the env vars below (the analog of the reference's
    MASTER_ADDR/RANK/WORLD_SIZE discovery, comm/comm.py:664-760), with
    MPI / AzureML / SageMaker env discovery as the fallback
    (``mpi_discovery``; reference :664, :708, :713).
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    # DS_* names take precedence; COORDINATOR_ADDRESS/NUM_PROCESSES/
    # PROCESS_ID are what launcher/launch.py exports (build_env) — the
    # launcher → init_distributed chain rendezvouses through them.
    coordinator_address = (coordinator_address or
                           os.environ.get("DS_COORDINATOR_ADDR") or
                           os.environ.get("COORDINATOR_ADDRESS"))
    if num_processes is None:
        for var in ("DS_NUM_PROCESSES", "NUM_PROCESSES"):
            if var in os.environ:
                num_processes = int(os.environ[var])
                break
    if process_id is None:
        for var in ("DS_PROCESS_ID", "PROCESS_ID"):
            if var in os.environ:
                process_id = int(os.environ[var])
                break
    if auto_mpi_discovery and num_processes is None and \
            ("OMPI_COMM_WORLD_SIZE" in os.environ or in_aws_sm()):
        # an explicitly-supplied coordinator waives the discovery's
        # address requirement — we only need size/rank from it then
        addr, size, rank = mpi_discovery(
            require_addr=coordinator_address is None)
        if size is not None and size > 1:
            coordinator_address = coordinator_address or addr
            num_processes, process_id = size, rank
            logger.info(f"mpi discovery: process {rank}/{size} "
                        f"coordinator={coordinator_address}")
    # NUM_PROCESSES=1 (launcher single-proc run) needs no rendezvous even
    # though the launcher always exports a coordinator address.
    multi_host = (num_processes is not None and num_processes > 1) or \
                 (num_processes is None and coordinator_address is not None)
    if multi_host:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
        logger.info(f"jax.distributed initialized: process {jax.process_index()}"
                    f"/{jax.process_count()}")
    _INITIALIZED = True


def is_initialized() -> bool:
    return _INITIALIZED


def get_rank() -> int:
    """Process rank (host rank on a pod)."""
    return jax.process_index()


def get_world_size() -> int:
    """Process count. Device-level parallelism lives in the mesh, not here."""
    return jax.process_count()


def get_local_rank() -> int:
    return int(os.environ.get("DS_LOCAL_RANK", 0))


def get_device_count() -> int:
    return jax.device_count()


# ---------------------------------------------------------------------------
# In-jit collectives over mesh axis names (usable inside shard_map).
# Dispatch table analog: deepspeed/comm/comm.py:224-537.
# ---------------------------------------------------------------------------

def all_reduce(x, op: str = SUM, axis_name: str = "data"):
    if op == SUM:
        return lax.psum(x, axis_name)
    if op == AVG:
        return lax.pmean(x, axis_name)
    if op == MAX:
        return lax.pmax(x, axis_name)
    if op == MIN:
        return lax.pmin(x, axis_name)
    raise ValueError(f"unsupported reduce op: {op}")


def all_gather(x, axis_name: str = "data", axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name: str = "data", axis: int = 0, tiled: bool = True):
    """Sum-reduce then scatter along ``axis`` — analog of
    reduce_scatter_coalesced (runtime/comm/coalesced_collectives.py:30);
    bucketing/coalescing is XLA's job."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=tiled)


def all_to_all(x, axis_name: str = "expert", split_axis: int = 0,
               concat_axis: int = 0, tiled: bool = True):
    """MoE dispatch/combine exchange (reference: _AllToAll autograd fn,
    moe/sharded_moe.py:89)."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=tiled)


def broadcast(x, src_index: int = 0, axis_name: str = "data"):
    """Broadcast from one index of the named axis to all (reference:
    comm/comm.py broadcast; engine._broadcast_model engine.py:1087)."""
    # one ring rotation: every member receives from the previous member;
    # after |axis| applications of `select src's value` semantics, a single
    # all_gather-free way to do this is to gather ONLY the src shard.
    # all_gather + static index lowers to a collective-broadcast on TPU
    # (XLA recognizes the single-slice use), unlike the old masked psum
    # which paid a full multiply+allreduce per call.
    gathered = lax.all_gather(x, axis_name)  # [axis, ...]
    return gathered[src_index]


def ppermute(x, perm, axis_name: str = "pipe"):
    """Neighbor exchange for pipeline parallelism (reference: pipe/p2p.py)."""
    return lax.ppermute(x, axis_name, perm)


def axis_index(axis_name: str):
    return lax.axis_index(axis_name)


def reduce(x, dst_index: int = 0, op: str = SUM, axis_name: str = "data"):
    """Reduce to one index of the axis (reference comm.py:492). SPMD has
    no one-sided result: ``dst_index`` receives the reduction, every
    other index keeps its input unchanged (the reference's in-place
    semantics on non-dst ranks)."""
    red = all_reduce(x, op=op, axis_name=axis_name)
    here = lax.axis_index(axis_name) == dst_index
    return jnp.where(here, red, x)


def gather(x, dst_index: int = 0, axis_name: str = "data", axis: int = 0):
    """Gather onto one index (reference comm.py:428): ``dst_index`` gets
    the concatenation along ``axis``; others get zeros of that shape
    (fixed SPMD shapes — the reference's non-dst ranks get nothing)."""
    gathered = lax.all_gather(x, axis_name, axis=axis, tiled=True)
    here = lax.axis_index(axis_name) == dst_index
    return jnp.where(here, gathered, jnp.zeros_like(gathered))


def scatter(x, src_index: int = 0, axis_name: str = "data", axis: int = 0):
    """Each index receives its chunk of ``src_index``'s array along
    ``axis`` (reference comm.py:445)."""
    n = lax.axis_size(axis_name)
    if x.shape[axis] % n:
        raise ValueError(f"scatter: dim {axis} size {x.shape[axis]} not "
                         f"divisible by axis size {n}")
    src = broadcast(x, src_index=src_index, axis_name=axis_name)
    chunk = x.shape[axis] // n
    return lax.dynamic_slice_in_dim(
        src, lax.axis_index(axis_name) * chunk, chunk, axis)


def send_recv(x, pairs, axis_name: str = "pipe"):
    """Point-to-point transfer expressed as a permutation: ``pairs`` is
    [(src, dst), ...]; indices not receiving get zeros. The analog of the
    reference's send/recv/isend/irecv (comm.py:380-427) — under SPMD both
    sides run one program, so the pair IS the primitive; the pipeline
    engine's p2p rides this (pipe/p2p.py analog)."""
    return ppermute(x, pairs, axis_name=axis_name)


def all_to_all_single(x, axis_name: str = "expert", split_axis: int = 0,
                      concat_axis: int = 0):
    """Alias of :func:`all_to_all` (reference all_to_all_single,
    comm.py:361 — the single-tensor form is the only one here; list
    batching is XLA's concern)."""
    return all_to_all(x, axis_name=axis_name, split_axis=split_axis,
                      concat_axis=concat_axis)


# ---------------------------------------------------------------------------
# Host-level (outside-jit) helpers.
# ---------------------------------------------------------------------------

def barrier() -> None:
    """Cross-process sync point (reference: dist.barrier)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("deepspeed_tpu_barrier")


def monitored_barrier(timeout=None) -> None:
    """Barrier that logs who it is waiting on (reference
    monitored_barrier, comm.py:473). XLA's sync has no per-rank
    reporting; the logging bracket still localizes a hang to this call
    site in each process's log."""
    logger.info(f"monitored_barrier: process {get_rank()}"
                f"/{get_world_size()} entering")
    barrier()
    logger.info(f"monitored_barrier: process {get_rank()} passed")


def broadcast_obj(obj: Any, root: int = 0) -> Any:
    """Broadcast a host python object from process 0 (used for checkpoint
    tag validation — engine.py:3043). Strings travel as fixed-width byte
    arrays (multihost broadcast requires identical shapes everywhere)."""
    if jax.process_count() == 1:
        return obj
    import numpy as np
    from jax.experimental import multihost_utils
    if isinstance(obj, str):
        buf = np.zeros(256, np.uint8)
        raw = obj.encode()[:256]
        buf[:len(raw)] = np.frombuffer(raw, np.uint8)
        out = np.asarray(multihost_utils.broadcast_one_to_all(buf))
        return bytes(out[out != 0]).decode(errors="replace")
    return multihost_utils.broadcast_one_to_all(obj)


def log_summary():
    comms_logger.log_all()


# ---------------------------------------------------------------------------
# reference-name compat shims (deepspeed/comm/comm.py public surface).
# Groups ARE mesh axes here: anywhere the reference takes a ProcessGroup,
# these take (or return) axis names usable as ``axis_name=`` in the
# collective dispatchers above.
# ---------------------------------------------------------------------------

def is_available() -> bool:
    """torch.distributed.is_available analog — XLA collectives are always
    compiled in."""
    return True


def get_world_group():
    """The 'world' group = every axis of the global mesh (usable directly
    as ``axis_name=`` in the dispatchers; reference comm.py
    get_world_group)."""
    from deepspeed_tpu.comm.mesh import get_global_mesh
    return tuple(get_global_mesh().axis_names)


def get_global_rank(group=None, group_rank: int = 0) -> int:
    """Translate a group-relative rank to a global rank. Identity for the
    world group; sub-axis translation needs the caller's mesh coordinates
    and has no single answer — refuse loudly there."""
    world = set(get_world_group())
    if group is None or set(group if isinstance(group, (tuple, list))
                            else (group,)) == world:
        return group_rank
    raise NotImplementedError(
        "get_global_rank for sub-axis groups: ranks are mesh coordinates "
        "here — compute them from Mesh.devices / parallel.topology instead")


def new_group(ranks=None):
    """Process groups are STATIC mesh axes under XLA SPMD — collectives
    take ``axis_name=``; slicing devices dynamically the NCCL way has no
    compiled analog (SURVEY §7.1)."""
    raise NotImplementedError(
        "new_group: define parallel groups as mesh axes "
        "(comm.mesh.MeshConfig) and pass axis_name= to the collectives; "
        "arbitrary rank subsets do not exist under compiled SPMD")


def has_allgather_base() -> bool:
    return True


def has_reduce_scatter_base() -> bool:
    return True


def all_gather_base(x, axis_name: str = "data", **kw):
    """_all_gather_base/allgather_fn analog (flat-tensor all-gather);
    XLA has no separate flat path — same dispatcher."""
    return all_gather(x, axis_name=axis_name)


allgather_fn = all_gather_base


def reduce_scatter_base(x, axis_name: str = "data", **kw):
    return reduce_scatter(x, axis_name=axis_name)


reduce_scatter_fn = reduce_scatter_base


def send(*a, **k):
    raise NotImplementedError(
        "host-level p2p send/recv has no compiled-SPMD analog; use "
        "send_recv (ppermute ring) inside jit, or jax.device_put for "
        "host-driven handoffs")


recv = isend = irecv = send


def set_backend(backend=None) -> None:
    """Single backend (XLA) — accepted and ignored for script compat."""
    logger.warning("set_backend: XLA is the only backend; ignored")


def init_deepspeed_backend(*a, **k) -> None:
    """Reference-internal init hook; init_distributed is the real entry."""
    init_distributed()


def destroy_process_group(group=None) -> None:
    """Tear down the multi-host runtime (torch destroy_process_group
    analog)."""
    global _INITIALIZED
    if jax.process_count() > 1:
        jax.distributed.shutdown()
    _INITIALIZED = False
