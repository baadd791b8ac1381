"""Core runtime: initialization, topology, process sets, global state.

Reference parity: this module rebuilds the capability surface of
``horovod/common/operations.cc`` (init/shutdown/rank/size C exports),
``horovod/common/global_state.h`` (HorovodGlobalState) and
``horovod/common/process_set.cc`` (ProcessSet / ProcessSetTable) — see
SURVEY.md §2.1/§3.1 — redesigned for the TPU SPMD model:

* The reference runs **one process per accelerator**; rank == process.  On
  TPU one Python process drives many chips through XLA, so we map Horovod's
  "worker" onto a **chip**: ``size()`` is the number of chips participating
  in collectives (``jax.device_count()``), ``local_size()`` the chips owned
  by this process.  ``rank()`` is the global index of this process's lead
  chip, which preserves the two idioms user scripts rely on:
  ``hvd.rank() == 0`` gates checkpointing exactly on the coordinator
  process, and rank-dependent data sharding maps to per-chip shards.
* The reference's MPI/Gloo rendezvous becomes ``jax.distributed.initialize``
  against the coordination service (over DCN); the background negotiation
  thread lives in ``horovod_tpu.ops.engine``.
* Process sets (subsets of workers with their own communicators) become
  sub-``Mesh``es over device subsets; XLA emits collectives only over the
  sub-mesh's ICI/DCN links.
"""

from __future__ import annotations

import atexit
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.extend.backend
import numpy as np

from . import metrics as _metrics
from . import tracing as _tracing
from .config import Config
from .exceptions import NotInitializedError

logger = logging.getLogger("horovod_tpu")

# Reduction op enums, mirroring the reference's hvd.Sum/Average/Adasum/Min/Max
# (horovod/common/common.h ReduceOp + horovod/torch/mpi_ops.py).
class ReduceOp:
    AVERAGE = "average"
    SUM = "sum"
    ADASUM = "adasum"
    MIN = "min"
    MAX = "max"
    PRODUCT = "product"
    BAND = "band"
    BOR = "bor"
    BXOR = "bxor"


Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT


class ProcessSet:
    """A subset of workers (chips) with its own communicator (sub-mesh).

    Reference parity: ``horovod/common/process_set.cc`` — each ProcessSet had
    its own controller + tensor queue over an MPI sub-communicator.  Here a
    process set owns a 1-D ``jax.sharding.Mesh`` over the selected chips;
    eager collectives over the set are compiled against that mesh, and the
    engine keeps a separate pending-queue per set.

    ``ranks`` are *global worker (chip) indices* into ``hvd.size()``.
    """

    def __init__(self, ranks: Optional[Sequence[int]] = None):
        self.ranks: Optional[List[int]] = (
            sorted(int(r) for r in ranks) if ranks is not None else None)
        self.process_set_id: Optional[int] = None
        self._mesh: Optional[jax.sharding.Mesh] = None
        self._axis: str = "workers"
        self._spans: Optional[bool] = None

    # -- queries -------------------------------------------------------------
    def initialized(self) -> bool:
        return self.process_set_id is not None

    def size(self) -> int:
        self._check()
        return len(self.ranks)

    def included(self) -> bool:
        self._check()
        return _state().lead_worker_rank in self.ranks

    def rank(self) -> int:
        """Rank of this process's lead chip within the set (-1 if excluded)."""
        self._check()
        lead = _state().lead_worker_rank
        return self.ranks.index(lead) if lead in self.ranks else -1

    @property
    def mesh(self) -> jax.sharding.Mesh:
        self._check()
        return self._mesh

    @property
    def axis(self) -> str:
        return self._axis

    @property
    def spans_processes(self) -> bool:
        """True when the set's mesh includes other processes' devices
        (constant per set; computed once — hot-path queried)."""
        if self._spans is None:
            self._check()
            me = jax.process_index()
            self._spans = any(d.process_index != me
                              for d in self._mesh.devices.flat)
        return self._spans

    def hier_shape(self) -> Optional[tuple]:
        """(n_groups, group_size) for hierarchical collectives, or None.

        Reference: NCCLHierarchicalAllreduce's intra-node/inter-node split
        (SURVEY §2.1/§5.8) — on TPU the analog is ICI within a host's
        chips vs DCN across hosts.  Valid when the set's workers group by
        process contiguously with uniform size (TPU slices are).  Cached
        (hot-path queried per dispatch); tests may force a factorization
        by assigning ``_hier_shape``.
        """
        if getattr(self, "_hier_shape", None) is not None:
            return self._hier_shape
        cached = getattr(self, "_hier_cached", False)
        if cached is not False:
            return cached
        self._check()
        self._hier_cached = self._compute_hier_shape()
        return self._hier_cached

    def _compute_hier_shape(self) -> Optional[tuple]:
        procs = [d.process_index for d in self._mesh.devices.flat]
        n = len(procs)
        n_groups = len(set(procs))
        if n_groups <= 1 or n % n_groups:
            return None
        group = n // n_groups
        # contiguous process-major grouping required for the 2-D reshape
        for g in range(n_groups):
            if len({procs[g * group + i] for i in range(group)}) != 1:
                return None
        return (n_groups, group)

    def _check(self):
        if not self.initialized():
            raise NotInitializedError("ProcessSet")

    def _materialize(self, set_id: int, all_devices, axis: str):
        self.process_set_id = set_id
        self._axis = axis
        if self.ranks is None:
            self.ranks = list(range(len(all_devices)))
        if any(r < 0 or r >= len(all_devices) for r in self.ranks):
            raise ValueError(
                f"process set ranks {self.ranks} out of range for "
                f"{len(all_devices)} workers")
        devs = np.array([all_devices[r] for r in self.ranks])
        self._mesh = jax.sharding.Mesh(devs, (axis,))

    def __repr__(self):
        return (f"ProcessSet(id={self.process_set_id}, ranks={self.ranks})")


class ProcessSetTable:
    """Registry of process sets (reference: ProcessSetTable, process_set.cc)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._table: Dict[int, ProcessSet] = {}
        self._next_id = 0

    def register(self, ps: ProcessSet, all_devices, axis: str) -> int:
        with self._lock:
            # Duplicate rank-lists map to the existing set, as in the
            # reference's AddProcessSet.
            for existing in self._table.values():
                if existing.ranks == (ps.ranks if ps.ranks is not None
                                      else list(range(len(all_devices)))):
                    raise ValueError(
                        f"A process set with ranks {existing.ranks} already "
                        f"exists (id={existing.process_set_id})")
            set_id = self._next_id
            self._next_id += 1
            ps._materialize(set_id, all_devices, axis)
            self._table[set_id] = ps
            return set_id

    def remove(self, set_id: int):
        with self._lock:
            if set_id == 0:
                raise ValueError("cannot remove the global process set")
            if set_id not in self._table:
                raise ValueError(f"no process set with id {set_id}")
            ps = self._table.pop(set_id)
            ps.process_set_id = None

    def get(self, set_id: int) -> ProcessSet:
        with self._lock:
            return self._table[set_id]

    def ids(self) -> List[int]:
        with self._lock:
            return sorted(self._table)

    def clear(self):
        with self._lock:
            for ps in self._table.values():
                ps.process_set_id = None
            self._table.clear()
            self._next_id = 0


class _RuntimeState:
    """Singleton global state (reference: HorovodGlobalState, global_state.h)."""

    def __init__(self):
        self.initialized = False
        self.config: Optional[Config] = None
        self.devices: List = []
        self.global_mesh: Optional[jax.sharding.Mesh] = None
        self.process_set_table = ProcessSetTable()
        self.global_process_set: Optional[ProcessSet] = None
        self.lead_worker_rank: int = 0
        self.engine = None          # ops.engine.CollectiveEngine
        self.timeline = None        # timeline.Timeline
        self.stall_inspector = None  # stall.StallInspector
        self.autotuner = None       # autotune.ParameterManager
        self.shutdown_hooks: List = []
        self.owns_jax_distributed = False
        self._init_lock = threading.Lock()


_STATE = _RuntimeState()
_INIT_GENERATION = 0  # survives shutdown(); processes re-init in lockstep


def _state() -> _RuntimeState:
    return _STATE


def _require_init() -> _RuntimeState:
    if not _STATE.initialized:
        raise NotInitializedError()
    return _STATE


_m_compile_cache = _metrics.counter(
    "hvd_compile_cache_total",
    "Programs looked up in jax's persistent compilation cache",
    labels=("result",))

#: jax.monitoring duration events that become ``compile`` spans, by the
#: ``stage`` each span carries.
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_compile_listeners_installed = False


def _on_compile_duration(event, duration, fun_name="", **_):
    """One closed ``compile`` span per function jax traces, lowers or
    compiles (``backend`` is the compile, or the load from the persistent
    cache), named by the function: which one recompiled, and when.
    Functions traced inside another's trace (every ``jnp`` helper: a
    ResNet-50 step has thousands) lie inside that one's span and get
    none of their own."""
    stage = _COMPILE_STAGES.get(event)
    if stage is not None and _tracing.ACTIVE:
        if stage == "trace" and not jax.core.trace_ctx.is_top_level():
            return
        t1 = _tracing.now()
        _tracing.span("compile", fun_name, t1 - duration, t1, round=-1,
                      stage=stage)


def _on_compile_event(event, **_):
    result = _CACHE_RESULTS.get(event)
    if result is not None and _metrics.ACTIVE:
        _m_compile_cache.inc(result=result)


def use_compile_cache() -> str:
    """Keep jax's persistent compilation cache in a directory that does
    not move, and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``,
    derived from this package's location: the directory is part of what
    a cached program is found by, so a per-run path never hits.  Takes
    effect for every compile that follows the call, and so do the
    ``compile`` spans and ``hvd_compile_cache_total``, which the first
    call hooks onto ``jax.monitoring``.
    """
    global _compile_listeners_installed
    if not _compile_listeners_installed:
        _compile_listeners_installed = True
        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_compile_event)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _rendezvous() -> Config:
    """``init.rendezvous``: the configuration from the environment, the
    elastic driver's assignment, and ``jax.distributed.initialize`` where
    the job has more than one process."""
    cfg = Config.from_env()
    _setup_logging(cfg)

    _STATE.config = cfg

    # Elastic rendezvous retry loop: a worker blocked in a stale
    # epoch's coordination-service barrier (its peers died before
    # joining) must not hang forever — each attempt re-fetches the
    # driver's CURRENT assignment (reference: elastic rendezvous
    # re-query, §3.5), so when the driver bumps the epoch mid-wait
    # the next attempt rendezvouses into the new world.
    start_deadline = time.monotonic() + float(os.environ.get(
        "HOROVOD_ELASTIC_START_TIMEOUT", "600"))
    attempt = 0
    while True:
        if cfg.elastic:
            from .elastic import worker as elastic_worker
            # first attempt wants an epoch newer than the last one this
            # worker saw (request_reform guarantees the bump); retries
            # accept the latest published epoch, whatever it is
            min_ep = (None if attempt == 0
                      else max(elastic_worker._last_epoch, 0))
            asg = elastic_worker.fetch_assignment(min_epoch=min_ep)
            if asg is not None:
                cfg.rank = asg["rank"]
                cfg.size = asg["size"]
                cfg.local_rank = asg["local_rank"]
                cfg.local_size = asg["local_size"]
                cfg.cross_rank = asg["cross_rank"]
                cfg.cross_size = asg["cross_size"]
                cfg.rendezvous_addr = asg["coordinator_addr"]
                cfg.rendezvous_port = asg["coordinator_port"]
                cfg.num_processes = asg["size"]
                cfg.process_id = asg["rank"]

        # Multi-process rendezvous via the JAX coordination service
        # (the TPU-native replacement for MPI/Gloo rendezvous, SURVEY.md
        # §5.8).  Process count/id resolution: prefer the launcher's
        # explicit HOROVOD_NUM_PROCESSES/PROCESS_ID; fall back to the
        # cross_* vars (one process per host driving all its chips) and
        # finally to rank/size (one process per worker).
        n_procs = cfg.num_processes or cfg.cross_size or cfg.size
        if not (n_procs is not None and n_procs > 1
                and cfg.rendezvous_addr):
            break  # single-process: nothing to rendezvous
        coordinator = (
            f"{cfg.rendezvous_addr}:{cfg.rendezvous_port or 9999}")
        if cfg.process_id is not None:
            proc_id = cfg.process_id
        elif cfg.num_processes is None and cfg.cross_rank is not None:
            proc_id = cfg.cross_rank
        else:
            proc_id = cfg.rank
        dist_kwargs = {}
        if cfg.elastic:
            # survive peer death instead of LOG(FATAL)-ing: collectives
            # fail with a catchable error (→ HorovodInternalError path)
            # and this process can re-rendezvous at the next epoch
            jax.config.update("jax_enable_recoverability", True)
            hb = int(os.environ.get(
                "HOROVOD_ELASTIC_HEARTBEAT_TIMEOUT", "10"))
            # init timeout gates EPOCH FORMATION only (post-init
            # death is the heartbeat's job).  Two pressures: it must
            # cover the slowest member's spawn + jax import on an
            # oversubscribed host (30 s is too tight for 3 workers
            # on one core), but a member stuck in RegisterTask is
            # UNINTERRUPTIBLE until this deadline LOG(FATAL)s it —
            # so it must not exceed the driver's start_timeout or
            # stuck members stay a full epoch out of phase with the
            # driver's re-forms.
            dist_kwargs = dict(
                heartbeat_timeout_seconds=hb,
                shutdown_timeout_seconds=hb,
                initialization_timeout=int(os.environ.get(
                    "HOROVOD_ELASTIC_INIT_TIMEOUT", "60")))
        try:
            # a prior solo epoch (job shrunk to 1 process: distributed
            # init skipped) may have lazily created local backends;
            # they must go before the world re-forms
            from jax._src import xla_bridge as _xb
            if _xb.backends_are_initialized():
                jax.extend.backend.clear_backends()
        except Exception:  # noqa: BLE001 - internal API drift
            logger.debug("pre-init backend clear skipped",
                         exc_info=True)
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=n_procs,
                process_id=proc_id,
                **dist_kwargs,
            )
            _STATE.owns_jax_distributed = True
            break
        except Exception as e:  # noqa: BLE001 - barrier timeout /
            # half-dead coordinator; non-elastic jobs fail loudly
            if not cfg.elastic or time.monotonic() > start_deadline:
                raise
            attempt += 1
            logger.warning(
                "elastic rendezvous attempt %d failed (%s); "
                "re-fetching assignment", attempt, e)
            try:
                jax.distributed.shutdown()
            except Exception:  # noqa: BLE001 - partial init
                pass

    return cfg


def _bring_up_devices(cfg: Config, process_sets):
    """``init.backend``: the first ``jax.devices()`` of the process (where
    the TPU client comes up), the global mesh and the process sets."""
    # Invalidate compiled-kernel caches from a previous incarnation:
    # device ids collide across re-inits but the device objects (and
    # their runtime clients) are new, so stale jitted fns would fail
    # with "incompatible devices".
    from .ops.collectives import reset_kernel_caches
    reset_kernel_caches()

    _STATE.devices = list(jax.devices())
    _STATE.global_mesh = jax.sharding.Mesh(
        np.array(_STATE.devices), (cfg.worker_axis,))
    _STATE.lead_worker_rank = (
        jax.process_index() * jax.local_device_count())

    _STATE.process_set_table.clear()
    global_ps = ProcessSet(None)
    _STATE.process_set_table.register(
        global_ps, _STATE.devices, cfg.worker_axis)
    _STATE.global_process_set = global_ps
    if process_sets:
        for ps in process_sets:
            _STATE.process_set_table.register(
                ps, _STATE.devices, cfg.worker_axis)


def _start_observability(cfg: Config) -> str:
    """``init.observability``: metrics, timeline, stall inspector, tracing
    and health.  Returns the namespace of this incarnation, which the
    controller's keys share with the spans' epoch."""
    # metrics exposition + flight recorder env contract (SIGUSR1
    # dump handler, HOROVOD_METRICS_DUMP snapshots,
    # HOROVOD_METRICS_PORT scrape server); idempotent across
    # elastic re-inits
    _metrics.init_from_env()
    if _metrics.RECORDING:
        _metrics.event("runtime.init", process=jax.process_index(),
                       processes=jax.process_count())
    from .timeline import Timeline
    from .stall import StallInspector
    _STATE.timeline = Timeline(
        cfg.timeline_path, mark_cycles=cfg.timeline_mark_cycles,
        use_native=cfg.use_native_core)
    # straggler-score -> elastic-blacklist bridge (OptiReduce tail
    # prescription): a host whose EWMA lateness crosses
    # HOROVOD_TAIL_BLACKLIST_SCORE is reported to the elastic
    # driver as a SOFT failure — it feeds the blacklist before the
    # host dies outright.  Best effort and a no-op outside the
    # elastic driver (no endpoint exported).
    def _report_straggler(process, score):
        from .elastic import worker as _ew
        _ew.report_straggler(process, score)

    _STATE.stall_inspector = StallInspector(
        check_time=cfg.stall_check_time,
        shutdown_time=cfg.stall_shutdown_time,
        disabled=cfg.stall_check_disable,
        use_native=cfg.use_native_core,
        blacklist_score=cfg.tail_blacklist_score,
        on_straggler=_report_straggler)

    # Controller keys are namespaced per incarnation so init→shutdown→
    # init against a persistent coordination service never reads the
    # previous incarnation's rounds: elastic re-forms share the
    # driver's epoch; plain re-inits count generations in lockstep.
    global _INIT_GENERATION
    _INIT_GENERATION += 1
    if cfg.elastic:
        from .elastic import worker as elastic_worker
        ns = f"e{max(elastic_worker._last_epoch, 0)}"
    else:
        ns = f"g{_INIT_GENERATION}"
    # distributed-tracing identity/context (tracing/): spans carry
    # this worker's process rank, host, and elastic epoch so the
    # driver's /trace/job merge can assign one pid per host and
    # correlate rounds across incarnations
    _tracing.init_from_env()
    _tracing.set_identity(
        process=jax.process_index(),
        host=os.environ.get("HOROVOD_HOSTNAME") or None,
        epoch=int(ns[1:]))
    # training-health evaluator identity (health/): verdicts carry
    # this worker's rank/host so the driver's /health/job merge
    # attributes them; history survives elastic re-inits (a
    # post-mortem scrape wants the pre-reform verdicts)
    from . import health as _health
    _health.init_from_env()
    _health.set_identity(
        process=jax.process_index(),
        host=os.environ.get("HOROVOD_HOSTNAME") or None)
    return ns


def _start_engine(cfg: Config, ns: str):
    """``init.engine``: autotuner, negotiation controller and the
    background collective engine, started."""
    if cfg.autotune:
        from .autotune import ParameterManager
        # hierarchical collectives need a valid (groups, group_size)
        # factorization of the global set; without one the GP's hier
        # dimension would be inert and waste its sample budget
        _STATE.autotuner = ParameterManager(
            cfg, hier_available=(
                _STATE.global_process_set.hier_shape() is not None))

    # The background collective engine (reference: BackgroundThreadLoop)
    # with its cross-process negotiation controller (controller.cc).
    from .ops.controller import Controller
    from .ops.engine import CollectiveEngine
    _STATE.engine = CollectiveEngine(
        cfg, _STATE.global_mesh, _STATE.timeline,
        _STATE.stall_inspector, _STATE.autotuner,
        controller=Controller(cfg, _STATE.stall_inspector,
                              namespace=ns))
    _STATE.engine.start()


def init(comm=None, process_sets: Optional[Sequence[ProcessSet]] = None):
    """Initialize the runtime (reference: horovod_init → InitializeHorovodOnce).

    Resolves topology from the TPU slice / JAX runtime instead of
    MPI_COMM_WORLD:

    * Under the ``hvdrun`` launcher (or any launcher exporting the reference's
      §3.4 env contract: HOROVOD_RANK/SIZE + rendezvous address), calls
      ``jax.distributed.initialize`` so every process joins the coordination
      service and sees the global device set.
    * Stand-alone, uses whatever devices JAX exposes (single host).

    ``comm`` is accepted for API compatibility (the reference takes an MPI
    communicator); only ``None`` (world) is supported.
    ``process_sets`` are additional process sets to create at init, as in the
    reference's ``hvd.init(process_sets=...)``.
    """
    with _STATE._init_lock:
        if _STATE.initialized:
            return
        if comm is not None:
            raise ValueError(
                "horovod_tpu.init(comm=...) with a custom communicator is not "
                "supported on TPU; use process_sets for sub-groups.")
        # start-up's own spans (docs/observability.md "Start-up and the
        # jitted step"): one for the call, one for each part below
        with _tracing.scope("setup", "init"):
            use_compile_cache()
            with _tracing.scope("setup", "init.rendezvous"):
                cfg = _rendezvous()
            with _tracing.scope("setup", "init.backend"):
                _bring_up_devices(cfg, process_sets)
            with _tracing.scope("setup", "init.native"):
                if cfg.use_native_core:
                    # built or loaded here once; the stall inspector,
                    # timeline and controller below find it loaded
                    from .native import loader
                    loader.load()
            with _tracing.scope("setup", "init.observability"):
                ns = _start_observability(cfg)
            with _tracing.scope("setup", "init.engine"):
                _start_engine(cfg, ns)

        _STATE.initialized = True
        atexit.register(shutdown)
        if cfg.elastic:
            # rendezvous complete: the driver now counts a death of this
            # worker as a real host failure, not re-rendezvous churn
            from .elastic.worker import record_running
            record_running()
        logger.info(
            "horovod_tpu initialized: %d workers (%d local), process %d/%d",
            len(_STATE.devices), jax.local_device_count(), jax.process_index(),
            jax.process_count())


def shutdown():
    """Tear down the runtime (reference: horovod_shutdown)."""
    with _STATE._init_lock:
        if not _STATE.initialized:
            return
        try:
            if _metrics.RECORDING:
                _metrics.event("runtime.shutdown")
            _metrics.stop_exposition()
            _tracing.shutdown()
            if _STATE.engine is not None:
                _STATE.engine.stop()
            if _STATE.timeline is not None:
                _STATE.timeline.close()
            for hook in _STATE.shutdown_hooks:
                try:
                    hook()
                except Exception:  # noqa: BLE001 - best-effort teardown
                    logger.exception("shutdown hook failed")
        finally:
            if _STATE.owns_jax_distributed:
                # With recoverable tasks the default shutdown barrier is
                # skipped, so the leader can tear the coordination service
                # down while peers are still disconnecting (they then die
                # fatally).  Meet at an explicit barrier first, as the
                # coordination service docs prescribe for recoverable mode.
                try:
                    from jax._src import distributed as _dist
                    client = _dist.global_state.client
                    if client is not None and jax.process_count() > 1:
                        client.wait_at_barrier(
                            "horovod_tpu_shutdown",
                            int(float(os.environ.get(
                                "HOROVOD_SHUTDOWN_BARRIER_TIMEOUT",
                                "15")) * 1000))
                        if (jax.process_index() == 0
                                and _STATE.config is not None
                                and _STATE.config.elastic):
                            # the barrier alone is not enough: after it,
                            # the leader's shutdown can still destroy the
                            # coordination service while followers'
                            # disconnect RPCs are in flight — with
                            # recoverable tasks (elastic only) that is a
                            # LOG(FATAL) process death, not a catchable
                            # error, and a re-form degrades to respawns.
                            # Let followers disconnect first.  Non-elastic
                            # jobs keep jax's default shutdown barrier and
                            # need no linger.
                            time.sleep(float(os.environ.get(
                                "HOROVOD_SHUTDOWN_LEADER_LINGER", "1.5")))
                except Exception:  # noqa: BLE001 - peers may be gone
                    logger.debug("shutdown barrier failed", exc_info=True)
                # release the coordination-service connection so an elastic
                # re-init can re-join the (possibly re-formed) cluster
                try:
                    jax.distributed.shutdown()
                except Exception:  # noqa: BLE001 - peer may already be gone
                    logger.warning("jax.distributed.shutdown failed",
                                   exc_info=True)
                # the device clients embed the old distributed world (size,
                # process id); drop them so re-init builds fresh ones.
                # NOTE: live device arrays die with the backends — the
                # elastic run wrapper calls state.evacuate() (snapshot →
                # host) before re-initializing for exactly this reason.
                try:
                    jax.extend.backend.clear_backends()
                except Exception:  # noqa: BLE001 - best effort
                    logger.warning("clear_backends failed", exc_info=True)
                _STATE.owns_jax_distributed = False
            _STATE.initialized = False
            _STATE.engine = None
            _STATE.global_mesh = None
            _STATE.global_process_set = None
            _STATE.process_set_table.clear()


def is_initialized() -> bool:
    """Reference: horovod_is_initialized / hvd.is_initialized()."""
    return _STATE.initialized


def start_timeline(file_path: str, mark_cycles: bool = False):
    """Reference: hvd.start_timeline (horovod/common/basics.py)."""
    st = _require_init()
    st.timeline.reopen(file_path, mark_cycles=mark_cycles)


def stop_timeline():
    st = _require_init()
    st.timeline.close()


def start_profiler(logdir: str):
    """Start a device (XLA/libtpu) trace via ``jax.profiler``.

    The NVTX-integration analog (reference: nvtx_op_range.cc + Nsight):
    while active, the engine's per-dispatch TraceAnnotation ranges land
    in the same Perfetto trace as XLA's collective/kernel spans, giving
    the merged framework+device view SURVEY §5.1 prescribes.  View with
    ``tensorboard --logdir`` or Perfetto.  The python tracer is off:
    on, eight steps of a jitted loop were 9 MB of ``$builtins
    isinstance`` (PERF.md, PR 23) around the spans that say something.
    """
    _require_init()
    import jax.profiler
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=options)


def stop_profiler():
    _require_init()
    import jax.profiler
    jax.profiler.stop_trace()


# --- topology accessors (reference: horovod/common/basics.py) ---------------

def size() -> int:
    """Total number of workers (chips) participating in collectives."""
    _require_init()
    return len(_STATE.devices)


def rank() -> int:
    """Global rank of this process's lead worker (chip).

    ``rank() == 0`` is true exactly on the coordinator process, preserving
    the reference's checkpoint-gating idiom.
    """
    _require_init()
    return _STATE.lead_worker_rank


def local_size() -> int:
    """Number of workers (chips) driven by this process."""
    _require_init()
    return jax.local_device_count()


def local_rank() -> int:
    """Rank of the lead worker within this host (0 in SPMD: the process owns
    all its local chips)."""
    _require_init()
    return 0


def cross_size() -> int:
    """Number of processes (hosts) — reference: ranks with my local_rank."""
    _require_init()
    return jax.process_count()


def cross_rank() -> int:
    """Index of this process among processes (hosts)."""
    _require_init()
    return jax.process_index()


def process_count() -> int:
    """TPU-native explicit name for ``jax.process_count()``."""
    _require_init()
    return jax.process_count()


def process_index() -> int:
    """TPU-native explicit name for ``jax.process_index()``."""
    _require_init()
    return jax.process_index()


def is_homogeneous() -> bool:
    """Reference: horovod_is_homogeneous — equal local sizes on all hosts.

    TPU slices are homogeneous by construction.
    """
    _require_init()
    return True


def mesh() -> jax.sharding.Mesh:
    """The global 1-D worker mesh (TPU-native addition)."""
    _require_init()
    return _STATE.global_mesh


def worker_axis() -> str:
    _require_init()
    return _STATE.config.worker_axis


# --- feature queries (reference: util.py check_extension / basics.py) -------

def mpi_threads_supported() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def mpi_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def ccl_built() -> bool:
    return False


def cuda_built() -> bool:
    return False


def rocm_built() -> bool:
    return False


def xla_built() -> bool:
    """All collectives compile to XLA on this framework."""
    return True


def tpu_built() -> bool:
    return True


# --- process set API (reference: horovod/common/process_sets.py) ------------

global_process_set: Optional[ProcessSet] = None  # set lazily via __getattr__


def _get_global_process_set() -> ProcessSet:
    _require_init()
    return _STATE.global_process_set


def add_process_set(ps_or_ranks) -> ProcessSet:
    """Create a new process set at runtime (reference: hvd.add_process_set)."""
    st = _require_init()
    ps = (ps_or_ranks if isinstance(ps_or_ranks, ProcessSet)
          else ProcessSet(ps_or_ranks))
    st.process_set_table.register(ps, st.devices, st.config.worker_axis)
    return ps


def remove_process_set(ps: ProcessSet) -> bool:
    st = _require_init()
    if not ps.initialized():
        return False
    st.process_set_table.remove(ps.process_set_id)
    return True


def get_process_set_ids_and_ranks() -> Dict[int, List[int]]:
    st = _require_init()
    return {i: list(st.process_set_table.get(i).ranks)
            for i in st.process_set_table.ids()}


def get_process_set_by_id(set_id: int) -> ProcessSet:
    """Resolve a registered process set by its id (reference:
    process_set.cc lookups — used by bindings that carry the id through
    an op attribute, e.g. the TF custom-op bridge)."""
    st = _require_init()
    try:
        return st.process_set_table.get(set_id)
    except KeyError:
        raise ValueError(
            f"process set id {set_id} is not registered (removed, or "
            "from a previous init?) — compiled graphs carrying the id "
            "must not outlive remove_process_set") from None


def _setup_logging(cfg: Config):
    level = {
        "trace": logging.DEBUG, "debug": logging.DEBUG,
        "info": logging.INFO, "warning": logging.WARNING,
        "error": logging.ERROR, "fatal": logging.CRITICAL,
        "off": logging.CRITICAL,
    }.get(cfg.log_level.lower(), logging.WARNING)
    fmt = ("%(asctime)s %(name)s %(levelname)s: %(message)s"
           if cfg.log_timestamp else "%(name)s %(levelname)s: %(message)s")
    handler = logging.StreamHandler()
    handler.setFormatter(logging.Formatter(fmt))
    logger.handlers[:] = [handler]
    logger.setLevel(level)
