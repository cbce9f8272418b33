"""Execution environments — "Users are only expected to select the execution
environment for the tasks of the workflow ... switching from one environment
to another is achieved by modifying a single line" (paper §2.2).

  LocalEnvironment()                     laptop: plain jit, 1 device
  MeshEnvironment(multi_pod=False)       one pod: (16,16) data x model
  MeshEnvironment(multi_pod=True)        two pods: (2,16,16)

The same workflow object runs on any of them. GridScale's over-submission
trick (submit a job to several queues, keep the first result) survives as
``speculative`` execution for host-side PyTasks; retries with backoff handle
transient failures. Device tasks are SPMD and synchronous: their fault
tolerance is checkpoint/restart at the workflow layer (see launch/).
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core.faults import (FaultSpec, InjectedFailure, ResultCorruption,
                               corrupt_output, interruptible_sleep,
                               is_device_error)
from repro.core.prototype import Context
from repro.core.task import Task, TaskError
from repro.runtime import sharding as shd


@dataclasses.dataclass
class EnvStats:
    submitted: int = 0
    completed: int = 0
    retried: int = 0
    speculative_wins: int = 0
    failed: int = 0        # attempts lost to (injected or real) failures
    hung: int = 0          # attempts abandoned past timeout_s
    corrupted: int = 0     # attempts rejected by fingerprint verification


class Environment:
    """Base execution environment: local execution with retry, speculation,
    and a futures-based async submission path for the dataflow scheduler.

    Args:
        retries: transient-failure retries per task submission (exponential
            backoff; ``TaskError`` declaration bugs never retry).
        backoff_s: base backoff between retries (doubles per attempt).
        speculative: >1 over-submits host-side PyTasks that many times and
            keeps the first result (GridScale's EGI trick).
        async_workers: thread-pool width for ``submit_async`` (default 8).
        capacity: concurrent-task slots this environment offers to an
            ``EnvironmentPool`` (core/envpool.py) — a 2-core worker vs a
            whole queue of grid slots.
        latency_s: fixed per-attempt submission latency (heterogeneous
            environments differ in queue latency, not only capacity).
        timeout_s: per-attempt wall-clock budget; an attempt exceeding it
            counts as hung and is resubmitted (the abandoned attempt's
            late result is discarded).
        faults: optional injectable failure model (core/faults.py) used by
            the chaos tests and the ``egi_200k_init`` benchmark.
        name: override the environment's display name (pool members need
            distinguishable names in provenance records).
    """

    name = "local"

    def __init__(self, *, retries: int = 2, backoff_s: float = 0.1,
                 speculative: int = 1, async_workers: int = 8,
                 capacity: int = 8, latency_s: float = 0.0,
                 timeout_s: Optional[float] = None,
                 faults: Optional[FaultSpec] = None,
                 name: Optional[str] = None):
        self.retries = retries
        self.backoff_s = backoff_s
        self.speculative = speculative
        self.async_workers = async_workers
        self.capacity = capacity
        self.latency_s = latency_s
        self.timeout_s = timeout_s
        self.faults = faults
        if name is not None:
            self.name = name
        self.stats = EnvStats()
        self._pool: Optional[cf.ThreadPoolExecutor] = None
        self._async_pool: Optional[cf.ThreadPoolExecutor] = None
        self._attempt_pool: Optional[cf.ThreadPoolExecutor] = None
        self._lock = threading.Lock()
        # Injected hangs sleep on this event so pool shutdown (or test
        # teardown) can wake stragglers instead of wedging on them.
        self._wake = threading.Event()
        # Per-attempt wake events of timeout-bounded attempts currently in
        # flight: an abandoned (timed-out) attempt is woken individually so
        # it cannot pin its _attempt_pool slot for the injected hang's full
        # duration. release_hangs() sets these too.
        self._attempt_wakes: set = set()

    # -- single task ---------------------------------------------------------
    def submit(self, task: Task, context: Context) -> Context:
        """Run one task synchronously (with retry/speculation).

        Args:
            task: the Task to execute.
            context: its input Context.

        Returns:
            The task's validated output Context (outputs only, not merged
            with the inputs — the workflow layer does the union).
        """
        return self.submit_traced(task, context)[0]

    def submit_traced(self, task: Task, context: Context
                      ) -> Tuple[Context, Dict[str, Any]]:
        """Like :meth:`submit`, but also returns execution metadata.

        Returns:
            ``(output, meta)`` where ``meta`` has keys ``retries`` (int),
            ``speculative`` (bool), ``t0`` (monotonic start time),
            ``wall_s`` (float), and ``attempts`` (one dict per attempt:
            environment, outcome, wall_s) — consumed by the scheduler's
            per-attempt provenance records (core/scheduler.py).
        """
        meta: Dict[str, Any] = {"retries": 0, "speculative": False,
                                "t0": time.monotonic(), "wall_s": 0.0,
                                "attempts": []}
        with self._lock:
            self.stats.submitted += 1
        if task.kind == "py" and self.speculative > 1:
            out = self._speculative_run(task, context, meta)
            meta["speculative"] = True
        else:
            out = self._run_with_retry(task, context, meta)
        with self._lock:
            self.stats.completed += 1
        meta["wall_s"] = time.monotonic() - meta["t0"]
        # Hand out a COPY: losing speculative attempts may still be running
        # and will append to the internal attempts list after we return —
        # they must not mutate meta already aliased into TaskRecords.
        out_meta = dict(meta)
        out_meta["attempts"] = [dict(a) for a in list(meta["attempts"])]
        return out, out_meta

    def submit_async(self, task: Task, context: Context) -> "cf.Future":
        """Submit one task to the environment's thread pool.

        Returns:
            A future resolving to ``(output Context, meta dict)`` exactly as
            :meth:`submit_traced` would return. The async dataflow scheduler
            uses this to overlap host-side PyTasks within and across
            capsules; device-side JaxTask fan-outs go through
            :meth:`map_explore` instead (batched SPMD lanes).
        """
        with self._lock:
            if self._async_pool is None:
                self._async_pool = cf.ThreadPoolExecutor(
                    max_workers=self.async_workers,
                    thread_name_prefix=f"repro-env-{self.name}")
        return self._async_pool.submit(self.submit_traced, task, context)

    # -- attempt machinery ---------------------------------------------------
    def _job_key(self, task: Task, context: Context) -> str:
        """Stable identity of one (task, inputs) job for fault decisions.
        Only computed when a FaultSpec is active (hashing costs)."""
        from repro.core.cache import inputs_digest
        return f"{task.name}:{inputs_digest(task, context)}"

    def run_attempt(self, task: Task, context: Context, *, attempt: int = 0,
                    job: Optional[str] = None,
                    wake: Optional[threading.Event] = None
                    ) -> Tuple[Context, Optional[str]]:
        """Execute ONE attempt of a task on this environment.

        Applies the environment's latency and — when a :class:`FaultSpec`
        is installed — the deterministic fault decision for ``(job,
        attempt)``: injected failures raise, injected hangs sleep
        (interruptibly) before completing, injected corruption perturbs the
        output *after* the source-side fingerprint was taken.

        Args:
            wake: optional per-attempt event that interrupts this attempt's
                sleeps (in addition to the environment-wide ``_wake``);
                :meth:`attempt_once` sets it when it abandons the attempt
                at timeout so the executor slot drains promptly.

        Returns:
            ``(output, fingerprint)`` — fingerprint is the sha256 of the
            output as computed at the source, or None when no faults are
            active (verification is then unnecessary). The caller detects
            corruption by recomputing the fingerprint on receipt
            (:meth:`verify_result`).
        """
        w = wake if wake is not None else self._wake
        if self.latency_s:
            interruptible_sleep(self.latency_s, w)
        f = self.faults
        decision = "ok"
        if f is not None:
            job = job or self._job_key(task, context)
            decision = f.decide(job, attempt)
            if f.latency_s:
                interruptible_sleep(f.latency_s, w)
        if decision == "fail":
            raise InjectedFailure(
                f"injected failure: {task.name} attempt {attempt} "
                f"on {self.name}")
        if decision == "hang":
            interruptible_sleep(f.hang_s, w)
        out = task.run(context)
        if f is None:
            return out, None
        from repro.core.cache import hash_context
        digest = hash_context(out)
        if decision == "corrupt":
            out = corrupt_output(out)
        return out, digest

    @staticmethod
    def verify_result(out: Context, digest: Optional[str]) -> Context:
        """Receiver-side integrity check: recompute the output fingerprint
        and reject mismatches as :class:`ResultCorruption` (transient —
        the caller resubmits)."""
        if digest is not None:
            from repro.core.cache import hash_context
            if hash_context(out) != digest:
                raise ResultCorruption("output fingerprint mismatch")
        return out

    def release_hangs(self) -> None:
        """Wake every injected hang currently sleeping on this environment
        (pool shutdown / test teardown); late results are discarded by
        their abandoned futures."""
        self._wake.set()
        self._wake = threading.Event()
        with self._lock:
            wakes = list(self._attempt_wakes)
        for w in wakes:                    # timeout-bounded attempts sleep
            w.set()                        # on their own per-attempt event

    def attempt_once(self, task: Task, context: Context, *, attempt: int = 0,
                     job: Optional[str] = None) -> Context:
        """One timeout-bounded, integrity-verified attempt — the shared
        primitive under both the single-environment retry loop and the
        pool's cross-member resubmission (core/envpool.py).

        Raises:
            TimeoutError: the attempt exceeded ``timeout_s`` (counted as
                hung; the late result is discarded).
            ResultCorruption: receiver-side fingerprint mismatch.
            TaskError: declaration bug — callers must not retry it.
            Exception: whatever the task raised (counted as failed).
        """
        try:
            if self.timeout_s is not None:
                with self._lock:
                    if self._attempt_pool is None:
                        self._attempt_pool = cf.ThreadPoolExecutor(
                            max_workers=max(self.capacity, 2),
                            thread_name_prefix=f"repro-att-{self.name}")
                begun = threading.Event()
                wake = threading.Event()
                with self._lock:
                    self._attempt_wakes.add(wake)

                def _attempt():
                    begun.set()
                    return self.run_attempt(task, context, attempt=attempt,
                                            job=job, wake=wake)

                fut = self._attempt_pool.submit(_attempt)
                try:
                    # The timeout budget opens when the attempt BEGINS
                    # executing — time spent queued behind a saturated
                    # _attempt_pool does not count against it.
                    while not begun.wait(timeout=0.02):
                        if fut.done():
                            break          # raced a cancel/error: surface it
                    out, digest = fut.result(timeout=self.timeout_s)
                except cf.TimeoutError:
                    # Abandon the attempt AND drain its executor slot: the
                    # per-attempt wake interrupts its (injected-hang or
                    # latency) sleeps so the worker returns promptly and the
                    # fixed-width pool is not pinned by abandoned attempts.
                    wake.set()
                    fut.cancel()           # late result discarded
                    with self._lock:
                        self.stats.hung += 1
                    raise TimeoutError(
                        f"task {task.name} attempt {attempt} exceeded "
                        f"{self.timeout_s}s on {self.name}") from None
                finally:
                    with self._lock:
                        self._attempt_wakes.discard(wake)
            else:
                out, digest = self.run_attempt(task, context,
                                               attempt=attempt, job=job)
        except (TaskError, TimeoutError):
            raise
        except Exception:                  # transient (I/O, preemption)
            with self._lock:
                self.stats.failed += 1
            raise
        try:
            return self.verify_result(out, digest)
        except ResultCorruption:
            with self._lock:
                self.stats.corrupted += 1
            raise

    @staticmethod
    def attempt_outcome(err: Optional[BaseException]) -> str:
        """Classify an :meth:`attempt_once` exception for provenance."""
        if err is None:
            return "ok"
        if isinstance(err, TimeoutError):
            return "hang"
        if isinstance(err, ResultCorruption):
            return "corrupt"
        return "fail"

    def _run_with_retry(self, task: Task, context: Context,
                        meta: Optional[Dict[str, Any]] = None) -> Context:
        err = None
        job = self._job_key(task, context) if self.faults is not None else None
        for attempt in range(self.retries + 1):
            a_t0 = time.monotonic()
            try:
                out = self.attempt_once(task, context, attempt=attempt,
                                        job=job)
                self._note_attempt(meta, "ok", a_t0)
                return out
            except TaskError:
                raise                      # declaration bugs don't retry
            except Exception as e:
                err = e
            self._note_attempt(meta, self.attempt_outcome(err), a_t0, err)
            if is_device_error(err):
                raise err                  # a retry would repeat it
            with self._lock:
                self.stats.retried += 1
            if meta is not None:
                meta["retries"] += 1
            interruptible_sleep(self.backoff_s * (2 ** attempt), self._wake)
        raise RuntimeError(
            f"task {task.name} failed after {self.retries + 1} attempts") \
            from err

    def _note_attempt(self, meta, outcome: str, a_t0: float,
                      err: Optional[BaseException] = None) -> None:
        if meta is None:
            return
        meta.setdefault("attempts", []).append({
            "environment": self.name, "outcome": outcome,
            "wall_s": time.monotonic() - a_t0,
            "error": None if err is None else f"{type(err).__name__}: {err}"})

    def _speculative_run(self, task: Task, context: Context,
                         meta: Optional[Dict[str, Any]] = None) -> Context:
        """First-result-wins over `speculative` duplicate submissions —
        straggler mitigation exactly as OpenMOLE over-submits on EGI."""
        with self._lock:
            if self._pool is None:
                self._pool = cf.ThreadPoolExecutor(max_workers=8)
            pool = self._pool
        job = self._job_key(task, context) if self.faults is not None else None

        def one(i):
            a_t0 = time.monotonic()
            try:
                out = self.attempt_once(task, context, attempt=i, job=job)
            except BaseException as e:
                self._note_attempt(meta, self.attempt_outcome(e), a_t0, e)
                raise
            self._note_attempt(meta, "ok", a_t0)
            return out

        futures = [pool.submit(one, i) for i in range(self.speculative)]
        err = None
        for f in cf.as_completed(futures):
            try:
                result = f.result()
                with self._lock:
                    self.stats.speculative_wins += 1
                for other in futures:
                    other.cancel()
                return result
            except Exception as e:
                if is_device_error(e):
                    raise
                err = e
        raise RuntimeError(f"all speculative copies of {task.name} failed") \
            from err

    # -- vectorized exploration ------------------------------------------------
    def map_explore(self, task: Task, contexts: Sequence[Context]):
        """Run one task over many contexts (an exploration fan-out).

        Args:
            task: the Task to evaluate at every point.
            contexts: input Contexts, one per design-of-experiments point.

        Returns:
            A list of output Contexts in the same order. The base
            environment runs them one by one (a laptop-sized DoE);
            MeshEnvironment batches JaxTasks into sharded vmap lanes.
        """
        return [self.submit(task, c) for c in contexts]

    def jit(self, fn, **kw):
        """Compile ``fn`` for this environment (plain ``jax.jit`` locally;
        mesh environments install their mesh around the call)."""
        return jax.jit(fn, **kw)

    @property
    def mesh(self):
        """The device mesh backing this environment (None for local)."""
        return None

    def __repr__(self):
        return f"{type(self).__name__}()"


class LocalEnvironment(Environment):
    pass


def _stack_lanes(contexts: Sequence[Context]) -> Optional[Dict[str, Any]]:
    """Stack the contexts' leaves into leading-axis lane arrays, or None
    when they are ragged: different keys, or a leaf whose shape or dtype
    differs across contexts or that is not numeric. Only ragged fan-outs
    leave the device path; any error past this point is the program's and
    surfaces to the caller."""
    names = sorted(contexts[0].keys())
    if any(sorted(c.keys()) != names for c in contexts):
        return None
    batched = {}
    for n in names:
        leaves = [v if isinstance(v, jax.Array) else np.asarray(v)
                  for v in (c[n] for c in contexts)]
        first = leaves[0]
        if (not isinstance(first, jax.Array)
                and first.dtype.kind not in "biufc") or any(
                (a.shape, a.dtype) != (first.shape, first.dtype)
                for a in leaves):
            return None
        stack = jax.numpy.stack if isinstance(first, jax.Array) else np.stack
        batched[n] = stack(leaves)
    return batched


class MeshEnvironment(Environment):
    """Delegates JaxTasks to a device mesh; explorations become batched
    lanes sharded over the data axes (one grid job per lane)."""

    def __init__(self, mesh=None, *, multi_pod: bool = False, **kw):
        super().__init__(**kw)
        if mesh is None:
            from repro.launch.mesh import make_production_mesh
            mesh = make_production_mesh(multi_pod=multi_pod)
        self._mesh = mesh
        self.name = "multipod" if multi_pod else "pod"

    @property
    def mesh(self):
        return self._mesh

    def jit(self, fn, **kw):
        mesh = self._mesh

        def wrapped(*args, **kwargs):
            with shd.use_mesh(mesh):
                return fn(*args, **kwargs)

        return jax.jit(wrapped, **kw)

    def map_explore(self, task: Task, contexts: Sequence[Context]):
        """Batch numeric leaves across contexts into leading-axis arrays,
        vmap the task function, shard the lane axis over data/pod axes."""
        if task.kind != "jax" or not contexts:
            return super().map_explore(task, contexts)
        batched = _stack_lanes(contexts)
        if batched is None:
            return super().map_explore(task, contexts)  # ragged -> host

        def one(ctx):
            return task.fn(Context(ctx))

        n_lanes = len(contexts)
        mesh = self._mesh

        def run(batch):
            with shd.use_mesh(mesh):
                batch = {k: shd.constrain(v, ("island",) + (None,) * (v.ndim - 1))
                         for k, v in batch.items()}
                return jax.vmap(one)(batch)

        out = jax.jit(run)(batched)
        with self._lock:
            self.stats.submitted += n_lanes
            self.stats.completed += n_lanes
        out_host = jax.tree.map(np.asarray, out)
        results = []
        for i in range(n_lanes):
            results.append(task.validate_outputs(
                {k: v[i] for k, v in out_host.items()}))
        return results


class DeviceEnvironment(Environment):
    """A pool member that owns a **disjoint subset of local devices**.

    The paper scales the 200k streaming init by spreading pure jobs over
    whatever compute is attached; with thread-backed members every attempt
    still lands on jax's process-wide default device. A DeviceEnvironment
    pins its work to its own devices instead:

    * host-side attempts (``run_attempt`` — the streaming-init chunk and
      surrogate-eval PyTasks) run under a thread-local
      ``jax.default_device`` chosen round-robin from the member's devices,
      so jit dispatch and PRNG ops inside the task land on this member's
      silicon, not the global default;
    * batched JaxTask lanes (``map_explore`` — the pool's batched-lane
      fast path) are explicitly placed on the member's device subset with
      a ``NamedSharding`` over a one-axis ``lane`` mesh (falling back to a
      single member device when the lane count does not divide evenly).

    All the existing knobs (``capacity``/``latency_s``/``timeout_s``/
    ``faults``/``retries``...) apply unchanged, so device-set members slot
    into an ``EnvironmentPool`` exactly like thread members — including
    under chaos injection. ``capacity`` defaults to ``2 * len(devices)``
    so each device keeps one attempt in flight while the next is queued.
    """

    def __init__(self, devices: Sequence[Any], *, capacity: Optional[int] = None,
                 **kw):
        devices = tuple(devices)
        if not devices:
            raise ValueError("DeviceEnvironment requires at least one device")
        kw.setdefault("name", "dev[" + ",".join(
            str(getattr(d, "id", d)) for d in devices) + "]")
        super().__init__(capacity=(2 * len(devices) if capacity is None
                                   else capacity), **kw)
        self.devices = devices
        self._rr_cursor = 0
        # Device ids the most recent batched map_explore actually placed
        # its lanes on (read back from the output arrays' sharding) —
        # observability for the forced-device placement tests.
        self.last_lane_devices: Optional[Tuple[int, ...]] = None

    @property
    def mesh(self):
        if len(self.devices) == 1:
            return None
        return jax.sharding.Mesh(np.asarray(self.devices), ("lane",))

    def _next_device(self):
        """Round-robin over the member's devices (lock-protected cursor)."""
        with self._lock:
            d = self.devices[self._rr_cursor % len(self.devices)]
            self._rr_cursor += 1
        return d

    def run_attempt(self, task: Task, context: Context, *, attempt: int = 0,
                    job: Optional[str] = None,
                    wake: Optional[threading.Event] = None
                    ) -> Tuple[Context, Optional[str]]:
        # jax.default_device is thread-local (verified under jax 0.9.0),
        # so concurrent attempts on other members cannot unpin this one.
        with jax.default_device(self._next_device()):
            return super().run_attempt(task, context, attempt=attempt,
                                       job=job, wake=wake)

    def jit(self, fn, **kw):
        dev = self.devices[0]

        def wrapped(*args, **kwargs):
            with jax.default_device(dev):
                return fn(*args, **kwargs)

        return jax.jit(wrapped, **kw)

    def map_explore(self, task: Task, contexts: Sequence[Context]):
        """Batched lanes explicitly placed on the member's own devices."""
        if task.kind != "jax" or not contexts:
            return super().map_explore(task, contexts)
        batched = _stack_lanes(contexts)
        if batched is None:
            return super().map_explore(task, contexts)  # ragged -> host

        n_lanes = len(contexts)
        devs = self.devices
        if len(devs) > 1 and n_lanes % len(devs) == 0:
            mesh = jax.sharding.Mesh(np.asarray(devs), ("lane",))
            sharding = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec("lane"))
            placed = {k: jax.device_put(v, sharding)
                      for k, v in batched.items()}
        else:
            placed = {k: jax.device_put(v, self._next_device())
                      for k, v in batched.items()}

        def one(ctx):
            return task.fn(Context(ctx))

        # jit outputs follow the (committed) input sharding, so the whole
        # batch stays on this member's subset end to end.
        out = jax.jit(jax.vmap(one))(placed)
        leaf = jax.tree.leaves(out)[0]
        self.last_lane_devices = tuple(
            sorted(d.id for d in leaf.sharding.device_set))
        with self._lock:
            self.stats.submitted += n_lanes
            self.stats.completed += n_lanes
        out_host = jax.tree.map(np.asarray, out)
        return [task.validate_outputs({k: v[i] for k, v in out_host.items()})
                for i in range(n_lanes)]

    def __repr__(self):
        ids = ",".join(str(getattr(d, "id", d)) for d in self.devices)
        return f"DeviceEnvironment(devices=[{ids}])"


def make_device_members(mesh_or_devices=None, k: int = 2, **kw):
    """Partition the local device list into ``k`` disjoint
    :class:`DeviceEnvironment` pool members.

    Args:
        mesh_or_devices: a ``jax.sharding.Mesh``, an explicit device
            sequence, or None for ``jax.local_devices()``.
        k: number of members; devices are split contiguously, remainders
            go to the earliest members.
        **kw: forwarded to every member (``retries``/``timeout_s``/...).
            ``faults`` may be a callable ``i -> FaultSpec`` for per-member
            seeds (the chaos-test idiom).

    Returns:
        A list of k DeviceEnvironments over pairwise-disjoint device sets,
        ready for ``EnvironmentPool(members)``.
    """
    if mesh_or_devices is None:
        devices = list(jax.local_devices())
    elif hasattr(mesh_or_devices, "devices"):          # a Mesh
        devices = list(np.asarray(mesh_or_devices.devices).ravel())
    else:
        devices = list(mesh_or_devices)
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if k > len(devices):
        raise ValueError(
            f"cannot partition {len(devices)} device(s) into {k} members")
    faults = kw.pop("faults", None)
    q, r = divmod(len(devices), k)
    members, start = [], 0
    for i in range(k):
        n = q + (1 if i < r else 0)
        sub = devices[start:start + n]
        start += n
        f = faults(i) if callable(faults) else faults
        ids = ",".join(str(getattr(d, "id", d)) for d in sub)
        members.append(DeviceEnvironment(
            sub, name=f"dev{i}[{ids}]", faults=f, **kw))
    return members


def EGIEnvironment(*args, **kw):
    """The paper's EGIEnvironment("biomed", ...) — on TPU infrastructure the
    closest analogue is the multi-pod mesh. Kept as an alias so paper
    listings port one-to-one."""
    kw.pop("vo", None)
    kw.pop("openMOLEMemory", None)
    kw.pop("wallTime", None)
    return MeshEnvironment(multi_pod=True, **kw)
