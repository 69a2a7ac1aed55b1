"""Measured overlap: the wall-clock twin of the simulated DOLMA runtime.

Every fabric/pipeline speedup number in this repo before PR 8 came from the
charged-timeline simulator (:mod:`repro.core.dual_buffer` on a
:class:`~repro.core.fabric.SimClock`). This module runs the same
fetch→compute→commit loop *for real*:

  * LOCAL-tier objects live as jax device arrays, materialized once at
    placement time;
  * REMOTE-tier objects live host-side and stream in through
    :class:`HostFetchEngine` — one emulated QP (a worker thread) that really
    moves the bytes (``jax.device_put`` + ``block_until_ready``) after
    pacing out the modeled fabric time (the container has no NIC, exactly
    the premise the simulator was built on — but here the latency *elapses
    on the wall clock* and must be hidden by *real* compute to disappear);
  * compute runs through the Pallas kernels (:mod:`repro.kernels.ops`) —
    compiled on TPU, where ``streaming_matmul`` additionally dual-buffers
    the HBM→VMEM edge with ``pltpu.make_async_copy``; ``interpret=True``
    elsewhere so the path is exercisable on CPU CI hosts;
  * the dual buffer is :class:`StreamingExecutor`'s prefetch: the next
    remote stage's fetch is posted *before* the current stage's compute, so
    the transfer and the kernel overlap; the access barrier is the
    ``Future.result()`` deferred to first use (§5);
  * ``commit_output=True`` writes the final activation back through the
    engine (device→host, write-model paced) — the commit leg of the loop.

The simulator is then held to account: :meth:`StreamingExecutor.simulate`
replays the identical control flow on a :class:`SimClock` through a
:class:`~repro.core.fabric.FabricResource`, and
:meth:`FabricResource.calibrate` fits that resource's cost model from the
engine's own wall-clock measurements — so ``predicted vs measured`` error is
a property of the *model*, not of hand-tuned constants. Both sides record
spans into one :class:`~repro.core.telemetry.Telemetry` (wall tracks
``wall/...`` via :meth:`Telemetry.wall_span`, simulated tracks
``sim/...``), so a single exported Perfetto trace shows the real
fetch/compute overlap next to the simulated timeline.

The wall spans are also profiler annotations, made whether or not the
telemetry is enabled, so a ``jax.profiler`` trace of a run shows on the
device's clock where the host was: ``dolma:exec.pass`` around a pass, and
inside it ``exec.input`` (the input's copy to the device), ``exec.barrier``
(waiting for a streamed stage's copy), ``exec.stage`` holding
``exec.dispatch`` (launching the stage's kernel) and ``exec.sync`` (waiting
for it), and ``exec.commit``; the fetch worker's thread records
``dolma:fabric.read`` / ``fabric.write`` with the bytes moved.

Outputs are bit-identical to the untiered oracle by construction: prefetch
on, prefetch off, and all-local runs execute the same jitted kernels on the
same values — streaming changes *when* bytes move, never *what* is computed
(asserted in tests and in ``benchmarks/fig_measured_overlap.py``).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Iterable, Sequence

import jax
import numpy as np

from repro.core.fabric import (
    FabricModel,
    FabricResource,
    INFINIBAND_100G,
    SimClock,
)
from repro.core.metadata import Tier
from repro.core.objects import DataObject, ObjectCatalog, ObjectKind
from repro.core.placement import PlacementPlan, PlacementPolicy
from repro.core.telemetry import NULL_TELEMETRY, Telemetry
from repro.kernels import ops, resolve_interpret

#: Default RDMA-op chunk for the emulated QP (the paper's 4 MiB anchor).
DEFAULT_CHUNK_BYTES = 4 << 20


@dataclasses.dataclass
class StreamStage:
    """One link of a streamed compute chain.

    ``params`` holds the streamable payloads by role — ``{"w": ...}`` for a
    matmul stage, ``{"k": ..., "v": ...}`` for an attention stage (the KV
    path). ``kwargs`` is forwarded to the kernel wrapper (block sizes,
    causal/window flags).
    """

    name: str
    op: str                                   # "matmul" | "attention"
    params: dict[str, np.ndarray]
    tier: Tier = Tier.REMOTE
    kwargs: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def nbytes(self) -> int:
        return int(sum(int(a.nbytes) for a in self.params.values()))


class HostFetchEngine:
    """One emulated QP: a worker thread that really moves the bytes.

    A read = (modeled fabric time, really slept) + (actual host→device
    ``jax.device_put``); a write is the mirror image (device→host). The
    single worker serializes ops like a real QP. ``throttle`` scales the
    modeled time (1.0 = the paper-calibrated model as-is; 0 disables pacing
    so a transfer costs only its real copy). Every paced op's
    ``(kind, nbytes, us)`` wall measurement is collected in
    :attr:`measurements` — the input to :meth:`FabricResource.calibrate`.
    """

    def __init__(
        self,
        *,
        fabric: FabricModel = INFINIBAND_100G,
        throttle: float = 1.0,
        chunk_bytes: int = DEFAULT_CHUNK_BYTES,
        telemetry: Telemetry | None = None,
        track: str = "wall/fabric",
    ) -> None:
        if throttle < 0.0:
            raise ValueError(f"throttle must be >= 0, got {throttle!r}")
        self.fabric = fabric
        self.throttle = float(throttle)
        self.chunk_bytes = int(chunk_bytes)
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        self.track = track
        self.measurements: list[tuple[str, int, float]] = []
        self.bytes_read = 0
        self.bytes_written = 0
        self.n_ops = 0
        self._lock = threading.Lock()
        self._pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="dolma-fetch"
        )

    # -- pacing ------------------------------------------------------------
    def pace_us(self, kind: str, nbytes: int) -> float:
        """Modeled duration of one posted transfer at the current throttle."""
        if self.throttle <= 0.0 or nbytes <= 0:
            return 0.0
        return self.throttle * self.fabric.stream_us(
            kind, nbytes, self.chunk_bytes, mode="pipelined"
        )

    def prediction_model(self) -> FabricModel:
        """The model :meth:`StreamingExecutor.simulate` should price with
        when no calibrated model is supplied: the base fabric slowed to the
        throttled emulation speed (pacing dominates the real copy)."""
        if self.throttle <= 0.0:
            return self.fabric
        return self.fabric.scaled(self.throttle)

    # -- transfers ---------------------------------------------------------
    def _transfer(self, kind: str, name: str,
                  payloads: dict[str, Any], pace: bool) -> dict[str, Any]:
        nbytes = int(sum(int(a.nbytes) for a in payloads.values()))
        with self.telemetry.wall_span(
                f"fabric.{kind}", record=kind, track=self.track, cat="io",
                stage=name, nbytes=nbytes):
            t0 = time.perf_counter()
            if pace:
                sleep_us = self.pace_us(kind, nbytes)
                if sleep_us > 0.0:
                    time.sleep(sleep_us * 1e-6)
            if kind == "read":
                out = {k: jax.device_put(a) for k, a in payloads.items()}
                for a in out.values():
                    a.block_until_ready()
            else:
                out = {k: np.asarray(a) for k, a in payloads.items()}
            us = (time.perf_counter() - t0) * 1e6
        with self._lock:
            self.n_ops += 1
            if kind == "read":
                self.bytes_read += nbytes
            else:
                self.bytes_written += nbytes
            if pace:
                self.measurements.append((kind, nbytes, us))
        return out

    def fetch(self, name: str, payloads: dict[str, np.ndarray],
              *, pace: bool = True) -> "Future[dict[str, jax.Array]]":
        """Post an async read (host → device); barrier = ``.result()``."""
        return self._pool.submit(self._transfer, "read", name, payloads, pace)

    def write(self, name: str, arrays: dict[str, Any],
              *, pace: bool = True) -> "Future[dict[str, np.ndarray]]":
        """Post an async write-back (device → host)."""
        return self._pool.submit(self._transfer, "write", name, arrays, pace)

    def measure_sweep(
        self,
        sizes_bytes: Sequence[int],
        *,
        kinds: Sequence[str] = ("read", "write"),
        repeats: int = 2,
        seed: int = 0,
    ) -> list[tuple[str, int, float]]:
        """Microbenchmark the real path; returns the new (kind, nbytes, us)
        samples (also appended to :attr:`measurements`)."""
        rng = np.random.default_rng(seed)
        before = len(self.measurements)
        for size in sizes_bytes:
            n = max(int(size) // 4, 1)
            host = rng.standard_normal(n).astype(np.float32)
            for _ in range(max(repeats, 1)):
                if "read" in kinds:
                    dev = self.fetch("sweep", {"x": host}).result()["x"]
                else:
                    dev = jax.device_put(host)
                if "write" in kinds:
                    self.write("sweep", {"x": dev}).result()
        with self._lock:
            return list(self.measurements[before:])

    def drain(self) -> None:
        """Wait until every posted op has retired (the commit fence)."""
        self._pool.submit(lambda: None).result()

    def close(self) -> None:
        self._pool.shutdown(wait=True)


@dataclasses.dataclass
class ExecResult:
    """One measured chain execution."""

    output: Any                        # final activation (jax array)
    elapsed_us: float                  # wall-clock, fetch warmup included
    stage_compute_us: dict[str, float]
    stage_wait_us: dict[str, float]    # barrier stalls per remote stage
    prefetch: bool
    fetched_bytes: int

    @property
    def compute_us(self) -> float:
        return sum(self.stage_compute_us.values())

    @property
    def stall_us(self) -> float:
        return sum(self.stage_wait_us.values())


@dataclasses.dataclass
class SimReport:
    """The simulator's prediction for the same chain + config."""

    predicted_us: float
    stage_stall_us: dict[str, float]
    stage_compute_us: dict[str, float]
    fabric_name: str
    prefetch: bool

    def error_vs(self, measured_us: float) -> float:
        """Relative prediction error against a wall-clock measurement."""
        return abs(self.predicted_us - measured_us) / max(measured_us, 1e-9)


class StreamingExecutor:
    """Wall-clock streaming execution of a tiered compute chain.

    The measured counterpart of ``DolmaRuntime``'s simulated loop: same
    structure (placement → per-stage fetch barrier → compute → optional
    commit; prefetch posted one stage ahead), but every duration is real.
    """

    def __init__(
        self,
        stages: Iterable[StreamStage],
        *,
        prefetch: bool = True,
        interpret: bool | None = None,
        engine: HostFetchEngine | None = None,
        fabric: FabricModel = INFINIBAND_100G,
        throttle: float = 1.0,
        chunk_bytes: int = DEFAULT_CHUNK_BYTES,
        telemetry: Telemetry | None = None,
        commit_output: bool = False,
    ) -> None:
        self.stages = list(stages)
        names = [st.name for st in self.stages]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate stage names: {names}")
        for st in self.stages:
            if st.op not in ("matmul", "attention"):
                raise ValueError(f"stage {st.name!r}: unknown op {st.op!r}")
        self.prefetch = prefetch
        self.interpret = resolve_interpret(interpret)
        self.telemetry = telemetry if telemetry is not None else NULL_TELEMETRY
        self.engine = engine or HostFetchEngine(
            fabric=fabric, throttle=throttle, chunk_bytes=chunk_bytes,
            telemetry=self.telemetry,
        )
        self.commit_output = commit_output
        self.track = "wall/exec"
        self._local_params: dict[int, dict[str, jax.Array]] = {}
        self._host_store: dict[int, dict[str, np.ndarray]] = {}
        self._place()

    # -- placement ---------------------------------------------------------
    def _place(self) -> None:
        """Materialize LOCAL params on device; REMOTE params stay host-side
        (the emulated remote data-object region)."""
        self._local_params.clear()
        self._host_store.clear()
        for i, st in enumerate(self.stages):
            if st.tier is Tier.REMOTE:
                self._host_store[i] = {
                    k: np.ascontiguousarray(a) for k, a in st.params.items()
                }
            else:
                self._local_params[i] = {
                    k: jax.device_put(np.asarray(a))
                    for k, a in st.params.items()
                }

    def plan_tiers(self, local_fraction: float,
                   *, policy: PlacementPolicy | None = None) -> PlacementPlan:
        """Decide which stages stream with the same placement policy the
        simulator uses (largest-remote-first over an object catalog), then
        re-seat the params. Returns the plan."""
        catalog = ObjectCatalog(
            DataObject(
                name=st.name,
                shape=(st.nbytes,),
                dtype=np.uint8,
                kind=ObjectKind.PARAM,
                n_reads=1,
                lifetime_iters=math.inf,
            )
            for st in self.stages
        )
        policy = policy or PlacementPolicy()
        plan = policy.plan(catalog, local_fraction=local_fraction)
        for st in self.stages:
            st.tier = plan.tier_of(st.name)
        self._place()
        return plan

    # -- execution ---------------------------------------------------------
    def _compute_stage(self, st: StreamStage,
                       params: dict[str, jax.Array], x: jax.Array):
        if st.op == "matmul":
            return ops.matmul(x, params["w"], interpret=self.interpret,
                              **st.kwargs)
        return ops.attention(x, params["k"], params["v"],
                             interpret=self.interpret, **st.kwargs)

    def warmup(self, x: np.ndarray) -> Any:
        """Run the chain once unpaced: populates jit caches and the device
        transfer path so measured runs don't pay compilation. Returns the
        final activation (which doubles as the untiered-oracle output)."""
        x = jax.device_put(np.asarray(x))
        for i, st in enumerate(self.stages):
            params = self._local_params.get(i)
            if params is None:
                params = self.engine.fetch(
                    st.name, self._host_store[i], pace=False
                ).result()
            x = self._compute_stage(st, params, x)
        jax.block_until_ready(x)
        return x

    def run(self, x: np.ndarray) -> ExecResult:
        """One measured pass over the chain. With ``prefetch`` on, remote
        stage *j*'s read is posted before stage *i*'s compute (i < j next
        remote); off, every read is a demand fetch the compute waits for."""
        with self.telemetry.wall_span("exec.pass"):
            return self._run(x)

    def _run(self, x: np.ndarray) -> ExecResult:
        tel = self.telemetry
        eng = self.engine
        with tel.wall_span("exec.input"):
            x = jax.device_put(np.asarray(x))
            jax.block_until_ready(x)
        remote = [i for i, st in enumerate(self.stages)
                  if st.tier is Tier.REMOTE]
        futures: dict[int, Future] = {}
        next_post = 0
        stage_wait: dict[str, float] = {}
        stage_compute: dict[str, float] = {}
        fetched = 0

        def post_next(after_i: int) -> None:
            nonlocal next_post
            while next_post < len(remote) and remote[next_post] <= after_i:
                next_post += 1
            if next_post < len(remote):
                j = remote[next_post]
                futures[j] = eng.fetch(
                    self.stages[j].name, self._host_store[j]
                )
                next_post += 1

        t_start = time.perf_counter()
        if self.prefetch and remote:
            # warmup fetch: the first remote stage cannot be hidden (§6.1)
            post_next(-1)
        for i, st in enumerate(self.stages):
            params = self._local_params.get(i)
            if st.tier is Tier.REMOTE:
                fut = futures.pop(i, None)
                if fut is None:  # demand fetch (prefetch off, or mispost)
                    fut = eng.fetch(st.name, self._host_store[i])
                t0 = time.perf_counter()
                with tel.wall_span("exec.barrier", record="stall:barrier",
                                   track=self.track, cat="stall",
                                   stage=st.name):
                    params = fut.result()  # the deferred access barrier
                stage_wait[st.name] = (time.perf_counter() - t0) * 1e6
                fetched += st.nbytes
                if self.prefetch:
                    # dual buffer: post the next remote read before computing
                    post_next(i)
            t0 = time.perf_counter()
            with tel.wall_span("exec.stage", record=f"compute:{st.name}",
                               track=self.track, cat="compute",
                               stage=st.name, op=st.op):
                with tel.wall_span("exec.dispatch", stage=st.name, op=st.op):
                    x = self._compute_stage(st, params, x)
                with tel.wall_span("exec.sync"):
                    jax.block_until_ready(x)
            stage_compute[st.name] = (time.perf_counter() - t0) * 1e6
        if self.commit_output:
            with tel.wall_span("exec.commit", record="commit",
                               track=self.track, cat="io"):
                eng.write("output", {"y": x}).result()
        return ExecResult(
            output=x,
            elapsed_us=(time.perf_counter() - t_start) * 1e6,
            stage_compute_us=stage_compute,
            stage_wait_us=stage_wait,
            prefetch=self.prefetch,
            fetched_bytes=fetched,
        )

    # -- the simulator, held to the same control flow ----------------------
    def simulate(
        self,
        *,
        compute_us: dict[str, float],
        fabric: FabricModel | None = None,
        prefetch: bool | None = None,
        telemetry: Telemetry | None = None,
        track_prefix: str = "sim",
        commit_bytes: int = 0,
    ) -> SimReport:
        """Charged-timeline replay of :meth:`run` on a fresh SimClock.

        ``compute_us`` holds the measured per-stage kernel times (from a
        prior :class:`ExecResult`); ``fabric`` is normally the *calibrated*
        model from :meth:`FabricResource.calibrate` — the default falls back
        to the engine's throttled base model. The prediction error of the
        returned report against the measured wall-clock is the simulator's
        credibility metric (``fig_measured_overlap`` sweeps it).
        """
        prefetch = self.prefetch if prefetch is None else prefetch
        model = fabric or self.engine.prediction_model()
        tel = telemetry if telemetry is not None else NULL_TELEMETRY
        clock = SimClock()
        qp = FabricResource(clock, model, name=f"{track_prefix}-qp",
                            telemetry=tel, track=f"{track_prefix}/fabric")
        tl = f"{track_prefix}/exec"
        remote = [i for i, st in enumerate(self.stages)
                  if st.tier is Tier.REMOTE]
        pending: dict[int, float] = {}
        next_post = 0
        stage_stall: dict[str, float] = {}
        stage_comp: dict[str, float] = {}

        def post_next(after_i: int) -> None:
            nonlocal next_post
            while next_post < len(remote) and remote[next_post] <= after_i:
                next_post += 1
            if next_post < len(remote):
                j = remote[next_post]
                _, end = qp.issue_stream(
                    "read", self.stages[j].nbytes, self.engine.chunk_bytes,
                    clock.now(tl), pipelined=True,
                )
                pending[j] = end
                next_post += 1

        if prefetch and remote:
            post_next(-1)
        for i, st in enumerate(self.stages):
            if st.tier is Tier.REMOTE:
                end = pending.pop(i, None)
                if end is None:
                    _, end = qp.issue_stream(
                        "read", st.nbytes, self.engine.chunk_bytes,
                        clock.now(tl), pipelined=True,
                    )
                t0 = clock.now(tl)
                t = clock.wait_until(tl, end)
                stage_stall[st.name] = t - t0
                if tel.enabled and t > t0:
                    tel.record_span("stall:barrier", track=tl, begin_us=t0,
                                    end_us=t, cat="stall", obj=st.name)
                if prefetch:
                    post_next(i)
            us = compute_us[st.name]
            t0 = clock.now(tl)
            t = clock.advance(tl, us)
            stage_comp[st.name] = us
            if tel.enabled and us > 0.0:
                tel.record_span(f"compute:{st.name}", track=tl, begin_us=t0,
                                end_us=t, cat="compute", op=st.op)
        if self.commit_output and commit_bytes > 0:
            _, end = qp.issue_stream("write", commit_bytes,
                                     self.engine.chunk_bytes,
                                     clock.now(tl), pipelined=True)
            clock.wait_until(tl, end)
        return SimReport(
            predicted_us=clock.now(tl),
            stage_stall_us=stage_stall,
            stage_compute_us=stage_comp,
            fabric_name=model.name,
            prefetch=prefetch,
        )


# -- chain builders (shared by tests, benchmarks, examples) ----------------
def matmul_chain(
    n_layers: int,
    *,
    m: int = 256,
    k: int = 512,
    n: int | None = None,
    dtype: Any = np.float32,
    seed: int = 0,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
) -> tuple[list[StreamStage], np.ndarray]:
    """A chain of square-ish streamed matmuls: x @ W0 @ W1 ... (K = N so the
    activation shape is stable across layers)."""
    n = k if n is None else n
    if n != k:
        raise ValueError(f"matmul_chain needs N == K to chain, got K={k} N={n}")
    rng = np.random.default_rng(seed)
    scale = 1.0 / np.sqrt(k)
    stages = [
        StreamStage(
            name=f"w{i}",
            op="matmul",
            params={"w": (rng.standard_normal((k, n)) * scale).astype(dtype)},
            kwargs={"block_m": block_m, "block_n": block_n, "block_k": block_k},
        )
        for i in range(n_layers)
    ]
    x0 = rng.standard_normal((m, k)).astype(dtype)
    return stages, x0


def attention_chain(
    n_layers: int,
    *,
    batch: int = 1,
    heads: int = 4,
    kv_heads: int | None = None,
    seq: int = 256,
    head_dim: int = 32,
    causal: bool = True,
    window: int | None = None,
    dtype: Any = np.float32,
    seed: int = 0,
    block_q: int = 128,
    block_k: int = 128,
) -> tuple[list[StreamStage], np.ndarray]:
    """A chain of attention stages whose K/V tensors are the streamed
    objects (the serving KV path); the query is the flowing activation."""
    kv = heads if kv_heads is None else kv_heads
    rng = np.random.default_rng(seed)
    stages = [
        StreamStage(
            name=f"kv{i}",
            op="attention",
            params={
                "k": rng.standard_normal(
                    (batch, seq, kv, head_dim)).astype(dtype),
                "v": rng.standard_normal(
                    (batch, seq, kv, head_dim)).astype(dtype),
            },
            kwargs={"causal": causal, "window": window,
                    "block_q": block_q, "block_k": block_k},
        )
        for i in range(n_layers)
    ]
    q0 = rng.standard_normal((batch, seq, heads, head_dim)).astype(dtype)
    return stages, q0


def untiered_oracle(stages: Sequence[StreamStage], x: np.ndarray,
                    *, interpret: bool | None = None) -> np.ndarray:
    """All-local reference run: identical kernels, no streaming — the
    bit-identity ground truth for every measured configuration."""
    oracle = StreamingExecutor(
        [dataclasses.replace(st, tier=Tier.LOCAL) for st in stages],
        prefetch=False, interpret=interpret, throttle=0.0,
    )
    try:
        return np.asarray(oracle.warmup(x))
    finally:
        oracle.engine.close()


def balanced_throttle(
    stages: Sequence[StreamStage],
    compute_us: dict[str, float],
    *,
    fabric: FabricModel = INFINIBAND_100G,
    chunk_bytes: int = DEFAULT_CHUNK_BYTES,
    ratio: float = 1.0,
) -> float:
    """Throttle that makes the mean modeled fetch of the remote stages take
    ``ratio`` x their mean measured compute — the balanced operating point
    where overlap matters most (ideal prefetch speedup → 1 + ratio)."""
    remote = [st for st in stages if st.tier is Tier.REMOTE]
    if not remote:
        raise ValueError("balanced_throttle: no REMOTE stages to pace")
    fetch = [
        fabric.stream_us("read", st.nbytes, chunk_bytes, mode="pipelined")
        for st in remote
    ]
    comp = [compute_us[st.name] for st in remote]
    mean_fetch = sum(fetch) / len(fetch)
    mean_comp = sum(comp) / len(comp)
    if mean_fetch <= 0.0:
        raise ValueError("balanced_throttle: modeled fetch time is zero")
    return ratio * mean_comp / mean_fetch
