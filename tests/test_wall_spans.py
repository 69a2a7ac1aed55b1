"""Wall spans as profiler annotations: the executor's pass and the
scheduler's step record their ``dolma:`` spans, nested as the work nests,
on the profiler's clock; the fetch worker's spans sit on their own thread;
and a disabled telemetry still records nothing in memory."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData, ProfileOptions

from repro.configs import get_config, reduced_config
from repro.core.exec import StreamingExecutor, matmul_chain
from repro.core.metadata import Tier
from repro.core.telemetry import NULL_TELEMETRY, Telemetry
from repro.models import get_model
from repro.serving import (
    ContinuousScheduler,
    EngineConfig,
    Request,
    SchedulerConfig,
    ServingEngine,
)

MARK = "test:driving"


def _traced(log_dir, fn):
    """Run ``fn`` under the profiler on this thread, inside a ``MARK``
    annotation. Returns the ``dolma:`` spans of each host thread as
    ``(name, start_ns, end_ns, stats)`` lists: ``(driving, others)``."""
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(MARK):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    driving, others = None, []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats)) for e in evs if e.name.startswith("dolma:")]
            if any(e.name == MARK for e in evs):
                driving = spans
            elif spans:
                others.append(spans)
    assert driving is not None
    return driving, others


def _named(spans, name):
    return [s for s in spans if s[0] == "dolma:" + name]


def _inside(child, parents):
    """Each ``child`` span lies within one of ``parents``."""
    return all(any(p[1] <= c[1] and c[2] <= p[2] for p in parents)
               for c in child)


@pytest.fixture
def streamed_chain():
    """Three stages, the first two streamed, warmed so nothing compiles
    under the profiler."""
    stages, x0 = matmul_chain(3, m=128, k=128, seed=0, block_m=128,
                              block_n=128, block_k=128)
    ex = StreamingExecutor(stages, throttle=0.0)
    for st, tier in zip(stages, (Tier.REMOTE, Tier.REMOTE, Tier.LOCAL)):
        st.tier = tier
    ex._place()
    ex.warmup(x0)
    ex.run(x0)
    yield ex, x0
    ex.engine.close()


def test_executor_pass_spans(tmp_path, streamed_chain):
    ex, x0 = streamed_chain
    driving, others = _traced(tmp_path, lambda: ex.run(x0))
    (pass_,) = _named(driving, "exec.pass")
    barriers = _named(driving, "exec.barrier")
    dispatch = _named(driving, "exec.dispatch")
    sync = _named(driving, "exec.sync")
    assert [b[3]["stage"] for b in barriers] == ["w0", "w1"]
    assert [d[3]["stage"] for d in dispatch] == ["w0", "w1", "w2"]
    assert {d[3]["op"] for d in dispatch} == {"matmul"}
    assert len(sync) == 3 and len(_named(driving, "exec.input")) == 1
    assert _inside(barriers + dispatch + sync, [pass_])
    assert _inside(dispatch + sync, _named(driving, "exec.stage"))
    # the copies run on the fetch worker's thread, never the driving one
    assert not _named(driving, "fabric.read")
    (reads,) = [_named(o, "fabric.read") for o in others
                if _named(o, "fabric.read")]
    assert [(r[3]["stage"], r[3]["nbytes"]) for r in reads] == [
        (st.name, st.nbytes) for st in ex.stages[:2]]


def test_scheduler_step_spans(tmp_path):
    cfg = reduced_config(get_config("granite-8b"), dtype=jnp.float32)
    params = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32))
    sched = ContinuousScheduler(eng, SchedulerConfig(readvise_every=0))
    sched.submit(Request(tenant="a", prompt=np.array([3, 5, 7], np.int32),
                         max_new=4))
    sched.step()   # compiles the step outside the trace
    driving, _ = _traced(tmp_path, sched.step)
    (step,) = _named(driving, "sched.step")
    (lanes,) = _named(driving, "decode.lanes")
    assert lanes[3]["active"] == 1
    assert _inside([lanes], [step])
    dispatch, readback = (_named(driving, n)
                          for n in ("decode.dispatch", "decode.readback"))
    assert len(dispatch) == len(readback) == 1
    assert _inside(dispatch + readback, [lanes])
    assert dispatch[0][2] <= readback[0][1]
    assert _inside([s for n in ("sched.grant", "sched.feed", "sched.collect")
                    for s in _named(driving, n)], [step])


def test_disabled_telemetry_keeps_nothing_in_memory(streamed_chain):
    """The executor and the scheduler default to ``NULL_TELEMETRY``: their
    spans reach the profiler only."""
    ex, x0 = streamed_chain
    assert ex.telemetry is NULL_TELEMETRY
    ex.run(x0)
    cfg = reduced_config(get_config("granite-8b"), dtype=jnp.float32)
    params = get_model(cfg).init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, EngineConfig(max_batch=2, max_len=32))
    sched = ContinuousScheduler(eng, SchedulerConfig(readvise_every=1))
    assert sched.telemetry is NULL_TELEMETRY
    sched.submit(Request(tenant="a", prompt=np.array([3, 5], np.int32),
                         max_new=2))
    sched.drain()
    assert not NULL_TELEMETRY.spans and not NULL_TELEMETRY.instants
    assert not NULL_TELEMETRY.counters and not NULL_TELEMETRY.gauges


def test_enabled_telemetry_keeps_the_wall_tracks(streamed_chain):
    """Enabled, the same spans also land in memory under their wall-track
    names; spans without a ``record`` name stay in the profiler only."""
    ex, x0 = streamed_chain
    tel = Telemetry()
    ex.telemetry = ex.engine.telemetry = tel
    ex.run(x0)
    names = [s.name for s in tel.spans_on("wall/exec")]
    assert names.count("stall:barrier") == 2
    assert [n for n in names if n.startswith("compute:")] == [
        "compute:w0", "compute:w1", "compute:w2"]
    reads = tel.spans_on("wall/fabric", cats=("io",))
    assert [(s.args["stage"], s.args["nbytes"]) for s in reads] == [
        (st.name, st.nbytes) for st in ex.stages[:2]]
    assert tel.tracks() == ["wall/exec", "wall/fabric"]
    assert not tel.counters
