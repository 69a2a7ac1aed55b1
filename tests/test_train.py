"""Training loop: convergence, microbatching, checkpoint/restart, faults."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.optim import AdamWConfig, CompressionConfig
from repro.train.loop import LoopConfig, train
from repro.train.step import TrainStepConfig, init_train_state, make_train_step


@pytest.fixture(scope="module")
def tiny_cfg():
    return reduced_config(get_config("granite-8b"), dtype=jnp.float32,
                          n_layers=2, vocab_size=128)


def test_loss_decreases(tiny_cfg, tmp_path):
    res = train(
        tiny_cfg,
        TrainStepConfig(remat="full"),
        AdamWConfig(lr=3e-3, warmup_steps=5, decay_steps=100),
        LoopConfig(steps=25, batch=4, seq=32, log_every=100),
    )
    first = np.mean(res.losses[:5])
    last = np.mean(res.losses[-5:])
    assert last < first - 0.2, f"no learning: {first:.3f} -> {last:.3f}"


def test_microbatching_matches_full_batch(tiny_cfg):
    opt = AdamWConfig(lr=1e-3, warmup_steps=0)
    from repro.models import make_batch

    params, opt_state = init_train_state(
        jax.random.PRNGKey(0), tiny_cfg, TrainStepConfig(), opt
    )
    batch = make_batch(tiny_cfg, jax.random.PRNGKey(1), 8, 32)

    step_full = make_train_step(tiny_cfg, TrainStepConfig(microbatches=1), opt)
    step_mb = make_train_step(tiny_cfg, TrainStepConfig(microbatches=4), opt)
    p1, _, m1 = step_full(params, opt_state, batch)
    p2, _, m2 = step_mb(params, opt_state, batch)
    assert jnp.allclose(m1["loss"], m2["loss"], rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-4)


def test_compression_path_trains(tiny_cfg):
    res = train(
        tiny_cfg,
        TrainStepConfig(compression=CompressionConfig(enabled=True)),
        AdamWConfig(lr=3e-3, warmup_steps=5),
        LoopConfig(steps=12, batch=4, seq=32, log_every=100),
    )
    assert np.isfinite(res.losses).all()


def test_checkpoint_restart_resumes_exactly(tiny_cfg, tmp_path):
    """Fault tolerance: a killed run resumes bit-exactly from the ckpt."""
    opt = AdamWConfig(lr=1e-3, warmup_steps=0)
    common = dict(batch=4, seq=32, log_every=100, ckpt_every=10,
                  ckpt_dir=str(tmp_path / "ckpt"))

    # uninterrupted reference run
    ref = train(tiny_cfg, TrainStepConfig(), opt,
                LoopConfig(steps=20, ckpt_dir=None, **{k: v for k, v in
                                                       common.items()
                                                       if k != "ckpt_dir"}))

    # run that dies at step 13 (after the step-10 checkpoint)
    class Boom(Exception):
        pass

    def bomb(step):
        if step == 13:
            raise Boom()

    with pytest.raises(Boom):
        train(tiny_cfg, TrainStepConfig(), opt,
              LoopConfig(steps=20, **common), fault_hook=bomb)

    resumed = train(tiny_cfg, TrainStepConfig(), opt,
                    LoopConfig(steps=20, **common))
    assert resumed.restored_from == 10
    # the data stream is deterministic in step => identical trajectory
    np.testing.assert_allclose(resumed.losses[-1], ref.losses[-1], rtol=1e-4)


def test_straggler_watchdog_detects(monkeypatch, tiny_cfg):
    """Inject a 10s stall into exactly one step's measured duration."""
    import time as _time

    orig = _time.perf_counter
    state = {"phase": 0}

    def fake_counter():
        t = orig()
        if state["phase"] == 1:     # t0 of the step after the hook fired
            state["phase"] = 2
            return t
        if state["phase"] >= 2:     # its dt measurement (+ keep the offset
            state["phase"] = 3      # so later deltas are normal again)
            return t + 10.0
        return t

    monkeypatch.setattr("repro.train.loop.time.perf_counter", fake_counter)

    def hook(step):
        if step == 15 and state["phase"] == 0:
            state["phase"] = 1

    res = train(tiny_cfg, TrainStepConfig(), AdamWConfig(),
                LoopConfig(steps=20, batch=2, seq=16, log_every=100),
                fault_hook=hook)
    assert any(e["step"] >= 15 for e in res.straggler_events)


def test_mesh_run_places_state_and_resumes(tiny_cfg, tmp_path):
    """Under a mesh the loop creates params in their NamedSharding, keeps
    that layout through the step and a checkpoint restore, and computes
    the same losses as the meshless run."""
    from jax.sharding import NamedSharding

    from repro.launch.mesh import make_mesh
    from repro.models.sharding import use_mesh

    opt = AdamWConfig(lr=1e-3, warmup_steps=0)
    loop = dict(steps=8, batch=2, seq=16, log_every=100)
    ref = train(tiny_cfg, TrainStepConfig(), opt, LoopConfig(**loop))

    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    ckpt = dict(ckpt_dir=str(tmp_path / "ckpt"), ckpt_every=4)

    class Stop(Exception):
        pass

    def stop_at_6(step):
        if step == 6:
            raise Stop()

    with use_mesh(mesh):
        with pytest.raises(Stop):
            train(tiny_cfg, TrainStepConfig(), opt,
                  LoopConfig(**loop, **ckpt), fault_hook=stop_at_6)
        resumed = train(tiny_cfg, TrainStepConfig(), opt,
                        LoopConfig(**loop, **ckpt))
    assert resumed.restored_from == 4
    for leaf in jax.tree.leaves(resumed.params):
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.mesh == mesh
    np.testing.assert_allclose(resumed.losses, ref.losses[4:], rtol=1e-5)
