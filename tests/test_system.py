"""End-to-end behaviour tests: train -> crash -> resume -> serve, watchdog,
straggler handling. These exercise the same code paths the launchers use."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ParallelConfig, TrainConfig, get_config
from repro.data.pipeline import DataConfig, SyntheticPacked
from repro.launch.mesh import make_local_mesh
from repro.models import build_model
from repro.serve import Request, ServeEngine
from repro.train.fault_tolerance import FailureInjector, StepTimeout, Watchdog
from repro.train.loop import run_training


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-7b").reduced()
    lm = build_model(cfg)
    mesh = make_local_mesh(1, 1)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=4, seed=0)
    src = SyntheticPacked(dcfg)
    batches = {s: {"tokens": jnp.asarray(src.batch(s)["tokens"])} for s in range(40)}
    return cfg, lm, mesh, batches


def _tcfg(d, steps, **kw):
    base = dict(
        lr=2e-3, total_steps=steps, warmup_steps=2, checkpoint_every=5,
        checkpoint_dir=str(d), keep_checkpoints=2,
    )
    base.update(kw)
    return TrainConfig(**base)


def test_loss_decreases_over_training(setup, tmp_path):
    cfg, lm, mesh, batches = setup
    fixed = batches[0]
    res = run_training(
        lm, _tcfg(tmp_path, 25), ParallelConfig(), mesh,
        make_batch=lambda s: fixed, log_every=0,
    )
    assert res.losses[-1] < res.losses[0] - 1.0


def test_crash_checkpoint_resume(setup, tmp_path):
    cfg, lm, mesh, batches = setup
    inj = FailureInjector(crash_at=(12,))
    # The failure propagates after the last completed step is checkpointed.
    with pytest.raises(RuntimeError, match="injected"):
        run_training(
            lm, _tcfg(tmp_path, 20), ParallelConfig(), mesh,
            make_batch=lambda s: batches[s], injector=inj, log_every=0,
        )
    from repro.train.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 11
    # resume picks up from the last checkpoint and finishes
    res2 = run_training(
        lm, _tcfg(tmp_path, 20), ParallelConfig(), mesh,
        make_batch=lambda s: batches[s], log_every=0,
    )
    assert res2.resumed_from == 11
    assert res2.final_step == 19


def test_failed_checkpoint_after_a_crash_keeps_the_step_error(
    setup, tmp_path, monkeypatch
):
    """After a device error the donated state may be unsaveable: the save's
    own failure is logged, and the step's error is what propagates."""
    from repro.train.checkpoint import CheckpointManager

    cfg, lm, mesh, batches = setup

    def broken_save(self, *a, **k):
        raise OSError("state buffers were donated")

    monkeypatch.setattr(CheckpointManager, "save", broken_save)
    with pytest.raises(RuntimeError, match="injected"):
        run_training(
            lm, _tcfg(tmp_path, 4, checkpoint_every=0), ParallelConfig(), mesh,
            make_batch=lambda s: batches[s],
            injector=FailureInjector(crash_at=(2,)), log_every=0,
        )


def test_straggler_watchdog_retries(setup, tmp_path):
    cfg, lm, mesh, batches = setup

    class SlowOnce:
        fired = False

        def maybe_fail(self, step):
            import time
            if step == 3 and not self.fired:
                self.fired = True
                time.sleep(1.2)

    res = run_training(
        lm, _tcfg(tmp_path, 6), ParallelConfig(), mesh,
        make_batch=lambda s: batches[s], injector=SlowOnce(),
        step_timeout_s=1.0, log_every=0,
    )
    assert res.final_step == 5  # retried step completed the run


def test_watchdog_unit():
    import time
    with pytest.raises(StepTimeout):
        with Watchdog(0.05):
            time.sleep(0.2)
    with Watchdog(5.0):
        pass  # no timeout


def test_microbatching_matches_full_batch(setup, tmp_path):
    cfg, lm, mesh, batches = setup
    from repro.train.step import make_train_state, make_train_step

    tcfg = TrainConfig(lr=1e-3, warmup_steps=0)
    batch = batches[0]
    losses = {}
    for micro in (1, 4):
        pcfg = ParallelConfig(microbatches=micro)
        with jax.set_mesh(mesh):
            state = make_train_state(lm, tcfg, jax.random.PRNGKey(0))
            _, compile_step = make_train_step(lm, tcfg, pcfg, mesh)
            compiled = compile_step(state, batch)
            state, m = compiled(state, batch)
            state, m = compiled(state, batch)
            losses[micro] = float(m["loss"])
    assert abs(losses[1] - losses[4]) < 5e-3, losses


def test_train_then_serve(setup, tmp_path):
    cfg, lm, mesh, batches = setup
    fixed = batches[0]
    res = run_training(
        lm, _tcfg(tmp_path, 15), ParallelConfig(), mesh,
        make_batch=lambda s: fixed, log_every=0,
    )
    from repro.train.checkpoint import restore_pytree
    params0 = lm.init(jax.random.PRNGKey(0))
    state, _ = restore_pytree({"params": params0}, str(tmp_path))
    eng = ServeEngine(lm, state["params"], batch_size=2, max_len=128)
    prompt = np.asarray(fixed["tokens"][0, :8], np.int32)
    out = eng.generate([Request(tokens=prompt, max_new_tokens=8)])
    assert out[0].steps >= 1
    assert np.isfinite(out[0].tokens).all()
