"""Checkpoint manager + fault-tolerance runtime tests."""
import os
import time

import jax
from jax.sharding import AxisType
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import manager as ckpt
from repro.runtime import elastic, fault_tolerance as ft


def _state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (4, 8)), "b": jnp.zeros(8)},
        "step": jnp.asarray(7, jnp.int32),
    }


def test_checkpoint_roundtrip(tmp_path):
    s = _state()
    ckpt.save(s, str(tmp_path), 7)
    r = ckpt.restore(str(tmp_path), s)
    for (pa, la), (pb, lb) in zip(
        jax.tree_util.tree_flatten_with_path(s)[0],
        jax.tree_util.tree_flatten_with_path(r)[0],
    ):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_checkpoint_latest_and_gc(tmp_path):
    s = _state()
    for step in (1, 2, 3, 4, 5):
        ckpt.save(s, str(tmp_path), step, keep_last=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    kept = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(kept) == 2


def test_checkpoint_atomicity(tmp_path):
    """A leftover .tmp dir must not be treated as a checkpoint."""
    s = _state()
    ckpt.save(s, str(tmp_path), 3)
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert ckpt.latest_step(str(tmp_path)) == 3


def test_checkpoint_async(tmp_path):
    s = _state()
    t = ckpt.save_async(s, str(tmp_path), 11)
    t.join(timeout=30)
    assert ckpt.latest_step(str(tmp_path)) == 11


def test_elastic_restore_resharded(tmp_path):
    """Restore onto a different (1-device) mesh with NamedSharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    s = _state()
    ckpt.save(s, str(tmp_path), 1)
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), s)
    r = ckpt.restore(str(tmp_path), s, shardings=sh)
    assert r["params"]["w"].sharding.mesh.shape == {"data": 1, "model": 1}


def test_heartbeat_monitor():
    hb = ft.HeartbeatMonitor(["w0", "w1"], timeout_s=0.05)
    hb.beat("w0")
    time.sleep(0.08)
    hb.beat("w1")
    assert hb.dead_workers() == {"w0"}


def test_straggler_detector():
    sd = ft.StragglerDetector([f"w{i}" for i in range(8)], min_steps=3)
    for step in range(5):
        for i in range(8):
            sd.record(f"w{i}", 1.0 + (3.0 if i == 5 else 0.0) + 0.01 * step)
    assert sd.stragglers() == {"w5"}


def test_straggler_no_false_positive():
    sd = ft.StragglerDetector([f"w{i}" for i in range(8)], min_steps=3)
    rng = np.random.default_rng(0)
    for _ in range(10):
        for i in range(8):
            sd.record(f"w{i}", 1.0 + rng.normal() * 0.02)
    assert sd.stragglers() == set()


def test_run_with_restarts_resumes_from_checkpoint(tmp_path):
    """Injected crash at step 20 -> restore from the step-10 checkpoint;
    the trajectory (deterministic data) completes to 50."""
    trained = []
    saved = {"step": 0}

    def train_some(start, n):
        for s in range(start, start + n):
            trained.append(s)
        return start + n

    def save(step):
        saved["step"] = step

    def restore():
        return saved["step"]

    out = ft.run_with_restarts(
        train_some_steps=train_some,
        save_ckpt=save,
        restore_ckpt=restore,
        total_steps=50,
        ckpt_every=10,
        failure_at={20: ft.FailureEvent(step=20, kind="crash", workers={"h3"})},
    )
    assert out["final_step"] == 50
    assert out["restarts"] == 1
    # steps 20..29 were re-trained after restore (deterministic replay)
    assert trained.count(25) == 1 and trained.count(5) == 1


def test_elastic_mesh_proposal():
    shape, axes = elastic.propose_mesh_shape(512, preferred_model=16, want_pod_axis=True)
    assert shape == (2, 16, 16) and axes == ("pod", "data", "model")
    shape, axes = elastic.propose_mesh_shape(448, preferred_model=16)  # lost a pod slice
    assert shape == (28, 16)
    shape, axes = elastic.propose_mesh_shape(24, preferred_model=16)
    assert shape[0] * shape[1] == 24  # degrade model axis to keep all chips


def test_end_to_end_restart_with_real_checkpoints(tmp_path):
    """Real train steps + real checkpoints + injected failure."""
    from repro.configs.base import get_config, reduced
    from repro.data.pipeline import DataConfig, Pipeline
    from repro.train import loop as train_loop, state as train_state

    cfg = reduced(get_config("stablelm-1.6b"))
    pipe = Pipeline(DataConfig(global_batch=2, seq_len=16, vocab_size=cfg.vocab_size))
    step_fn = jax.jit(train_loop.make_train_step(cfg, total_steps=12, remat=False))
    box = {"state": train_state.init_state(jax.random.PRNGKey(0), cfg)}

    def train_some(start, n):
        for s in range(start, start + n):
            batch = {k: jnp.asarray(v) for k, v in pipe.batch(s).items()}
            box["state"], _ = step_fn(box["state"], batch)
        return start + n

    def save(step):
        ckpt.save(box["state"], str(tmp_path), step)

    def restore():
        box["state"] = ckpt.restore(str(tmp_path), box["state"])
        return int(box["state"].step)

    out = ft.run_with_restarts(
        train_some_steps=train_some, save_ckpt=save, restore_ckpt=restore,
        total_steps=12, ckpt_every=4,
        failure_at={8: ft.FailureEvent(step=8, kind="crash")},
    )
    assert out["final_step"] == 12 and out["restarts"] == 1
    assert int(box["state"].step) == 12


# --------------------------------------------------------------------------
# Elastic training runtime (repro.training): deterministic fault injection,
# checkpointed recovery with bitwise replay, corrupt-checkpoint fallback
# --------------------------------------------------------------------------

from repro import training


def _toy_harness(ckpt_dir, *, total=12, ckpt_every=3, faults=None,
                 telemetry=None, max_restarts=8):
    """A tiny pure-jnp training problem: fast, deterministic, bitwise."""

    @jax.jit
    def step_fn(state, batch):
        p = state["p"] - 0.1 * jnp.tanh(state["p"] * batch["x"])
        return ({"p": p, "step": state["step"] + 1},
                {"loss": jnp.sum(p * p)})

    def batch_fn(step):
        rng = np.random.default_rng((5, step))
        return {"x": jnp.asarray(rng.standard_normal(4).astype(np.float32))}

    def init_fn():
        return {"p": jnp.ones(4, jnp.float32),
                "step": jnp.zeros((), jnp.int32)}

    cfg = training.HarnessConfig(
        total_steps=total, ckpt_every=ckpt_every, ckpt_dir=ckpt_dir,
        max_restarts=max_restarts, async_ckpt=False)
    return training.TrainingHarness(
        step_fn=step_fn, batch_fn=batch_fn, init_fn=init_fn, config=cfg,
        faults=faults, telemetry=telemetry)


def test_restore_latest_valid_skips_corrupt(tmp_path):
    s = _state()
    ckpt.save(s, str(tmp_path), 2)
    ckpt.save(s, str(tmp_path), 4)
    assert training.corrupt_latest_checkpoint(str(tmp_path)) is not None
    state, step, skipped = ckpt.restore_latest_valid(str(tmp_path), s)
    assert step == 2
    assert [st for st, _ in skipped] == [4]
    np.testing.assert_array_equal(np.asarray(state["params"]["b"]),
                                  np.asarray(s["params"]["b"]))


def test_restore_latest_valid_skips_missing_leaf(tmp_path):
    """A torn write that lost a leaf file entirely is also 'corrupt'."""
    s = _state()
    ckpt.save(s, str(tmp_path), 1)
    ckpt.save(s, str(tmp_path), 3)
    os.remove(tmp_path / "step_00000003" / "leaf_00000.npy")
    _, step, skipped = ckpt.restore_latest_valid(str(tmp_path), s)
    assert step == 1 and [st for st, _ in skipped] == [3]


def test_restore_latest_valid_all_corrupt_raises(tmp_path):
    s = _state()
    ckpt.save(s, str(tmp_path), 5)
    training.corrupt_latest_checkpoint(str(tmp_path))
    with pytest.raises(FileNotFoundError) as ei:
        ckpt.restore_latest_valid(str(tmp_path), s)
    assert "5" in str(ei.value)  # names what it skipped


def test_corrupt_latest_checkpoint_empty_dir_returns_none(tmp_path):
    """No checkpoints yet -> nothing to corrupt, and no crash.

    Regression: the chaos harness calls ``corrupt_latest_checkpoint``
    unconditionally at boot; on a fresh run the checkpoint dir is empty
    (or absent) and the injector must report 'no-op', not raise.
    """
    assert training.corrupt_latest_checkpoint(str(tmp_path)) is None
    assert training.corrupt_latest_checkpoint(str(tmp_path / "missing")) is None


def test_corrupt_latest_checkpoint_skips_junk_entries(tmp_path):
    """Non-``step_NNN`` entries (and ``step_final``) must not break the
    latest-step scan — only numeric step dirs are candidates."""
    (tmp_path / "tmp_write").mkdir()
    (tmp_path / "step_final").mkdir()
    (tmp_path / "step_final" / "manifest.json").write_text("{}")
    (tmp_path / "notes.txt").write_text("x")
    # junk only -> still nothing corruptible
    assert training.corrupt_latest_checkpoint(str(tmp_path)) is None
    s = _state()
    ckpt.save(s, str(tmp_path), 7)
    hit = training.corrupt_latest_checkpoint(str(tmp_path))
    assert hit is not None and "step_00000007" in hit


def test_fault_schedule_spec_and_fire_once():
    fs = training.FaultSchedule.from_spec("host_loss@5, corrupt_ckpt@9")
    assert fs.take(4) is None
    ev = fs.take(5)
    assert ev is not None and ev.kind == "host_loss"
    assert fs.take(5) is None  # fires exactly once
    with pytest.raises(ValueError):
        training.FaultSchedule.from_spec("melted@3")
    with pytest.raises(ValueError):
        training.FaultSchedule(
            [training.FaultEvent(2, "preempt"), training.FaultEvent(2, "host_loss")])


def test_fault_schedule_seeded_is_reproducible():
    a = training.FaultSchedule.generate(11, 40, n_faults=3)
    b = training.FaultSchedule.generate(11, 40, n_faults=3)
    assert a.describe() == b.describe()
    assert len(a.events) == 3
    assert all(1 <= s < 40 for s in a.events)
    c = training.FaultSchedule.generate(12, 40, n_faults=3)
    assert c.describe() != a.describe()  # the seed is the schedule


def test_fault_schedule_generate_validates_inputs():
    """Regression: ``generate(kinds=())`` used to reach the rng draw and
    die with ZeroDivisionError; bad inputs must fail up front with a
    ValueError that names the legal kinds."""
    with pytest.raises(ValueError, match="at least one fault kind"):
        training.FaultSchedule.generate(0, 40, n_faults=2, kinds=())
    with pytest.raises(ValueError, match="unknown fault kind"):
        training.FaultSchedule.generate(0, 40, n_faults=2,
                                        kinds=("host_loss", "melted"))
    with pytest.raises(ValueError, match="n_faults"):
        training.FaultSchedule.generate(0, 40, n_faults=-1)
    # a kinds subset is still a legal (and now validated) call
    fs = training.FaultSchedule.generate(3, 40, n_faults=2,
                                         kinds=("preempt",))
    assert all(e.kind == "preempt" for e in fs.events.values())


def test_harness_kill_and_resume_is_bitwise(tmp_path):
    """Stop the loop at step 5; a FRESH harness on the same ckpt dir
    must continue to a loss trajectory bitwise equal to an
    uninterrupted run."""
    ref = _toy_harness(None).run()
    assert ref["final_step"] == 12 and ref["restarts"] == 0

    d = str(tmp_path / "ck")
    half = _toy_harness(d, total=5).run()
    assert half["final_step"] == 5
    resumed = _toy_harness(d).run()  # fresh harness = simulated new process
    assert min(resumed["losses"]) == 5  # resumed at the checkpoint, not 0
    for s in range(5, 12):
        assert resumed["losses"][s] == ref["losses"][s]


def test_harness_preemption_recovers_bitwise(tmp_path):
    ref = _toy_harness(None).run()
    faults = training.FaultSchedule.from_spec("preempt@7")
    out = _toy_harness(str(tmp_path / "ck"), faults=faults).run()
    assert out["restarts"] == 1
    [rec] = out["recovery_log"]
    assert rec["kind"] == "preempt" and rec["failed_step"] == 7
    assert rec["resumed_from"] == 6  # newest ckpt (ckpt_every=3)
    assert out["losses"] == ref["losses"]  # full bitwise continuity


def test_harness_corrupt_ckpt_falls_back_to_previous_step(tmp_path):
    """corrupt_ckpt kills the newest checkpoint with the process: the
    recovery must skip it and resume from the PREVIOUS step."""
    ref = _toy_harness(None).run()
    faults = training.FaultSchedule.from_spec("corrupt_ckpt@7")
    out = _toy_harness(str(tmp_path / "ck"), faults=faults).run()
    assert out["restarts"] == 1
    [rec] = out["recovery_log"]
    assert rec["resumed_from"] == 3  # step-6 ckpt was corrupted -> step 3
    assert rec["ckpt_skipped"] == [6]
    assert out["losses"] == ref["losses"]


def test_harness_identical_recovery_decisions_across_runs(tmp_path):
    """Acceptance: the same seeded schedule reproduces IDENTICAL
    recovery decisions across two runs."""
    outs = []
    for run in ("a", "b"):
        faults = training.FaultSchedule.generate(3, 12, n_faults=2)
        outs.append(_toy_harness(str(tmp_path / run), faults=faults).run())
    assert outs[0]["recovery_log"] == outs[1]["recovery_log"]
    assert outs[0]["restarts"] == outs[1]["restarts"] >= 1
    assert outs[0]["losses"] == outs[1]["losses"]


def test_harness_max_restarts_bounds_the_loop(tmp_path):
    faults = training.FaultSchedule.from_spec("host_loss@2,host_loss@4")
    with pytest.raises(RuntimeError, match="max_restarts"):
        _toy_harness(None, faults=faults, max_restarts=1).run()


def test_harness_telemetry_payload(tmp_path):
    rec = training.StepTimeRecorder(tokens_per_step=128,
                                    config={"arch": "toy"})
    faults = training.FaultSchedule.from_spec("preempt@7")
    _toy_harness(str(tmp_path / "ck"), faults=faults, telemetry=rec).run()
    payload = rec.payload()
    assert payload["bench"] == "train_runtime"
    assert payload["config"] == {"arch": "toy"}
    res = payload["results"]
    # 12 committed steps + 1 replayed (7 computed twice: preempted, redone)
    assert res["steps"] == 13
    assert res["recoveries"] == 1 and len(res["recovery_latency_s"]) == 1
    assert res["tokens_per_sec"] > 0
    assert {r["step"] for r in payload["trajectory"]} == set(range(12))
    [ev] = payload["events"]
    assert ev["kind"] == "recovery" and "preempt@7" in ev["detail"]
    out = rec.write(str(tmp_path / "BENCH_train.json"))
    import json as _json
    with open(out) as f:
        assert _json.load(f)["bench"] == "train_runtime"
