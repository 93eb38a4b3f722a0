"""The ``bevformer`` family through the harness on the CPU: a tiny
BEVFormer cell added to the tiny checkout (``chipbench_tiny.py``) by new
files and ``BENCHMARK.json`` entries alone, as a model family joins the
benchmark, run through ``chipbench.run.main`` with the look for a chip
skipped.

The tiny size keeps every head width of ``bevformer-base`` (d=256, 8
heads of 32, FFN 512, 8 SCA points over 4 pillar anchors, 4 TSA and
decoder points, 10 classes of code size 10) and cuts the BEV to 8x8, the
rig to 2 cameras of a 128x192 image with 2 levels, the depth to 2+2
layers and the object queries to 20."""
import copy
import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import pytest

import chipbench_tiny
from chipbench import catalog, run

REPO = chipbench_tiny.REPO
BASE = catalog.config(catalog.benchmark(REPO), "bevformer-base", REPO)

TINY_CONFIG = dict(
    copy.deepcopy(BASE), name="tiny-bev", levels=[[16, 24], [8, 12]],
    num_cams=2, image_size=[128, 192], input_size=[128, 192],
    encoder_layers=2, decoder_layers=2, bev_h=8, bev_w=8, num_queries=20,
    rig={"frame": BASE["rig"]["frame"], "cameras": [
        {"name": "FRONT", "yaw_deg": 0.0, "position": [0.7, 0.0, -0.3],
         "f": 150.0, "cx": 96.0, "cy": 64.0},
        {"name": "BACK", "yaw_deg": 180.0, "position": [-0.9, 0.0, -0.3],
         "f": 100.0, "cx": 96.0, "cy": 64.0}]})
TINY_TRAFFIC = dict(catalog.traffic("scene-b1", REPO), scene_frames=5,
                    distinct_frames=3, box_xy=30.0)
# Set like a cell's limit (lower^0.4 x upper^0.6), on the CPU (the
# program's `cpu` MSDA backend): the program's largest reading over seeds
# 11..22, and the smallest over seeds 11..13 of the float8 control and of
# the reference run with no BEV carried; the hit counts are whole numbers,
# so any difference fails:
#   logit_gap  lower 2.66e-6, upper 0.366 (float8; no carry 0.887)
#   box_gap    lower 3.18e-6, upper 0.380 (no carry; float8 0.924)
#   bev_gap    lower 2.26e-6, upper 0.332 (float8; no carry 2.67)
TINY_LIMITS = {"logit_gap": 3.2e-3, "box_gap": 3.5e-3, "bev_gap": 2.8e-3,
               "hit_count_gap": 0.5}
SEEDS = list(range(11, 23))


def add_bevformer(root: str) -> str:
    cb = os.path.join(root, "chipbench")
    for rel, obj in (("configs/tiny-bev.json", TINY_CONFIG),
                     ("traffic/tiny-scene.json", TINY_TRAFFIC),
                     ("limits/tiny-bev-infer.json", TINY_LIMITS)):
        with open(os.path.join(cb, rel), "x") as f:
            json.dump(obj, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-bev", "source": "test",
                             "file": "chipbench/configs/tiny-bev.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-bev-infer", "config": "tiny-bev",
                               "traffic": "tiny-scene", "chips": 1,
                               "why": "test"})
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    with chipbench_tiny.kept_cache_config():
        yield add_bevformer(chipbench_tiny.make_root(
            str(tmp_path_factory.mktemp("bev"))))


def family(root):
    return catalog.family(TINY_CONFIG, root)


def drive(root, trace=0, hooks=None, seed=11, seconds=0.3):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = run.main(["--workload", "tiny-bev-infer", "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, require_tpu=False, hooks=hooks)
    lines = out.getvalue().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 else None), err.getvalue()


@pytest.mark.parametrize("trace", [0, 1])
def test_bevformer_cell_runs_and_is_correct(root, trace):
    rc, res, err = drive(root, trace, seed=2147483777)
    assert rc == 0, err[-3000:]
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"logit_gap", "box_gap", "bev_gap",
                                  "hit_count_gap"}
    assert res["checks"]["hit_count_gap"]["value"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        assert res["metrics"]["sca_slot_fill"]["unit"] == "%"
        assert 0 < res["metrics"]["sca_slot_fill"]["value"] <= 100


@pytest.mark.parametrize("fault", ["logit_altered", "no_carry"])
def test_bevformer_fault_is_not_correct(root, fault):
    hooks = {"frame_fn": family(root).FAULTS[fault]}
    rc, res, err = drive(root, hooks=hooks)
    assert rc == 0, err[-3000:]
    assert res["correct"] is False, res["checks"]


def test_bevformer_window_shorter_than_the_check(root):
    """A window of one call: the scene's next frames run after it, so the
    check still compares the first three."""
    rc, res, err = drive(root, seconds=1e-6)
    assert rc == 0, err[-3000:]
    assert res["attempted"] == 1 and res["correct"] is True, res["checks"]


def test_bevformer_overflow_fails_the_frames(root):
    """Camera bounds below the rig's hits: the program counts the overflow
    and every frame of the window is failed."""
    fam = family(root)
    real = fam.program_config

    def short(cfg):
        import dataclasses
        mcfg = real(cfg)
        return dataclasses.replace(
            mcfg, cam_bounds=tuple(max(b // 8, 8) for b in mcfg.cam_bounds))

    fam.program_config = short
    try:
        rc, res, err = drive(root)
    finally:
        fam.program_config = real
    assert rc == 0, err[-3000:]
    assert res["failed"] == res["attempted"] > 0


def test_bevformer_weights_are_the_benchmarks_own(root, monkeypatch):
    """The family draws the weights from the configuration's shapes, in
    the program's layout: with the program's ``init_params`` zeroed they
    are drawn all the same, and a layout the program does not have is
    refused."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chipbench.harness import BenchError
    from repro.models import bevformer as bf

    fam = family(root)
    mcfg = fam.program_config(TINY_CONFIG)
    real = bf.init_params
    monkeypatch.setattr(bf, "init_params", lambda k, c: jax.tree.map(
        jnp.zeros_like, real(k, c)))
    params = fam.weights(3, TINY_CONFIG, mcfg)
    for path, x in jax.tree_util.tree_leaves_with_path(params):
        if path[-1].key not in ("bias", "scale"):  # LayerNorms: 0 and 1
            assert np.std(np.asarray(x, np.float32)) > 0, path
    reg = np.asarray(params["dec"]["reg"]["l3"]["w"], np.float32)
    assert 0 < np.std(reg) * np.sqrt(TINY_CONFIG["d_model"]) < 0.02
    mine = fam.init(jax.random.PRNGKey(5), TINY_CONFIG)
    del mine["dec"]["cross"]["out"]
    with pytest.raises(BenchError):
        fam.check_layout(mine, mcfg)


@pytest.mark.parametrize("control", ["float8", "no_carry"])
@pytest.mark.parametrize("seed", SEEDS[:3])
def test_bevformer_control_is_not_correct(root, seed, control):
    """The reference in float8 (e4m3), or run with no BEV carried, in the
    program's place fails."""
    from chipbench import checks

    fam = family(root)
    mcfg = fam.program_config(TINY_CONFIG)
    frames = fam.make_frames(seed, TINY_CONFIG, TINY_TRAFFIC,
                             fam.lidar2img(TINY_CONFIG))
    inputs = {"params": fam.weights(seed, TINY_CONFIG, mcfg),
              "frames": frames[:3], "lidar2img": fam.lidar2img(TINY_CONFIG)}
    ref = fam.reference(TINY_CONFIG, inputs)
    if control == "float8":
        low = fam.control("infer", TINY_CONFIG, TINY_TRAFFIC, inputs, ref)
    else:
        low = fam.no_carry_control(TINY_CONFIG, inputs, ref)
    assert checks.judge(low, TINY_LIMITS)["correct"] is False, low
