"""CLI entry-point tests: train loop end-to-end (incl. exact resume), demo
output artifacts, evaluate dispatch, and the viz colormap."""

import json
import os

import numpy as np
import pytest
from PIL import Image

import jax

from raftstereo_tpu.config import RAFTStereoConfig, TrainConfig
from raftstereo_tpu.data import datasets as ds
from raftstereo_tpu.utils.viz import colorize, jet

from test_data import make_synthetic_kitti


TINY = dict(n_gru_layers=2, hidden_dims=(32, 32), corr_levels=2,
            corr_radius=2)


class TestHelpRegression:
    """Every subcommand must exit 0 on --help: argparse wiring (flag
    groups, shared config builders, new subcommands) breaks at collection
    speed instead of in production.  In-process: a subprocess per command
    would pay ~10 s of fresh jax import each for no extra coverage."""

    SUBCOMMANDS = ["train", "evaluate", "demo", "serve", "convert",
                   "sl", "sl_smoke", "stream", "router", "certify",
                   "loadgen", "sessiontier", "obs"]

    @pytest.mark.parametrize("name", SUBCOMMANDS)
    def test_help_exits_zero(self, name, capsys):
        import importlib

        mod = importlib.import_module(f"raftstereo_tpu.cli.{name}")
        with pytest.raises(SystemExit) as ei:
            mod.main(["--help"])
        assert ei.value.code == 0
        assert "usage" in capsys.readouterr().out.lower()

    def test_certify_cascade_verb_help(self, capsys):
        # The cascade verb rides in front of certify's historical
        # flag-only parser (docs/serving.md "Tier cascade"); its --help
        # must wire up independently of the flag form above.
        from raftstereo_tpu.cli import certify

        with pytest.raises(SystemExit) as ei:
            certify.main(["cascade", "--help"])
        assert ei.value.code == 0
        out = capsys.readouterr().out
        assert "--schedules" in out and "--cascade_bound" in out
        # The budget is the schedule's own — the flag (rendered by
        # argparse as "--cert_iters CERT_ITERS") is not defined here;
        # the prose in --schedules' help may still NAME it.
        assert "--cert_iters CERT_ITERS" not in out

    def test_serve_help_lists_cascade_flags(self, capsys):
        import importlib

        mod = importlib.import_module("raftstereo_tpu.cli.serve")
        with pytest.raises(SystemExit) as ei:
            mod.main(["--help"])
        assert ei.value.code == 0
        out = capsys.readouterr().out
        assert "--cascades" in out and "--cascade_divergence" in out

    @pytest.mark.parametrize("name,required", [
        ("serve", []), ("train", []), ("stream", []), ("certify", []),
        ("sl", []), ("evaluate", ["--dataset", "kitti"]),
        ("demo", ["--restore_ckpt", "x", "-l", "a", "-r", "b"])])
    def test_a_removed_flag_fails_loudly(self, name, required, capsys):
        # One GRU step: every entry point that shares the model flags took
        # `--gru_backend`; an old command line that still pins it is an
        # argparse error (exit 2) naming the flag, not a flag accepted
        # and ignored.
        import importlib

        mod = importlib.import_module(f"raftstereo_tpu.cli.{name}")
        with pytest.raises(SystemExit) as ei:
            mod.main(required + ["--gru_backend", "xla"])
        assert ei.value.code == 2
        assert "unrecognized arguments: --gru_backend xla" \
            in capsys.readouterr().err

    def test_router_help_lists_observability_flags(self, capsys):
        # The fleet-observatory knobs (docs/observability.md "Fleet
        # observatory") must stay wired through add_router_args.
        from raftstereo_tpu.cli import router

        with pytest.raises(SystemExit) as ei:
            router.main(["--help"])
        assert ei.value.code == 0
        out = capsys.readouterr().out
        for flag in ("--tail_ring", "--alert_window_s",
                     "--alert_error_budget", "--alert_shed_budget",
                     "--alert_page_burn", "--fleet_timeout_s"):
            assert flag in out, flag

    @pytest.mark.parametrize("verb,flags", [
        ("trace", ("--trace_id", "--out")),
        ("fleet", ("--router",)),
        ("alerts", ("--watch",)),
    ])
    def test_obs_verb_help(self, verb, flags, capsys):
        from raftstereo_tpu.cli import obs

        with pytest.raises(SystemExit) as ei:
            obs.main([verb, "--help"])
        assert ei.value.code == 0
        out = capsys.readouterr().out
        for flag in flags:
            assert flag in out, flag


class TestViz:
    def test_jet_endpoints(self):
        out = jet(np.array([0.0, 0.5, 1.0]))
        # classic jet: dark blue -> green-ish -> dark red
        assert out.shape == (3, 3)
        assert out[0, 2] > 100 and out[0, 0] == 0       # low = blue
        assert out[1, 1] == 255                          # mid = green
        assert out[2, 0] > 100 and out[2, 2] == 0       # high = red

    def test_colorize_normalises(self):
        arr = np.array([[10.0, 20.0], [30.0, 40.0]])
        out = colorize(arr)
        assert out.shape == (2, 2, 3) and out.dtype == np.uint8
        flat = colorize(np.zeros((4, 4)))
        assert (flat == flat[0, 0]).all()  # constant input, no div-by-zero


class TestTrainCLI:
    @pytest.mark.slow
    def test_train_and_resume(self, tmp_path, rng, monkeypatch):
        import socket
        import threading
        import urllib.request

        from raftstereo_tpu.cli.train import train

        make_synthetic_kitti(tmp_path / "kitti", n=4, rng=rng)
        dataset = ds.KITTI(aug_params={"crop_size": (48, 64)},
                           root=str(tmp_path / "kitti"))
        monkeypatch.chdir(tmp_path)
        mcfg = RAFTStereoConfig(**TINY)
        tcfg = TrainConfig(name="t", batch_size=2, num_steps=3,
                           train_iters=2, image_size=(48, 64),
                           validation_frequency=2, seed=7,
                           checkpoint_dir=str(tmp_path / "ckpt"),
                           data_parallel=2)
        # --metrics_port exporter: scrape while the run is live (the
        # multi-second step compile guarantees a window) — the run itself
        # is the same one the resume assertions below depend on.
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        scraped = {}
        stop = threading.Event()

        def poll():
            base = f"http://127.0.0.1:{port}"
            while not stop.is_set():
                try:
                    for key, path in (("metrics", "/metrics"),
                                      ("vars", "/debug/vars"),
                                      ("trace", "/debug/trace?last=50")):
                        with urllib.request.urlopen(base + path,
                                                    timeout=2) as r:
                            scraped[key] = r.read().decode()
                except Exception:
                    pass
                stop.wait(0.05)

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()
        try:
            state = train(mcfg, tcfg, dataset=dataset, num_workers=0,
                          no_validation=True, profile_steps=(1, 2),
                          metrics_port=port)
        finally:
            stop.set()
            poller.join(10)
        assert int(state.step) == 4  # runs to num_steps+1 then stops
        final = tmp_path / "ckpt" / "t" / "t-final"
        assert final.exists()
        # --profile_steps integration: a trace landed in runs/<name>/profile.
        prof_dir = tmp_path / "runs" / "t" / "profile"
        assert any(p.is_file() for p in prof_dir.rglob("*"))
        # The exporter answered while training: the scrape is valid
        # Prometheus with the train families, and /debug/vars resolved the
        # run's config.
        from raftstereo_tpu.obs import validate_prometheus
        assert "train_steps_total" in scraped.get("metrics", ""), scraped
        assert "train_data_wait_seconds" in scraped["metrics"]
        assert validate_prometheus(scraped["metrics"]) == []
        dvars = json.loads(scraped["vars"])
        assert dvars["config"]["name"] == "t"
        assert "python" in dvars["build"]
        assert "traceEvents" in json.loads(scraped["trace"])

        # Resume: manager restores from step 4; loop exits immediately.
        state2 = train(mcfg, tcfg, dataset=dataset, num_workers=0,
                       no_validation=True)
        assert int(state2.step) == int(state.step)
        p1 = jax.tree.leaves(state.params)[0]
        p2 = jax.tree.leaves(state2.params)[0]
        np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))

    def test_absent_validation_data_fails_at_startup(self, tmp_path, rng,
                                                     monkeypatch):
        """The 10k-step regression check (reference: train_stereo.py:184-191)
        must not degrade into a silent skip: without FlyingThings data and
        without --no_validation, training refuses to start."""
        from raftstereo_tpu.cli.train import train

        make_synthetic_kitti(tmp_path / "kitti", n=2, rng=rng)
        dataset = ds.KITTI(aug_params={"crop_size": (48, 64)},
                           root=str(tmp_path / "kitti"))
        monkeypatch.chdir(tmp_path)  # no datasets/FlyingThings3D here
        mcfg = RAFTStereoConfig(**TINY)
        tcfg = TrainConfig(name="v", batch_size=2, num_steps=1,
                           train_iters=2, image_size=(48, 64), seed=7,
                           checkpoint_dir=str(tmp_path / "ckpt"),
                           data_parallel=2)
        with pytest.raises(ValueError, match="no_validation"):
            train(mcfg, tcfg, dataset=dataset, num_workers=0,
                  no_validation=False)

    def test_empty_loader_fails_fast(self, tmp_path, rng):
        from raftstereo_tpu.cli.train import train

        make_synthetic_kitti(tmp_path / "kitti", n=2, rng=rng)
        dataset = ds.KITTI(aug_params={"crop_size": (48, 64)},
                           root=str(tmp_path / "kitti"))
        mcfg = RAFTStereoConfig(**TINY)
        tcfg = TrainConfig(name="e", batch_size=8, num_steps=2,
                           train_iters=2, image_size=(48, 64),
                           checkpoint_dir=str(tmp_path / "ckpt"),
                           data_parallel=8)
        with pytest.raises(ValueError, match="empty train loader"):
            train(mcfg, tcfg, dataset=dataset, num_workers=0,
                  no_validation=True)

    def test_arg_roundtrip(self):
        from raftstereo_tpu.cli.train import (add_train_args,
                                              train_config_from_args)
        import argparse

        p = argparse.ArgumentParser()
        add_train_args(p)
        args = p.parse_args(["--batch_size", "4", "--train_datasets",
                             "sceneflow", "kitti", "--spatial_scale",
                             "-0.2", "0.4"])
        cfg = train_config_from_args(args)
        assert cfg.batch_size == 4
        assert cfg.train_datasets == ("sceneflow", "kitti")
        assert cfg.spatial_scale == (-0.2, 0.4)


@pytest.mark.slow
class TestDemoCLI:
    def test_demo_outputs(self, tmp_path, rng):
        from raftstereo_tpu.cli.demo import main
        from raftstereo_tpu.models import RAFTStereo
        from raftstereo_tpu.train.checkpoint import save_weights

        cfg = RAFTStereoConfig(**TINY)
        model = RAFTStereo(cfg)
        variables = model.init(jax.random.key(0))
        ckpt = tmp_path / "weights"
        save_weights(str(ckpt), variables)

        for i in range(2):
            for side in ("left", "right"):
                img = rng.integers(0, 255, (64, 96, 3), dtype=np.uint8)
                Image.fromarray(img).save(tmp_path / f"{i}_{side}.png")
        out_dir = tmp_path / "out"
        rc = main(["--restore_ckpt", str(ckpt),
                   "-l", str(tmp_path / "*_left.png"),
                   "-r", str(tmp_path / "*_right.png"),
                   "--output_directory", str(out_dir),
                   "--save_numpy", "--valid_iters", "2",
                   "--n_gru_layers", "2", "--hidden_dims", "32", "32",
                   "--corr_levels", "2", "--corr_radius", "2"])
        assert rc == 0
        for i in range(2):
            png = out_dir / f"{i}_left.png"
            npy = out_dir / f"{i}_left.npy"
            assert png.exists() and npy.exists()
            assert np.asarray(Image.open(png)).shape == (64, 96, 3)
            assert np.load(npy).shape == (64, 96)

    def test_demo_tiled(self, tmp_path, rng):
        """--tiled end-to-end: glue from argparse through tiled_infer to the
        saved full-resolution outputs (BASELINE.json config #5 CLI path)."""
        from raftstereo_tpu.cli.demo import main
        from raftstereo_tpu.models import RAFTStereo
        from raftstereo_tpu.train.checkpoint import save_weights

        cfg = RAFTStereoConfig(**TINY, corr_implementation="alt")
        model = RAFTStereo(cfg)
        variables = model.init(jax.random.key(0))
        ckpt = tmp_path / "weights"
        save_weights(str(ckpt), variables)

        for side in ("left", "right"):
            img = rng.integers(0, 255, (72, 200, 3), dtype=np.uint8)
            Image.fromarray(img).save(tmp_path / f"0_{side}.png")
        out_dir = tmp_path / "out"
        rc = main(["--restore_ckpt", str(ckpt),
                   "-l", str(tmp_path / "*_left.png"),
                   "-r", str(tmp_path / "*_right.png"),
                   "--output_directory", str(out_dir),
                   "--save_numpy", "--valid_iters", "2",
                   "--tiled", "--tile_size", "64", "128",
                   "--tile_overlap", "8", "--max_disparity", "32",
                   "--corr_implementation", "alt",
                   "--n_gru_layers", "2", "--hidden_dims", "32", "32",
                   "--corr_levels", "2", "--corr_radius", "2"])
        assert rc == 0
        d = np.load(out_dir / "0_left.npy")
        assert d.shape == (72, 200)
        assert np.isfinite(d).all()

    def test_demo_colliding_basenames_use_scene_dirs(self, tmp_path, rng):
        # ETH3D-style layout: every left image is im0.png — outputs must not
        # overwrite each other (reference: demo.py:44 uses the scene dir).
        from raftstereo_tpu.cli.demo import main
        from raftstereo_tpu.models import RAFTStereo
        from raftstereo_tpu.train.checkpoint import save_weights

        cfg = RAFTStereoConfig(**TINY)
        variables = RAFTStereo(cfg).init(jax.random.key(0))
        ckpt = tmp_path / "w"
        save_weights(str(ckpt), variables)
        for scene in ("sceneA", "sceneB"):
            os.makedirs(tmp_path / scene)
            for name in ("im0.png", "im1.png"):
                img = rng.integers(0, 255, (64, 96, 3), dtype=np.uint8)
                Image.fromarray(img).save(tmp_path / scene / name)
        out_dir = tmp_path / "out"
        rc = main(["--restore_ckpt", str(ckpt),
                   "-l", str(tmp_path / "scene*" / "im0.png"),
                   "-r", str(tmp_path / "scene*" / "im1.png"),
                   "--output_directory", str(out_dir), "--valid_iters", "2",
                   "--n_gru_layers", "2", "--hidden_dims", "32", "32",
                   "--corr_levels", "2", "--corr_radius", "2"])
        assert rc == 0
        assert sorted(p.name for p in out_dir.iterdir()) == [
            "sceneA.png", "sceneB.png"]

    def test_demo_bad_globs(self, tmp_path):
        from raftstereo_tpu.cli.demo import main
        from raftstereo_tpu.models import RAFTStereo
        from raftstereo_tpu.train.checkpoint import save_weights

        cfg = RAFTStereoConfig(**TINY)
        variables = RAFTStereo(cfg).init(jax.random.key(0))
        ckpt = tmp_path / "w"
        save_weights(str(ckpt), variables)
        rc = main(["--restore_ckpt", str(ckpt), "-l", str(tmp_path / "no*"),
                   "-r", str(tmp_path / "no*"),
                   "--n_gru_layers", "2", "--hidden_dims", "32", "32",
                   "--corr_levels", "2", "--corr_radius", "2"])
        assert rc == 1


@pytest.mark.slow
class TestEvaluateCLI:
    def test_evaluate_kitti_random_weights(self, tmp_path, rng, capsys):
        from raftstereo_tpu.cli.evaluate import main

        make_synthetic_kitti(tmp_path, n=2, rng=rng)
        rc = main(["--dataset", "kitti", "--dataset_root", str(tmp_path),
                   "--valid_iters", "2",
                   "--n_gru_layers", "2", "--hidden_dims", "32", "32",
                   "--corr_levels", "2", "--corr_radius", "2"])
        assert rc == 0
        out = capsys.readouterr().out.strip().splitlines()[-1]
        results = json.loads(out)
        assert "kitti-epe" in results and np.isfinite(results["kitti-epe"])


class TestSLSmokeCLI:
    def test_sl_smoke(self, tmp_path):
        from raftstereo_tpu.cli.sl_smoke import main
        from test_data import make_synthetic_sl

        make_synthetic_sl(tmp_path)
        assert main(["--root", str(tmp_path), "--scale", "1.0"]) == 0
        empty = tmp_path / "empty"
        os.makedirs(empty)
        assert main(["--root", str(empty)]) == 1


@pytest.mark.slow
class TestConvertCLI:
    @pytest.mark.torch_parity
    def test_pth_to_orbax_roundtrip(self, tmp_path, rng):
        """convert CLI: .pth in, Orbax weights out, loadable by evaluate."""
        torch = pytest.importorskip("torch")
        if not os.path.isdir("/root/reference"):
            pytest.skip("reference tree not mounted")
        from test_torch_parity import import_ref_raftstereo
        TorchRAFTStereo = import_ref_raftstereo()
        import argparse as ap

        targs = ap.Namespace(
            corr_implementation="reg", shared_backbone=False, corr_levels=2,
            corr_radius=2, n_downsample=2, slow_fast_gru=False,
            n_gru_layers=2, hidden_dims=[32, 32, 32], mixed_precision=False,
            context_norm="batch")
        torch.manual_seed(3)
        tmodel = TorchRAFTStereo(targs)
        pth = tmp_path / "w.pth"
        # Reference checkpoints carry the DataParallel 'module.' prefix
        # (reference: train_stereo.py:184-187 saves via the wrapper).
        torch.save({f"module.{k}": v for k, v in
                    tmodel.state_dict().items()}, str(pth))

        from raftstereo_tpu.cli.convert import main as convert_main
        dst = tmp_path / "orbax_w"
        rc = convert_main([str(pth), str(dst),
                           "--n_gru_layers", "2",
                           "--hidden_dims", "32", "32", "32",
                           "--corr_levels", "2", "--corr_radius", "2"])
        assert rc == 0 and dst.exists()

        # The converted weights load and run through the standard path.
        from raftstereo_tpu.cli.common import load_variables
        cfg = RAFTStereoConfig(n_gru_layers=2, hidden_dims=(32, 32, 32),
                               corr_levels=2, corr_radius=2)
        from raftstereo_tpu.models import RAFTStereo
        model = RAFTStereo(cfg)
        variables = load_variables(str(dst), cfg, model)
        import jax.numpy as jnp

        i = rng.uniform(0, 255, (1, 32, 48, 3)).astype(np.float32)
        _, up = model.forward(variables, jnp.asarray(i), jnp.asarray(i),
                              iters=2, test_mode=True)
        assert np.isfinite(np.asarray(up)).all()
