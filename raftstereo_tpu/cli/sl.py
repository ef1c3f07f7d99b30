"""Structured-light workload CLI: dataset stats + offline masked EPE.

    # stats + masked EPE over a real capture tree
    python -m raftstereo_tpu.cli.sl --root datasets/SL \
        --restore_ckpt sl-final --input_mode sl

    # stats only (no model, no jax compile)
    python -m raftstereo_tpu.cli.sl --root datasets/SL --stats_only

Without ``--root`` the run scores the in-memory exact-GT synthetic SL set
(sl/synthetic.py) — the same data the certification and serving-parity
tests use.  The metrics are MASKED: EPE and bad-px are computed over the
valid-modulation region only (docs/structured_light.md), and with
``--batch_pad`` the evaluator executes at the serving engine's padded
program shape, so the printed numbers are bitwise-comparable to
``/predict`` answers.

The grown-up form of ``cli.sl_smoke`` (which remains as the bare dataset
round-trip check): this one speaks the train protocol, runs the model,
and prints one JSON line for scripting.
"""

from __future__ import annotations

import argparse
import json
import logging
import sys

from .common import load_variables, setup_logging

logger = logging.getLogger(__name__)


def build_parser() -> argparse.ArgumentParser:
    from ..config import add_model_args

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--root", default=None,
                   help="SL capture tree (data/sl.py layout); default: "
                        "the in-memory exact-GT synthetic set")
    p.add_argument("--split", default="validation",
                   help="capture-tree split to read (with --root)")
    p.add_argument("--scale", type=float, default=1.0,
                   help="image rescale factor for the capture tree")
    p.add_argument("--pairs", type=int, default=8,
                   help="synthetic pairs when --root is not given")
    p.add_argument("--hw", type=int, nargs=2, default=[64, 96],
                   metavar=("H", "W"),
                   help="synthetic pair size when --root is not given")
    p.add_argument("--stats_only", action="store_true",
                   help="print dataset stats and exit (no model run)")
    p.add_argument("--restore_ckpt", default=None,
                   help=".pth or Orbax weights (default: random weights — "
                        "smoke/dev only)")
    p.add_argument("--eval_iters", type=int, default=12,
                   help="GRU iterations per evaluated pair")
    p.add_argument("--bad_px", type=float, default=1.0,
                   help="bad-pixel threshold for the bad-px metric")
    p.add_argument("--batch_pad", type=int, default=None,
                   help="serving-parity mode: zero-pad the batch axis to "
                        "this size (the row count of the engine's "
                        "dispatch: 1 for a pair served alone) so "
                        "results match /predict bitwise")
    add_model_args(p)
    return p


def _build_dataset(args):
    """(train-protocol view, stats dict).  Stats come from the raw reader
    when a tree is given, so they describe the capture, not the view."""
    if args.root:
        from ..data.sl import StructuredLightDataset
        from ..sl import SLTrainView
        raw = StructuredLightDataset(args.root, split=args.split,
                                     scale=args.scale, with_depth=True)
        stats = {"source": args.root, "split": args.split,
                 "samples": len(raw), "num_patterns": raw.num_patterns}
        if len(raw) == 0:
            return None, stats
        _meta, left, _r, _f, valid = SLTrainView(raw)[0]
        stats.update(hw=list(left.shape[:2]),
                     channels=int(left.shape[-1]),
                     valid_frac=round(float(valid.mean()), 4))
        return SLTrainView(raw), stats
    from ..sl import SLShiftStereoDataset
    ds = SLShiftStereoDataset(n=args.pairs, hw=tuple(args.hw))
    _meta, left, _r, _f, valid = ds[0]
    stats = {"source": "synthetic", "samples": len(ds),
             "hw": list(left.shape[:2]), "channels": int(left.shape[-1]),
             "valid_frac": round(float(valid.mean()), 4)}
    return ds, stats


def main(argv=None) -> int:
    setup_logging()
    args = build_parser().parse_args(argv)

    dataset, stats = _build_dataset(args)
    logger.info("SL dataset: %s", stats)
    if dataset is None:
        logger.error("Dataset is empty — check --root layout "
                     "(see raftstereo_tpu/data/sl.py docstring)")
        return 1
    if args.stats_only:
        print(json.dumps(stats))
        return 0

    from ..config import model_config_from_args

    config = model_config_from_args(args)
    if config.input_mode != "sl":
        logger.error("masked-EPE evaluation needs an SL model — pass "
                     "--input_mode sl (got %r)", config.input_mode)
        return 2

    import jax

    from ..models import RAFTStereo
    from ..sl import masked_epe

    model = RAFTStereo(config)
    if args.restore_ckpt:
        variables = load_variables(args.restore_ckpt, config, model)
        logger.info("Loaded checkpoint %s", args.restore_ckpt)
    else:
        variables = model.init(jax.random.key(0), tuple(stats["hw"]))
        logger.warning("No --restore_ckpt: evaluating RANDOM weights "
                       "(smoke/dev only)")

    metrics, _preds = masked_epe(model, variables, dataset,
                                 iters=args.eval_iters,
                                 batch_pad=args.batch_pad,
                                 bad_px=args.bad_px)
    logger.info("SL masked metrics: %s", metrics)
    print(json.dumps({**stats, **metrics}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
