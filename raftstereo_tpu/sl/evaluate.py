"""Offline masked-EPE evaluation for the structured-light workload.

Scores a model over train-protocol SL items — ``(meta, left12, right12,
flow_px, valid)`` from :class:`~raftstereo_tpu.sl.synthetic.
SLShiftStereoDataset` or :class:`~raftstereo_tpu.sl.adapter.SLTrainView` —
reporting EPE and bad-px ONLY over the valid-modulation region.  The
projector-shadow band carries no pattern signal, so predictions there are
unconstrained; unmasked metrics on SL scenes are meaningless by design
(sl/synthetic.py module docstring).

Serving parity: pass ``batch_pad`` = the row count of the engine's
dispatch (1 for a pair served alone, ``max_batch_size`` for one of a full
batch; serve/engine.py ``row_counts``) (plus the
engine's ``divis_by``/``bucket_multiple``) and the underlying
:class:`~raftstereo_tpu.eval.runner.Evaluator` executes each pair at the
serving engine's padded program shape, making the returned disparities
bitwise-identical to ``/predict`` answers for the same stacks — the SL
serving acceptance gate (tests/test_sl.py) is this comparison.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from ..eval.runner import Evaluator

__all__ = ["masked_epe"]


def masked_epe(model, variables, dataset, iters: int = 32, *,
               divis_by: int = 32, bucket_multiple=None, batch_pad=None,
               bad_px: float = 1.0
               ) -> Tuple[Dict[str, float], List[np.ndarray]]:
    """Masked EPE / bad-px over an SL dataset.

    Returns ``(metrics, preds)``: metrics has ``epe``, ``bad{bad_px}``
    (fraction of valid pixels with error > ``bad_px``), ``valid_frac`` and
    ``n``; preds holds each pair's full (H, W) disparity map so callers
    (cli/sl.py, serving-parity tests) can inspect per-pixel output.
    """
    evaluator = Evaluator(model, variables, iters=iters, divis_by=divis_by,
                          bucket_multiple=bucket_multiple,
                          batch_pad=batch_pad)
    errs, valids, preds = [], [], []
    for i in range(len(dataset)):
        _meta, left, right, flow, valid = dataset[i]
        pred = np.asarray(evaluator(left, right))
        preds.append(pred)
        errs.append(np.abs(pred - flow[..., 0]))
        valids.append(np.asarray(valid, np.float32))
    err = np.stack(errs)
    valid = np.stack(valids)
    n_valid = max(float(valid.sum()), 1.0)
    metrics = {
        "epe": float((err * valid).sum() / n_valid),
        f"bad{bad_px:g}": float(((err > bad_px) * valid).sum() / n_valid),
        "valid_frac": float(valid.mean()),
        "n": float(len(preds)),
    }
    return metrics, preds
