"""PyTorch checkpoint -> JAX variables conversion.

Loads the reference's released ``.pth`` state dicts (reference:
download_models.sh:4; saved with the DataParallel ``module.`` prefix,
train_stereo.py:187) into this framework's variables pytree, for numerical
parity evaluation and for fine-tuning from released weights.

Layout translation: torch convs are NCHW/OIHW, ours NHWC/HWIO; norm params map
weight->scale, bias->bias, running_{mean,var}->batch_stats {mean,var}.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np

from ..config import RAFTStereoConfig


def _np(t) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    return t.detach().cpu().numpy()  # torch tensor without importing torch


def load_state_dict(path: str) -> Dict[str, np.ndarray]:
    """Read a .pth file into a flat numpy dict (strips ``module.``)."""
    import torch

    sd = torch.load(path, map_location="cpu")
    if isinstance(sd, dict) and "state_dict" in sd:
        sd = sd["state_dict"]
    out = {}
    for k, v in sd.items():
        if k.startswith("module."):
            k = k[len("module."):]
        out[k] = _np(v)
    return out


# ---------------------------------------------------------------------------
# flax module path -> torch parameter prefix
# ---------------------------------------------------------------------------

def _translate_module(flax_path: tuple, shared_backbone: bool) -> str:
    """Map a flax module path (without the leaf name) to the torch prefix."""
    top, rest = flax_path[0], list(flax_path[1:])

    if top == "zqr":
        # zqr{i} -> context_zqr_convs.{i}
        assert len(rest) == 1 and rest[0].startswith("zqr")
        return f"context_zqr_convs.{rest[0][3:]}"

    def enc_part(parts):
        out = []
        for p in parts:
            if p.startswith("layer") and "_" in p:
                stage, blk = p[len("layer"):].split("_")
                out.append(f"layer{stage}.{blk}")
            elif p.startswith("head"):
                # head08_{hi}_res -> outputs08.{hi}.0 ; head08_{hi}_conv -> .1
                # head32_{hi}_conv -> outputs32.{hi}
                lvl = p[4:6]
                hi, kind = p[7:].split("_")
                if lvl == "32":
                    out.append(f"outputs32.{hi}")
                else:
                    out.append(f"outputs{lvl}.{hi}." + ("0" if kind == "res" else "1"))
            elif p == "downsample_conv":
                out.append("downsample.0")
            elif p == "downsample_norm":
                out.append("downsample.1")
            else:
                out.append(p)
        return ".".join(out)

    if top == "cnet":
        return "cnet." + enc_part(rest) if rest else "cnet"
    if top == "fnet":
        if shared_backbone:
            # SharedBackboneHead: res -> conv2.0, out -> conv2.1
            m = {"res": "conv2.0", "out": "conv2.1"}
            return enc_part([m[rest[0]]] + rest[1:])
        return "fnet." + enc_part(rest) if rest else "fnet"
    if top == "update":
        m = {"gru0": "gru08", "gru1": "gru16", "gru2": "gru32",
             "mask_conv1": "mask.0", "mask_conv2": "mask.2"}
        parts = [m.get(p, p) for p in rest]
        return "update_block." + ".".join(parts)
    raise KeyError(f"unknown flax top module {top}")


def _convert_leaf(name: str, torch_prefix: str,
                  sd: Mapping[str, np.ndarray]) -> np.ndarray:
    if torch_prefix.endswith(".convzr"):
        # Our ConvGRU fuses the reference's convz+convr into one conv
        # (models/update.py) — concatenate the torch weights on the output
        # axis; per-channel arithmetic is unchanged.
        parts = [torch_prefix[:-len("convzr")] + c for c in ("convz", "convr")]
        if name == "kernel":
            return np.concatenate(
                [np.transpose(sd[f"{p}.weight"], (2, 3, 1, 0))
                 for p in parts], axis=-1)
        if name == "bias":
            return np.concatenate([sd[f"{p}.bias"] for p in parts])
        raise KeyError(name)
    if name == "kernel":
        w = sd[f"{torch_prefix}.weight"]
        assert w.ndim == 4, (torch_prefix, w.shape)
        return np.transpose(w, (2, 3, 1, 0))  # OIHW -> HWIO
    if name == "bias":
        return sd[f"{torch_prefix}.bias"]
    if name == "scale":
        return sd[f"{torch_prefix}.weight"]
    if name == "mean":
        return sd[f"{torch_prefix}.running_mean"]
    if name == "var":
        return sd[f"{torch_prefix}.running_var"]
    raise KeyError(name)


def _walk(tree: Mapping, path=()):
    for k, v in tree.items():
        if isinstance(v, Mapping):
            yield from _walk(v, path + (k,))
        else:
            yield path + (k,), v


def torch_to_variables(sd: Mapping[str, np.ndarray], template: Dict,
                       config: RAFTStereoConfig) -> Dict:
    """Fill a ``model.init``-produced variables pytree from a torch state dict.

    The template supplies structure and dtypes; every leaf is replaced by the
    translated torch tensor.  Raises KeyError on any missing torch weight —
    conversion is strict, like the reference's ``load_state_dict(strict=True)``
    (reference: train_stereo.py:147).
    """
    import jax.numpy as jnp

    out: Dict[str, Any] = {"params": {}, "batch_stats": {}}
    consumed = set()
    leaf_to_torch = {"kernel": "weight", "bias": "bias", "scale": "weight",
                     "mean": "running_mean", "var": "running_var"}

    for coll in ("params", "batch_stats"):
        for path, leaf in _walk(template.get(coll, {})):
            *mods, name = path
            prefix = _translate_module(tuple(mods), config.shared_backbone)
            arr = _convert_leaf(name, prefix, sd)
            assert arr.shape == leaf.shape, (path, arr.shape, leaf.shape)
            if prefix.endswith(".convzr"):  # fused GRU gate conv: two sources
                for c in ("convz", "convr"):
                    consumed.add(f"{prefix[:-len('convzr')]}{c}."
                                 f"{leaf_to_torch[name]}")
            else:
                consumed.add(f"{prefix}.{leaf_to_torch[name]}")
            if prefix.endswith(".downsample.1"):
                # The reference's ResidualBlock registers the projection norm
                # twice (as `norm3` and inside the downsample Sequential —
                # core/extractor.py:20,44-45), so state dicts carry aliased
                # duplicates.
                consumed.add(prefix.replace(".downsample.1", ".norm3")
                             + f".{leaf_to_torch[name]}")
            _set(out[coll], path, jnp.asarray(arr, dtype=leaf.dtype))

    # Strict in both directions, like torch's strict=True: any torch weight
    # the template did not demand means a config/architecture mismatch.
    # Exception: the reference instantiates all three GRU levels regardless of
    # n_gru_layers (core/update.py:104-106, core/extractor.py:224-250), so
    # checkpoints of shallower configs carry dead weights — allow exactly those.
    dead_prefixes = []
    if config.n_gru_layers < 3:
        dead_prefixes += ["cnet.layer5.", "cnet.outputs32.", "update_block.gru32."]
    if config.n_gru_layers < 2:
        dead_prefixes += ["cnet.layer4.", "cnet.outputs16.", "update_block.gru16."]
    leftover = {k for k in sd
                if k not in consumed and not k.endswith("num_batches_tracked")
                and not any(k.startswith(p) for p in dead_prefixes)}
    if leftover:
        raise KeyError(
            f"checkpoint has {len(leftover)} weights the model config does not "
            f"use (config mismatch?): {sorted(leftover)[:8]}...")

    if not out["batch_stats"]:
        del out["batch_stats"]
    return out


def _set(tree: Dict, path, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def migrate_prefusion_variables(variables: Mapping) -> Dict:
    """Migrate a weights pytree saved before the GRU gate-conv fusion
    (round 2): every ConvGRU's separate ``convz``/``convr`` become one
    ``convzr`` with kernels/biases concatenated on the output axis — the
    exact transformation the .pth converter applies, so the migrated model
    is numerically identical."""
    import jax.numpy as jnp

    def walk(tree):
        if not isinstance(tree, Mapping):
            return tree
        out = {}
        keys = set(tree)
        if {"convz", "convr"} <= keys:
            out["convzr"] = {
                "kernel": jnp.concatenate([tree["convz"]["kernel"],
                                           tree["convr"]["kernel"]], axis=-1),
                "bias": jnp.concatenate([tree["convz"]["bias"],
                                         tree["convr"]["bias"]]),
            }
            keys -= {"convz", "convr"}
        for k in keys:
            out[k] = walk(tree[k])
        return out

    return walk(variables)


def convert_checkpoint(pth_path: str, config: RAFTStereoConfig,
                       image_hw=(64, 96)) -> Dict:
    """One-call conversion: .pth -> ready-to-use variables pytree."""
    import jax

    from ..models import RAFTStereo

    model = RAFTStereo(config)
    # Structure, shapes and dtypes are all the template gives: traced, not
    # run (an eager init is ~320 small programs on the device, tens of
    # seconds of a server's start).
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), image_hw=image_hw))
    return torch_to_variables(load_state_dict(pth_path), template, config)
