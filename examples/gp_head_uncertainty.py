"""The paper's technique composed with an assigned LM architecture: a
distributed sparse-GP readout head (deep-kernel style) on smollm-360m
features, giving calibrated uncertainty on a regression target.

    PYTHONPATH=src python examples/gp_head_uncertainty.py

Pipeline: (1) run the (smoke-sized) smollm backbone to pool per-sequence
features; (2) train the SVGP head on the collapsed bound — the exact same
sufficient-statistics + psum machinery as the GP-LVM, features being
deterministic inputs; (3) show that predictive variance separates
in-distribution from out-of-distribution inputs.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs.base import ShapeCell, get_smoke_config
from repro.core import gp_head
from repro.core.inference import fit_adam
from repro.models import model_zoo
from repro.models.layers import rmsnorm


def pooled_features(model, params, tokens, cfg):
    """Mean-pooled final hidden state (backbone as a feature extractor)."""
    from repro.models import transformer

    x = transformer._input_embeddings(params, {"tokens": tokens}, cfg)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    h, _, _ = transformer._backbone(params, x, positions, cfg, mode="train",
                                    states=None, cur_pos=None)
    return jnp.mean(h, axis=1)  # (B, d)


def main() -> None:
    compile_cache.enable()
    cfg = get_smoke_config("smollm-360m")
    model = model_zoo.build(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init(key)

    # synthetic task: target = smooth function of token statistics
    B, S = 256, 32
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size // 2, jnp.int32)
    target = jnp.sin(jnp.mean(tokens, axis=1) / 50.0)

    feats = pooled_features(model, params, tokens, cfg)
    print(f"features: {feats.shape} from {cfg.name}")

    head = gp_head.init_head(key, feats.shape[1], M=32)
    l0 = float(gp_head.head_loss(head, feats, target))
    head, hist = fit_adam(gp_head.head_loss, head, (feats, target), steps=200, lr=2e-2)
    print(f"head loss {l0:.3f} -> {hist[-1]:.3f}")

    # calibration: in-distribution vs OOD tokens (disjoint vocab range)
    tokens_ood = jax.random.randint(jax.random.fold_in(key, 9), (32, S),
                                    cfg.vocab_size // 2, cfg.vocab_size, jnp.int32)
    feats_ood = pooled_features(model, params, tokens_ood, cfg)
    pred_in = gp_head.head_predict(head, feats, target, feats[:32])
    pred_ood = gp_head.head_predict(head, feats, target, feats_ood)
    v_in = float(jnp.mean(pred_in.var))
    v_ood = float(jnp.mean(pred_ood.var))
    print(f"mean predictive variance: in-dist {v_in:.4f} vs OOD {v_ood:.4f}")
    assert v_ood > v_in, "OOD inputs should be more uncertain"
    print("GP head is calibrated: higher uncertainty off-manifold")


if __name__ == "__main__":
    main()
