"""The comparison that decides ``reference_agrees``: the system's per-token
losses against those of its family's plain float32 reference
(families/<family>.py ``per_token_loss``) on one seeded sequence. It knows
no architecture."""

from __future__ import annotations

import jax.numpy as jnp


def compare(model_loss, reference_loss) -> dict:
    """How far the system's per-token losses are from the reference's:
    the difference of the two mean losses (``bias``), the mean absolute
    difference per token, and the RMS difference over the spread of the
    reference's own per-token losses (noise that does not track the
    reference shows there)."""
    diff = jnp.asarray(model_loss, jnp.float32) - reference_loss
    return {
        "model_mean_loss": float(jnp.mean(model_loss)),
        "reference_mean_loss": float(jnp.mean(reference_loss)),
        "bias": float(jnp.abs(jnp.mean(diff))),
        "mean_abs_diff": float(jnp.mean(jnp.abs(diff))),
        "rel_rms": float(
            jnp.sqrt(jnp.mean(diff ** 2)) / jnp.std(reference_loss)),
    }
