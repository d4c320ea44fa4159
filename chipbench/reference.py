"""The comparison that decides ``reference_agrees``: the system's per-token
losses against those of its family's plain float32 reference
(families/<family>.py ``per_token_loss``) on one seeded sequence. It knows
no architecture."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

# What `compare` reports and a limit may be set on (run.py `DEFAULT_LIMITS`,
# a family's ``LIMITS``): each is 0 for equal losses and grows with the
# disagreement. The last is reported only where the family states where
# "far off" begins (``FAR_OFF``).
REPORTED = ("bias", "mean_abs_diff", "rel_rms", "median_abs_diff",
            "far_off_share")


def compare(model_loss, reference_loss, far_off=None) -> dict:
    """How far the system's per-token losses sit from the reference's:
    the difference of the two mean losses (``bias``), the mean absolute
    difference per token, and the RMS difference over the spread of the
    reference's own per-token losses (noise that does not track the
    reference shows there). Those three move with every token; two more do
    not move with a minority of far-off ones (a routed model's tokens whose
    last expert differs between two precisions): the median absolute
    difference and, where the family says how far is far (``far_off``, in
    the loss's own units: a threshold of the family's, set from its own
    seeds, and not a multiple of this run's median, which is 0 where most
    tokens agree to the bit and rises with a fault that lifts every
    token), the share of tokens further off than that, which counts such a
    minority instead of averaging it in."""
    diff = jnp.asarray(model_loss, jnp.float32) - reference_loss
    report = {
        "model_mean_loss": float(jnp.mean(model_loss)),
        "reference_mean_loss": float(jnp.mean(reference_loss)),
        "bias": float(jnp.abs(jnp.mean(diff))),
        "mean_abs_diff": float(jnp.mean(jnp.abs(diff))),
        "rel_rms": float(
            jnp.sqrt(jnp.mean(diff ** 2)) / jnp.std(reference_loss)),
    }
    # The later numbers are taken on the host, and last: whatever the
    # device allocates or holds in another order before the window moves
    # `peak_hbm_gb` by tens to hundreds of KiB (PR 29's first chip runs: a
    # median sorted on the device +288 KiB in cell 1, the same transfer
    # made before the five lines above -36.5 KiB in cell 2).
    off = np.abs(np.asarray(diff))
    report["median_abs_diff"] = float(np.median(off))
    if far_off is not None:
        report["far_off_share"] = float(np.mean(off > far_off))
    return report
