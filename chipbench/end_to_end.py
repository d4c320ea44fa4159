"""The end-to-end metrics, one function per metric and named like it. Each
takes the run's readings: ``n_steps``, ``tokens_per_step``, ``window_s``
(from the device idle before the first step to the last step's loss being
there), ``intervals_ms`` (between successive `on_batch_end` calls in the
window), ``peak_bytes`` and ``setup_s``."""

import math


def percentile(values, q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    ordered = sorted(values)
    pos = (len(ordered) - 1) * q
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def tokens_per_s(run):
    """All the window's optimizer steps over all the window's seconds."""
    return run["n_steps"] * run["tokens_per_step"] / run["window_s"]


def step_ms_p90(run):
    return percentile(run["intervals_ms"], 0.9)


def peak_hbm_gb(run):
    """The fullest chip's ``peak_bytes_in_use`` plus the compiled step's
    temporaries, which this runtime's counter leaves out."""
    return run["peak_bytes"] / 1e9


def setup_s(run):
    return run["setup_s"]
