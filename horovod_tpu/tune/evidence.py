"""Evidence loaders: recorded measurements -> tuner inputs.

The repo already records everything an analytic tuner needs, it just
records it in four places. This module is the funnel:

* **BENCH_* rows** (files named ``BENCH_*.json`` in the evidence
  directory: ``{"n", "cmd", "rc", "tail"}`` with a JSON tail, the
  format of a measurement script that is gone; nothing in the tree
  writes them now, ROADMAP D6) — per-bucket ``step_ms.comm_buckets``
  timings, the step decomposition, the serialized-vs-overlapped pair,
  the structural ``wire_bytes_per_opt_step``. Rows stamped with a
  ``config:`` block (PR 19) are self-describing; LEGACY rows without
  one get their tunable values inferred from the row's own keys
  (``bucket_bytes``, ``k``, ``compression`` ...).
* **hvt-trace spans** (``HVT_TRACE_DIR`` JSONL) — per-phase wall-time
  attribution via `obs.timeline.phase_attribution`, used to
  cross-check the input/compute split.
* **hvt-audit structural counts** ride inside the rows
  (``wire_bytes_per_opt_step``, ``flops_per_opt_step`` are audited
  from the lowered program, not timed), so loading rows loads them.

Every loader degrades to "no evidence" (empty/None) rather than
raising: the offline CLI turns missing evidence into exit 2, not a
traceback.
"""

from __future__ import annotations

import glob
import json
import os

from horovod_tpu.tune import space

__all__ = [
    "load_rows", "config_of", "anchor_row", "comm_points",
    "load_trace", "wire_ratio",
]

# Bytes-on-wire ratio per compression wire, relative to f32. Structural
# (dtype width), not timed — int8/fp8 quantized wires are byte-equal to
# their dtype width by construction (hvt-audit's wire gate checks this).
_WIRE_RATIO = {"none": 1.0, "bf16": 0.5, "fp16": 0.5,
               "int8": 0.25, "fp8": 0.25}


def wire_ratio(name: str) -> float:
    return _WIRE_RATIO.get(str(name or "none"), 1.0)


def load_rows(evidence_dir: str) -> list[dict]:
    """Parse every BENCH_*.json under ``evidence_dir`` into tail dicts.

    Each returned dict is the row's tail with bookkeeping keys added:
    ``_source`` (filename) and ``_cmd`` (the recorded command line).
    Unparseable files are skipped — stale evidence must not brick the
    tuner. Sorted by filename, so the NEWEST row (highest r-number)
    is last.
    """
    rows = []
    for path in sorted(glob.glob(os.path.join(evidence_dir, "BENCH_*.json"))):
        try:
            with open(path, encoding="utf-8") as f:
                rec = json.load(f)
            tail = rec.get("tail") if isinstance(rec, dict) else None
            row = json.loads(tail) if isinstance(tail, str) else (
                tail if isinstance(tail, dict) else rec)
            if not isinstance(row, dict):
                continue
            row = dict(row)
            row["_source"] = os.path.basename(path)
            row["_cmd"] = rec.get("cmd", "") if isinstance(rec, dict) else ""
            rows.append(row)
        except (OSError, ValueError):
            continue
    return rows


def config_of(row: dict) -> dict:
    """The tunable-knob values a row ran under.

    Rows since PR 19 carry an explicit ``config:`` block; legacy rows
    are inferred from the measurement keys every row carries, with
    registry defaults filling the gaps.
    """
    cfg = dict(space.default_config())
    legacy = {
        "HVT_BUCKET_BYTES": row.get("bucket_bytes"),
        "HVT_BACKWARD_PASSES": row.get("k"),
        "HVT_COMPRESSION": row.get("compression"),
        "HVT_COMPRESSION_ICI": row.get("compression_ici"),
        # a row's headline leg has always been the overlapped one
        # (serialized is the B leg) — a row reporting overlap_fraction
        # measured with the overlap on.
        "HVT_OVERLAP_REDUCTION": (True if "overlap_fraction" in row
                                  else None),
    }
    for name, v in legacy.items():
        if v is not None:
            cfg[name] = v
    stamped = row.get("config")
    if isinstance(stamped, dict):
        for name, v in stamped.items():
            if name in cfg and v is not None:
                cfg[name] = v
    return cfg


def anchor_row(rows: list[dict]) -> dict | None:
    """The newest row rich enough to anchor the model: needs the
    per-bucket comm attribution and the step decomposition."""
    for row in reversed(rows):
        sm = row.get("step_ms")
        if (isinstance(sm, dict) and sm.get("comm_buckets")
                and sm.get("total")):
            return row
    return None


def comm_points(rows: list[dict]) -> list[tuple[float, float]]:
    """Pooled per-bucket ``(bytes, ms)`` samples across every row that
    recorded them — the alpha/beta fit's input. Only f32-wire rows
    contribute (quantized wires would need their own fit line)."""
    pts = []
    for row in rows:
        cfg = config_of(row)
        if cfg.get("HVT_COMPRESSION") != "none":
            continue
        sm = row.get("step_ms")
        if not isinstance(sm, dict):
            continue
        for b in sm.get("comm_buckets") or []:
            try:
                pts.append((float(b["bytes"]), float(b["ms"])))
            except (KeyError, TypeError, ValueError):
                continue
    return pts


def load_trace(trace_dir: str | None) -> dict:
    """Per-phase wall-time attribution from hvt-trace spans, or {}.

    Imported lazily: the obs layer is optional evidence, and the tuner
    must work from recorded rows alone."""
    if not trace_dir or not os.path.isdir(trace_dir):
        return {}
    try:
        from horovod_tpu.obs import timeline
        return timeline.phase_attribution(trace_dir)
    except Exception:
        return {}
