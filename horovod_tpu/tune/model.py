"""The analytic comm/compute model the offline tuner searches.

Shape of the model (classic alpha-beta cost model, arxiv 1802.05799's
fusion tradeoff made explicit):

* each reduction bucket costs ``alpha + beta * bytes`` — alpha is the
  per-bucket launch/latency overhead, beta the per-byte wire cost. Both
  are LEAST-SQUARES FIT over the pooled per-bucket ``(bytes, ms)``
  samples the bench rows recorded (``step_ms.comm_buckets``), then
  SCALED so the model reproduces the anchor row's measured whole-step
  comm exactly (isolated per-bucket timings carry per-program overhead
  a fused step does not; the scale calibrates it away).
* total payload ``S`` is the structural sum of bucket bytes (audited,
  not timed), so bucket count at cap ``b`` is ``ceil(S / b)``.
* true compute is ``serialized_total - comm`` from the anchor's own
  serialized (overlap-off) leg; it scales linearly in K.
* the overlap hides up to ``hide_rate * (n-1)/n`` ms of comm: with n
  buckets, the last-produced bucket's reduction cannot overlap its own
  backward (Horovod's fusion-order argument), so hiding capacity grows
  with bucket count while per-bucket alpha cost grows against it —
  THE tradeoff the tuner searches. ``hide_rate`` is calibrated from
  the anchor's measured (serialized - overlapped) gap.

Every term's provenance (which row, which field) is carried into the
prediction so the report can say where each number came from.
"""

from __future__ import annotations

import dataclasses
import math

from horovod_tpu.tune import evidence as evidence_lib

__all__ = ["CostModel", "Prediction", "fit", "FitError"]


class FitError(ValueError):
    """The evidence is too thin to fit a model (no usable anchor row)."""


@dataclasses.dataclass(frozen=True)
class Prediction:
    """One config's predicted step decomposition (ms, per opt step)."""

    total_ms: float
    compute_ms: float
    comm_ms: float          # isolated (un-overlapped) comm cost
    hidden_ms: float        # comm the overlap is predicted to hide
    input_ms: float
    n_buckets: int
    per_example: float      # total_ms / K — the ranking objective
    unevidenced: tuple      # knob names whose effect no evidence covers

    @property
    def exposed_ms(self) -> float:
        return self.comm_ms - self.hidden_ms

    @property
    def evidenced(self) -> bool:
        return not self.unevidenced


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Fitted analytic model + the evidence each term came from."""

    alpha_ms: float          # per-bucket overhead (calibrated)
    beta_ms_per_byte: float  # per-byte wire cost (calibrated)
    payload_bytes: float     # S: structural sum of gradient bucket bytes
    compute_ms: float        # true compute at anchor K (serialized - comm)
    hide_rate_ms: float      # overlap hiding capacity at n -> inf
    input_ms: float
    anchor_k: int
    anchor_config: dict
    anchor_total_ms: float   # the measured total the fit must reproduce
    n_points: int            # pooled comm samples behind alpha/beta
    provenance: dict         # term -> human-readable evidence source

    def buckets(self, bucket_bytes: float) -> int:
        return max(1, math.ceil(self.payload_bytes / max(1.0, bucket_bytes)))

    def comm(self, bucket_bytes: float, wire: str) -> float:
        n = self.buckets(bucket_bytes)
        wire_bytes = self.payload_bytes * evidence_lib.wire_ratio(wire)
        return n * self.alpha_ms + wire_bytes * self.beta_ms_per_byte

    def predict(self, config: dict) -> Prediction:
        b = float(config.get("HVT_BUCKET_BYTES")
                  or self.anchor_config["HVT_BUCKET_BYTES"])
        k = int(config.get("HVT_BACKWARD_PASSES") or self.anchor_k)
        wire = str(config.get("HVT_COMPRESSION", "none"))
        wire_ici = str(config.get("HVT_COMPRESSION_ICI", "none"))
        overlap = bool(config.get("HVT_OVERLAP_REDUCTION", True))
        n = self.buckets(b)
        comm = self.comm(b, wire)
        compute = self.compute_ms * k / max(1, self.anchor_k)
        inp = self.input_ms * k / max(1, self.anchor_k)
        hidden = 0.0
        if overlap and n > 1:
            # The last-produced bucket can't hide behind its own
            # backward: capacity scales as (n-1)/n, and can never
            # exceed the comm there is, nor the compute to hide it in.
            hidden = min(self.hide_rate_ms * (n - 1) / n, comm, compute)
        unevidenced = []
        anchor_wire = str(self.anchor_config.get("HVT_COMPRESSION", "none"))
        if wire != anchor_wire:
            # The byte ratio is structural, but quantize/dequantize
            # compute and convergence cost are not in any recorded row.
            unevidenced.append("HVT_COMPRESSION")
        if wire_ici != str(self.anchor_config.get("HVT_COMPRESSION_ICI",
                                                  "none")):
            # Inert on single-slice meshes (dcn == 1) and no multi-slice
            # row exists to calibrate the ICI hop.
            unevidenced.append("HVT_COMPRESSION_ICI")
        total = compute + comm - hidden + inp
        return Prediction(
            total_ms=total, compute_ms=compute, comm_ms=comm,
            hidden_ms=hidden, input_ms=inp, n_buckets=n,
            per_example=total / max(1, k),
            unevidenced=tuple(unevidenced),
        )


def _fit_alpha_beta(points: list[tuple[float, float]]) -> tuple[float, float]:
    """Least-squares line ms = alpha + beta * bytes, clamped physical
    (alpha >= 0, beta > 0)."""
    n = len(points)
    mx = sum(p[0] for p in points) / n
    my = sum(p[1] for p in points) / n
    sxx = sum((p[0] - mx) ** 2 for p in points)
    if sxx <= 0.0:
        # One distinct bucket size: no slope information — attribute
        # everything to the wire (pessimistic for small buckets, which
        # only makes the tuner conservative about fragmenting).
        return 0.0, my / max(1.0, mx)
    sxy = sum((p[0] - mx) * (p[1] - my) for p in points)
    beta = sxy / sxx
    alpha = my - beta * mx
    if beta <= 0.0:
        return 0.0, my / max(1.0, mx)
    return max(0.0, alpha), beta


def fit(rows: list[dict], trace: dict | None = None) -> CostModel:
    """Fit the model from loaded evidence rows (see `evidence.load_rows`).

    ``trace``, when given (`evidence.load_trace`), cross-checks the
    input attribution: if the traced input phase is slower than the
    row's input column, trust the trace (a row's timing hides staged
    input behind the prefetch queue; the trace sees the drain)."""
    anchor = evidence_lib.anchor_row(rows)
    if anchor is None:
        raise FitError(
            "no usable evidence: need at least one BENCH_*.json row with "
            "step_ms.comm_buckets in the evidence directory"
        )
    points = evidence_lib.comm_points(rows)
    if not points:
        raise FitError("no per-bucket comm samples in any evidence row")
    cfg0 = evidence_lib.config_of(anchor)
    sm = anchor["step_ms"]
    total0 = float(sm["total"])
    comm0 = float(sm.get("comm") or 0.0)
    input0 = float(sm.get("input") or 0.0)
    src = anchor["_source"]
    payload = float(sum(b["bytes"] for b in sm["comm_buckets"]))
    alpha_fit, beta_fit = _fit_alpha_beta(points)
    # Calibrate: isolated per-bucket timings include per-program launch
    # overhead the fused step doesn't pay; scale the fit so the model's
    # comm at the anchor's own bucket cap equals the measured comm.
    b0 = float(cfg0["HVT_BUCKET_BYTES"])
    n0 = max(1, math.ceil(payload / b0))
    raw = n0 * alpha_fit + payload * beta_fit * evidence_lib.wire_ratio(
        cfg0.get("HVT_COMPRESSION", "none"))
    scale = (comm0 / raw) if (raw > 0 and comm0 > 0) else 1.0
    alpha = alpha_fit * scale
    beta = beta_fit * scale
    serialized0 = anchor.get("serialized_step_ms_total")
    if serialized0 is not None:
        compute0 = max(0.0, float(serialized0) - comm0 - input0)
        hidden0 = max(0.0, float(serialized0) - total0)
    else:
        # No overlap-off leg recorded: treat the measured total as fully
        # serialized (no hiding evidence -> the model won't credit any).
        compute0 = max(0.0, total0 - comm0 - input0)
        hidden0 = 0.0
    g0 = (n0 - 1) / n0 if n0 > 1 else 1.0
    hide_rate = hidden0 / g0 if hidden0 > 0 else 0.0
    if trace:
        step_in = trace.get("input") or trace.get("step_input")
        if step_in and step_in.get("mean_ms", 0.0) > input0:
            input0 = float(step_in["mean_ms"])
    prov = {
        "alpha/beta": (f"least-squares over {len(points)} per-bucket "
                       f"comm samples (step_ms.comm_buckets), "
                       f"calibrated to {src} step_ms.comm"),
        "payload": f"{src} comm_buckets structural bytes "
                   f"({int(payload)} B)",
        "compute": (f"{src} serialized_step_ms_total - comm - input"
                    if serialized0 is not None
                    else f"{src} step_ms.total - comm - input"),
        "hide_rate": (f"{src} serialized_step_ms_total - step_ms.total "
                      f"over (n-1)/n at n={n0}"
                      if hidden0 > 0 else "no overlap evidence"),
        "input": ("trace phase attribution"
                  if trace and trace.get("input") else f"{src} step_ms.input"),
        "anchor": f"{src} (k={cfg0['HVT_BACKWARD_PASSES']}, "
                  f"bucket_bytes={int(b0)})",
    }
    return CostModel(
        alpha_ms=alpha, beta_ms_per_byte=beta, payload_bytes=payload,
        compute_ms=compute0, hide_rate_ms=hide_rate, input_ms=input0,
        anchor_k=int(cfg0["HVT_BACKWARD_PASSES"]), anchor_config=cfg0,
        anchor_total_ms=total0, n_points=len(points), provenance=prov,
    )
