"""hvt-tune — the trace-replay autotuner (ISSUE 19).

The repo records everything a tuner needs (per-bucket comm timings,
audited FLOPs and wire bytes, per-phase trace attributions, serialized
vs overlapped step pairs); this package closes the loop so the config
searches itself — the `HOROVOD_AUTOTUNE` counterpart (arxiv
1802.05799), characterization-driven (arxiv 1810.11112) instead of
black-box:

* `space`    — candidate configs enumerated from registry ``tunable=``
               domain metadata (the tuner's reach is a registry edit);
* `evidence` — loaders funneling recorded rows (files named
               ``BENCH_*.json``), audit counts and trace spans into
               model inputs;
* `model`    — the analytic alpha-beta comm/compute model, fitted from
               evidence with per-term provenance;
* `offline`  — rank the space on predictions alone; report + --check;
* `probe`    — the paired-leg A/B discipline with an injectable
               clock;
* `insitu`   — job-start resolution: offline shortlist, real-step
               probe race in a subprocess, journaled + persisted so a
               restart reuses the winner;
* `cli`      — the `hvt-tune` console script (exit contract 0/1/2).

No program in the tree records such rows any more (the script that did
was deleted in PR 31); the only ones are the fixture under
``tests/fixtures/tune_evidence/``, three rows from 8 virtual CPU devices
that carry no speed of the system (ROADMAP D6).

Import-light by design: everything except `insitu.build_probe_step`
(the probe subprocess's leg builder) stays off jax.
"""

from horovod_tpu.tune.probe import PairedResult, paired_compare

__all__ = ["PairedResult", "paired_compare"]
